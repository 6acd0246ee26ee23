"""Pallas TPU kernels for DiLoCo's compute hot-spots.

flash_attention.py  blocked online-softmax attention (inner-loop compute)
fused_adamw.py      one-VMEM-pass inner AdamW update (memory-bound)
sign_prune.py       fused sign election + magnitude pruning (Table 6)
outer_nesterov.py   fused outer Nesterov update
ops.py              backend dispatch (kernel on TPU, jnp oracle elsewhere)
ref.py              pure-jnp oracles for every kernel
compat.py           Pallas TPU API names across jax releases

The kernels are wired into the training hot path via ``kernel_mode`` on
TrainConfig (inner AdamW), DiLoCoConfig (outer Nesterov, sign pruning)
and ModelConfig (self-attention, set by the trainer): ``ref`` = legacy
jnp, ``auto`` = kernels on TPU / oracles elsewhere,
``pallas``/``interpret`` = forced.
"""
