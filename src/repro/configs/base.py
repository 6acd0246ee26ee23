"""Config system: model architecture, input shapes, DiLoCo, training."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | encdec | vlm | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads

    # --- attention ---
    pos_emb: str = "rope"       # rope | learned | sincos | none
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0       # fraction of head_dim rotated
    qk_norm: bool = False
    attn_bias: bool = False
    mlp_bias: bool = False
    parallel_block: bool = False  # command-r style (attn & mlp share input)
    window: int = 0             # >0: sliding-window attention
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    act: str = "silu"           # silu | gelu
    mlp_gated: bool = True
    tie_embeddings: bool = False
    max_position: int = 1 << 20

    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0           # per-expert hidden dim
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    # --- MLA (DeepSeek-V2) ---
    mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 64
    v_head_dim: int = 0         # 0 -> head_dim

    # --- encoder-decoder (whisper) ---
    n_enc_layers: int = 0
    n_frames: int = 1500        # stubbed audio frontend output length

    # --- VLM ---
    cross_attn_every: int = 0   # every Nth layer is a cross-attn layer
    n_patches: int = 0          # stubbed vision frontend output length
    vision_dim: int = 0         # 0 -> d_model (projector stubbed)

    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv: int = 4
    shared_attn_every: int = 0  # zamba2: shared attn block every N layers
    slstm_every: int = 0        # xlstm: every Nth block is sLSTM

    # --- numerics / execution ---
    act_batch_axes: tuple = ("data",)   # mesh axes carrying the batch
    act_model_shard: bool = True        # residual d_model over "model"
    act_seq_shard: bool = False         # Megatron SP: residual seq dim
    decode_kv_shard: str = ""           # flash-decoding axis for caches
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    attn_chunk: int = 1024      # kv-chunk size of online-softmax attention
    remat: bool = True
    logit_softcap: float = 0.0
    init_scale: float = 0.02
    # Pallas kernel backend of the model's self-attention (ref | auto |
    # pallas | interpret, as DiLoCoConfig.kernel_mode): the trainer
    # sets it from the job's kernel mode
    kernel_mode: str = "ref"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def resolved_v_head_dim(self) -> int:
        return self.v_head_dim or self.resolved_head_dim


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode


# The four assigned input shapes.
SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# Sliding window used by full-attention archs for long_500k.
LONG_CONTEXT_WINDOW = 4_096


@dataclass(frozen=True)
class DiLoCoConfig:
    """Algorithm 1 hyper-parameters (paper defaults in comments)."""
    k: int = 8                  # number of replicas / islands
    H: int = 500                # inner steps per outer step
    outer_opt: str = "nesterov"  # nesterov | sgd | sgdm | adam
    outer_lr: float = 0.7       # paper: 0.7 for Nesterov
    outer_momentum: float = 0.9
    outer_adam_b2: float = 0.95
    outer_adam_eps: float = 0.1  # paper: raised to 0.1 for stability
    drop_prob: float = 0.0      # async-communication dropout (Fig 8)
    prune_frac: float = 0.0     # sign-pruning of outer grads (Tab 6)
    weighted_avg: bool = False  # weight outer grads by shard size
    sync_inner_state: bool = False  # paper: False (3x comm for no gain)
    # Backend for the fused outer-optimizer / pruning kernels:
    #   ref       — legacy pure-jnp tree maps (bit-identical to the
    #               pre-kernel implementation);
    #   auto      — Pallas kernels on TPU, jnp oracles elsewhere;
    #   pallas    — force the Pallas kernels (TPU);
    #   interpret — Pallas kernels in interpret mode (CPU testing).
    kernel_mode: str = "ref"
    # --- streaming outer sync (Streaming DiLoCo; see core/streaming.py) ---
    # 0 disables streaming (classic full-model outer step every H steps).
    # P >= 1 splits the parameter tree into P fragments, each synced on
    # its own staggered schedule within the round. P=1 with the defaults
    # below reproduces the synchronous path bit-exactly.
    streaming_fragments: int = 0
    stream_alpha: float = 1.0    # merge θ_i ← α·θ_global + (1−α)·θ_i
    stream_tau: int = 0          # inner steps between a fragment's
    #                              snapshot and its application (the
    #                              simulated in-flight collective)
    outer_grad_dtype: str = "float32"  # transport precision of outer
    #                              gradients: float32 | bfloat16 | int4
    stream_overrides: tuple = ()  # ((path-regex, fragment_idx), ...)
    #                              forcing whole leaves into a fragment
    # Error-feedback accumulation for quantized outer gradients: each
    # replica keeps its transport rounding residual locally and adds it
    # to the next round's delta, driving the mean quantization bias to
    # zero at no wire cost. Only meaningful with a low-precision
    # outer_grad_dtype on the streaming path.
    error_feedback: bool = False
    # Transport backend of the streaming outer sync:
    #   simulated — replica-stacked averaging on one device (the CPU
    #               benchmark path; the historical PR 2 semantics);
    #   sharded   — each replica lives on its own "pod" mesh slice
    #               (core/pod_collectives.py) and every fragment is
    #               reduced by a real pod-axis collective issued from
    #               inside the scanned round: float32 rides a weighted
    #               psum all-reduce; quantized transports all-gather the
    #               per-pod payloads (scale blocks stay pod-local) and
    #               reduce locally in the simulated path's exact op
    #               order. Requires a mesh with a "pod" axis at
    #               round-build time (make_round/make_run mesh=...).
    #   async     — barrier-free (core/async_diloco.py): no round
    #               structure at all; each worker's outer gradient is
    #               applied the moment it arrives at the parameter
    #               server, discounted by staleness_lambda^τ / k.
    #               Driven by AsyncEngine + a faults.Scenario, not by
    #               make_round (which rejects it).
    #   gossip    — NoLoCo-style pairwise partial averaging
    #               (core/gossip.py): no collective spans all k
    #               workers; each round every worker averages its
    #               global estimate with ONE partner's. Round-shaped,
    #               so it routes through make_round/make_run.
    transport: str = "simulated"
    # --- async transport (transport="async") ---
    # Delay compensation: an outer gradient applied τ outer steps after
    # its dispatch is weighted λ^τ / k (λ=1 disables discounting; the
    # 1/k is each worker's share of a synchronous round's evidence).
    staleness_lambda: float = 1.0
    # --- gossip transport (transport="gossip") ---
    #   butterfly — partner(i, t) = i XOR 2^(t mod log2 k): pairwise
    #               averaging along hypercube dimensions; log2(k)
    #               consecutive rounds mix any initial disagreement to
    #               the exact global mean (proven in tests).
    #   random    — a fresh uniform perfect matching each round.
    gossip_pairing: str = "butterfly"
    # Fraction of the partner's global estimate adopted per pairwise
    # exchange: g_i ← (1−mix)·g_i + mix·g_j. 0.5 (symmetric averaging)
    # is what the butterfly exactness proof assumes.
    gossip_mix: float = 0.5
    # Packed wire on the sharded transport (quantized dtypes only):
    # True (default) ships the REAL payload — int4 nibble-packs two
    # codes per int8 byte and lays codes + per-block f32 scales out in
    # ONE byte buffer per fragment (all leaf regions coalesced), bf16
    # ships one coalesced bf16 buffer — so the lowered collective
    # carries exactly the bytes ops.transport_bytes(..., packed=True)
    # charges, with one pod-axis all-gather per fragment per sync.
    # False keeps the legacy transport for comparison: per-leaf gathers
    # of the dequantized f32 payload, bytes charged by the static model
    # only. Ignored by transport="simulated" (no wire) and by the f32
    # dtype (which rides the psum all-reduce either way).
    pack_wire: bool = True
    # --- outer-gradient anomaly guard (resilience/guard.py) ---
    # guard_outer=True adds per-replica sanity checks to the classic
    # outer reduce: a replica whose outer delta contains any non-finite
    # value is excluded from the average (exactly as if its weight were
    # zero — its params still re-dispatch from the new global, which is
    # the recovery). On all-finite rounds the guarded reduce is
    # bit-identical to the unguarded one (multiplying the mask by 1.0
    # and where-ing finite values through are exact identities — gated
    # by BENCH_resilience.json).
    guard_outer: bool = False
    # > 0: additionally clip each replica's outer-delta norm to
    # guard_clip × the median replica norm before the reduce (the
    # norm-outlier escalation tier; 0 keeps norms untouched so clean
    # runs stay bit-identical).
    guard_clip: float = 0.0
    # --- replica-state precision policy (see optim/precision.py) ---
    # param_dtype:  storage dtype of the per-replica working params AND
    #               AdamW moments ("bfloat16" halves the params+moments
    #               donated carry).
    # master_dtype: storage dtype of the master-side state; when wider
    #               than param_dtype a per-replica master copy of the
    #               params is carried in the inner AdamW state and the
    #               outer deltas are computed master-vs-master.
    # MUST match the TrainConfig policy of the same run (checked by the
    # round builders). (float32, float32) is bit-identical to the
    # historical all-f32 path.
    param_dtype: str = "float32"
    master_dtype: str = "float32"


@dataclass(frozen=True)
class TrainConfig:
    inner_lr: float = 4e-4      # paper Table 5
    warmup_steps: int = 1_000
    total_steps: int = 88_000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    batch_size: int = 512       # per-replica batch (paper)
    seq_len: int = 1_024
    pretrain_steps: int = 24_000
    seed: int = 0
    # Backend for the fused inner-AdamW kernel (see DiLoCoConfig).
    kernel_mode: str = "ref"
    # Replica-state precision policy (see DiLoCoConfig / the full
    # explanation in optim/precision.py). Governs the dtypes the inner
    # AdamW step reads and writes; keep in sync with the DiLoCoConfig
    # of the same run.
    param_dtype: str = "float32"
    master_dtype: str = "float32"
