"""Gossip outer sync: NoLoCo-style pairwise partial averaging
(cf. arXiv 2506.10911) — the transport tier with NO collective that
spans all k workers.

Synchronous DiLoCo's outer step is one all-reduce over every replica:
a single straggler or lost link stalls the fleet. The gossip transport
removes the global collective entirely:

  * every worker keeps its OWN estimate g_i of the global parameters
    and its own outer Nesterov state;
  * each round, the worker applies its own outer gradient
    d_i = g_i − θ_i through its own momentum buffer — a purely local
    update, no wire at all;
  * the only communication is ONE pairwise exchange per worker per
    round: i receives partner j's fresh estimate and partially adopts
    it on the round's scheduled fragment,
        g_i ← g_i + mix · mask_p · (g_j − g_i),
    so per-round wire bytes are fragment-sized and point-to-point.

Pairings (``dcfg.gossip_pairing``):

  butterfly  partner(i, t) = i XOR 2^(t mod log2 k) — pairwise
             exchanges along hypercube dimensions. With mix=0.5 and a
             full-tree fragment, log2(k) consecutive rounds mix ANY
             initial disagreement to the exact global mean: averaging
             along dimension b equalizes every pair differing only in
             bit b, and induction over dimensions reaches the mean of
             all 2^L values — the proven mixing schedule (tested
             exactly in tests/test_gossip.py). Requires k a power of 2.
  random     a fresh uniform perfect matching each round (odd k leaves
             one worker unpaired); mixes in expectation — the NoLoCo
             setting.

Fragment scheduling reuses ``core/fragments.py``: with
``streaming_fragments = P > 1`` round t exchanges only fragment
(t mod P) — NoLoCo's partial parameter averaging — cutting per-round
bytes another P×. The exchanged payload takes a quantize→dequantize
round trip at ``outer_grad_dtype`` (float32 | bfloat16) through the
shared transport codec; int4 is rejected (absolute-parameter
quantization, unlike the zero-centered outer gradients the int4 path
was built for, is not meaningful at 4 bits).

Fault semantics (``core/faults.py`` round projections):
  drop_mask[i] = 0   worker i's link is down this round: every pair
                     containing i skips its exchange (both endpoints
                     keep their own estimate); i's LOCAL outer update
                     still applies — nothing was on the wire.
  active_mask[i] = 0 worker i is preempted: no inner steps, no local
                     update, no exchange for its pairs.

The round is signature-compatible with ``diloco._make_round_body`` and
plugs into ``make_round``/``make_run`` via ``transport="gossip"``;
``GossipState.global_params`` (the consensus mean of the k estimates)
makes it a drop-in for the drivers' eval hooks.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DiLoCoConfig, TrainConfig
from repro.optim import adamw, precision
from . import diloco, fragments, outer_opt


class GossipState(NamedTuple):
    """Gossip carry. Leaves of global_est / outer_state / replica_* all
    lead with the (k,) worker axis — there is no single global copy,
    only k estimates (``global_params`` exposes their consensus mean
    for eval and checkpoint readers)."""
    global_est: Any                # (k, ...) per-worker estimate g_i
    outer_state: outer_opt.OuterState   # (k, ...) leaves, (k,) count
    replica_params: Any            # (k, ...) working params θ_i
    inner_state: adamw.AdamWState  # (k, ...) AdamW moments (+ master)
    outer_t: jnp.ndarray           # round counter (drives the pairing)
    inner_steps_done: jnp.ndarray

    @property
    def global_params(self):
        """Consensus estimate: the mean over workers. Equals every g_i
        exactly once a butterfly sweep has fully mixed a quiescent
        fleet; the natural eval/checkpoint view otherwise."""
        return jax.tree.map(lambda g: g.mean(axis=0), self.global_est)


def validate(dcfg: DiLoCoConfig):
    k = dcfg.k
    if dcfg.gossip_pairing not in ("butterfly", "random"):
        raise ValueError(
            f"gossip_pairing must be butterfly|random, got "
            f"{dcfg.gossip_pairing!r}")
    if dcfg.gossip_pairing == "butterfly" and k & (k - 1):
        raise ValueError(
            f"butterfly pairing needs k a power of 2, got k={k} "
            "(use gossip_pairing='random')")
    if not 0.0 <= dcfg.gossip_mix <= 1.0:
        raise ValueError(f"gossip_mix must be in [0,1], got "
                         f"{dcfg.gossip_mix}")
    if dcfg.outer_grad_dtype == "int4":
        raise ValueError(
            "gossip exchanges absolute parameter estimates, not "
            "zero-centered outer gradients: int4 transport is not "
            "meaningful here (use float32 or bfloat16)")
    if dcfg.error_feedback:
        raise ValueError(
            "error_feedback applies to quantized outer-gradient "
            "transports; the gossip exchange has no residual to carry")
    if dcfg.prune_frac > 0:
        raise ValueError("prune_frac is not supported on the gossip "
                         "transport (deltas never cross the wire)")


def init_state(params, dcfg: DiLoCoConfig) -> GossipState:
    """Start gossip DiLoCo from ``params`` (cf. diloco.init_state):
    every worker begins with the same estimate and zero disagreement."""
    validate(dcfg)
    pol = precision.policy_of(dcfg)
    rep = diloco.broadcast_replicas(params, dcfg.k)
    inner = jax.vmap(lambda p: adamw.init(p, policy=pol))(rep)
    rep = precision.cast_tree(rep, pol.param_dtype)
    k = dcfg.k
    z = lambda p: jnp.zeros((k,) + p.shape, p.dtype)
    return GossipState(
        global_est=diloco.broadcast_replicas(params, k),
        outer_state=outer_opt.OuterState(
            buf=jax.tree.map(z, params), buf2=jax.tree.map(z, params),
            count=jnp.zeros((k,), jnp.int32)),
        replica_params=rep,
        inner_state=inner,
        outer_t=jnp.zeros((), jnp.int32),
        inner_steps_done=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# pairing + mixing (the pure exchange step — proven exact in tests)
# ---------------------------------------------------------------------------

# fold_in tag deriving the round's pairing key from its round key —
# shared by the in-graph round body and the host-side telemetry view,
# so pairing_edges() reconstructs the EXACT edges the exchange used
PAIR_FOLD = 0x90551b


def partner_map(k: int, t, pairing: str, key=None):
    """(k,) int32 partner indices for round ``t``. An involution:
    partner[partner[i]] == i, with partner[i] == i meaning "sit out"
    (k=1, or the odd worker of a random matching). ``t`` may be a
    traced scalar (butterfly); random pairing draws from ``key``."""
    if k == 1:
        return jnp.zeros((1,), jnp.int32)
    idx = jnp.arange(k, dtype=jnp.int32)
    if pairing == "butterfly":
        L = k.bit_length() - 1              # log2(k), k a power of 2
        stage = jnp.asarray(t, jnp.int32) % L
        return idx ^ jnp.left_shift(jnp.int32(1), stage)
    if pairing == "random":
        perm = jax.random.permutation(key, k).astype(jnp.int32)
        m = k // 2
        partner = idx                        # odd worker: self
        partner = partner.at[perm[0:2 * m:2]].set(perm[1:2 * m:2])
        partner = partner.at[perm[1:2 * m:2]].set(perm[0:2 * m:2])
        return partner
    raise ValueError(pairing)


def pairing_edges(k: int, t: int, pairing: str,
                  round_key=None) -> tuple:
    """Host-side telemetry view of round ``t``'s exchange graph:
    sorted (i, j) pairs with i < j (self-paired workers sit out, so
    an odd random matching's leftover never appears). ``round_key``
    is the SAME per-round key the round body receives (the split-chain
    sub-key); the pairing key is derived from it with ``PAIR_FOLD``
    exactly as the in-graph exchange does, so the edges recorded are
    the edges realized — required for random pairing, ignored for
    butterfly (which is a pure function of t)."""
    key = None
    if pairing == "random":
        if round_key is None:
            raise ValueError("random pairing edges need the round key")
        key = jax.random.fold_in(round_key, PAIR_FOLD)
    pm = np.asarray(partner_map(k, t, pairing, key=key))
    return tuple(sorted({(min(i, int(pm[i])), max(i, int(pm[i])))
                         for i in range(k) if int(pm[i]) != i}))


@jax.named_scope("diloco.sync")
def mix_round(est, partner, mask_tree, *, mix: float, ok=None,
              quant_dtype: str = "float32", kernel_mode: str = "ref",
              exchange=None):
    """One pairwise partial-averaging exchange on a (k, ...) estimate
    tree: every worker adopts ``mix`` of its partner's (transport-
    quantized) estimate on the masked region,

        g_i ← g_i + mix · ok_i · mask · (Q(g_partner[i]) − g_i).

    ``ok`` (k,) float gates each exchange (drop/inactive endpoints);
    ``mask_tree`` restricts it to the scheduled fragment (broadcastable
    per-leaf masks from ``fragments.partition_params``). Pure — the
    butterfly exactness proof runs directly on this function.

    ``exchange`` overrides the default ``jnp.take(payload, partner)``
    per-leaf with a custom (k, ...) -> (k, ...) permutation. It must
    realize the SAME partner map — it exists because a general take is
    opaque to the SPMD partitioner (it lowers to an all-gather of the
    whole worker axis), while a structured swap of a pod-sharded axis
    lowers to a pod permutation collective (see
    ``launch/dryrun.py::build_gossip_exchange``)."""
    k = jax.tree.leaves(est)[0].shape[0]
    ok = jnp.ones((k,), jnp.float32) if ok is None else ok
    gate = (ok * (partner != jnp.arange(k, dtype=jnp.int32))
            .astype(jnp.float32))

    def leaf(g, m):
        payload = g
        if quant_dtype != "float32":
            from repro.kernels import ops as kops
            payload = jax.vmap(
                lambda x: kops.quant_roundtrip(x, quant_dtype,
                                               mode=kernel_mode))(g)
        recv = (jnp.take(payload, partner, axis=0) if exchange is None
                else exchange(payload))
        sel = gate.reshape((k,) + (1,) * (g.ndim - 1))
        m = jnp.broadcast_to(jnp.asarray(m, g.dtype), g.shape[1:])
        return g + mix * sel * m[None] * (recv - g)

    return jax.tree.map(leaf, est, mask_tree)


def butterfly_swap(stage: int, k: int):
    """The butterfly stage-``stage`` partner exchange (i XOR 2^stage)
    as a structured reshape+flip of the worker axis — semantically
    identical to ``jnp.take(g, partner_map(k, stage, 'butterfly'))``
    (tested) but transparent to the SPMD partitioner: on a pod-sharded
    worker axis it lowers to a pairwise permutation collective instead
    of an all-worker gather."""
    B = 1 << int(stage)
    if k % (2 * B):
        raise ValueError(f"stage {stage} needs 2^{int(stage) + 1} | k, "
                         f"got k={k}")

    def swap(g):
        r = g.reshape((k // (2 * B), 2, B) + g.shape[1:])
        return jnp.flip(r, axis=1).reshape(g.shape)

    return swap


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------

def make_gossip_round_body(loss_fn, sample_fn, dcfg: DiLoCoConfig,
                           tcfg: TrainConfig, *, total_steps=None,
                           compute_cosine: bool = False,
                           batch_size=None, seq_len=None, mesh=None):
    """Un-jitted gossip round, signature-compatible with
    ``diloco._make_round_body``: round_body(GossipState, key,
    drop_mask, active_mask, weights) -> (GossipState, metrics).

    ``weights`` is accepted for signature compatibility and ignored —
    there is no global average to weight. ``mesh`` must be None: the
    gossip tier is the simulated (replica-stacked) execution; on a pod
    mesh each exchange lowers to a pod-axis collective-permute (see
    launch/dryrun.py's gossip lowering)."""
    validate(dcfg)
    if mesh is not None:
        raise ValueError(
            "transport='gossip' runs replica-stacked (simulated); "
            "pod-sharded gossip is demonstrated by the dryrun lowering "
            "only — drop mesh=")
    if precision.policy_of(dcfg) != precision.policy_of(tcfg):
        raise ValueError(
            "DiLoCoConfig and TrainConfig precision policies disagree")
    inner_step_tok = diloco.make_inner_step(
        lambda p, b: loss_fn(p, b), tcfg, total_steps)
    B = batch_size or tcfg.batch_size
    S = seq_len or tcfg.seq_len
    k = dcfg.k
    P = max(1, int(dcfg.streaming_fragments))
    mode = getattr(dcfg, "kernel_mode", "ref")

    # fragment masks, stacked (P,)+leaf_shape per leaf so a traced
    # round index can select the scheduled fragment with one take.
    # Built lazily from the state's leaf shapes at first trace (the
    # round builder never sees a params example).
    mask_cache: list = []

    def _stacked_masks(global_est):
        if not mask_cache:
            example = jax.tree.map(
                lambda g: np.zeros(g.shape[1:], g.dtype), global_est)
            part = fragments.partition_params(
                example, P, overrides=dcfg.stream_overrides)
            # pure-numpy constants: this runs inside an active jit
            # trace, where any jnp op would produce (and leak) tracers
            mask_cache.append(jax.tree.map(
                lambda p, *ms: np.stack(
                    [np.broadcast_to(np.asarray(m, np.float32),
                                     p.shape) for m in ms]),
                example, *part.masks))
        return mask_cache[0]

    @jax.named_scope("diloco.outer")
    def round_body(state: GossipState, key, drop_mask=None,
                   active_mask=None, weights=None):
        del weights
        H = dcfg.H
        ones = jnp.ones((k,), jnp.float32)
        drop_mask = ones if drop_mask is None else drop_mask
        active_mask = ones if active_mask is None else active_mask

        keys = jax.random.split(key, H)
        toks = jax.vmap(lambda kk: sample_fn(kk, B, S))(keys)
        toks = jnp.swapaxes(toks, 0, 1)[:k]
        rp, is_, ms = diloco.inner_phase(
            inner_step_tok, state.replica_params, state.inner_state,
            {"tokens": toks}, state.inner_steps_done,
            active_mask=active_mask)

        # local outer update: d_i = g_i − θ_i through worker i's OWN
        # Nesterov state — no wire, full weight (each estimate
        # integrates only its own evidence; mixing spreads it)
        masters = is_.master
        rep_src = masters if masters is not None else rp
        deltas = jax.tree.map(lambda g, r: g - r.astype(g.dtype),
                              state.global_est, rep_src)

        def upd(d, st, g):
            return outer_opt.update(
                d, st, g, kind=dcfg.outer_opt, lr=dcfg.outer_lr,
                momentum=dcfg.outer_momentum, b2=dcfg.outer_adam_b2,
                eps=dcfg.outer_adam_eps, kernel_mode=mode)

        new_g, new_outer = jax.vmap(upd)(deltas, state.outer_state,
                                         state.global_est)
        sel = lambda n, o: jax.tree.map(
            lambda a, b: jnp.where(
                active_mask.reshape((k,) + (1,) * (a.ndim - 1)) > 0,
                a, b), n, o)
        new_g = sel(new_g, state.global_est)
        new_outer = outer_opt.OuterState(
            sel(new_outer.buf, state.outer_state.buf),
            sel(new_outer.buf2, state.outer_state.buf2),
            jnp.where(active_mask > 0, new_outer.count,
                      state.outer_state.count))

        # the exchange: partner's fresh estimate, scheduled fragment
        pair_key = jax.random.fold_in(key, PAIR_FOLD)
        partner = partner_map(k, state.outer_t, dcfg.gossip_pairing,
                              key=pair_key)
        comm = drop_mask * active_mask
        ok = comm * jnp.take(comm, partner)
        frag = state.outer_t % P
        mask_p = jax.tree.map(lambda sm: jnp.take(sm, frag, axis=0),
                              _stacked_masks(state.global_est))
        mixed = mix_round(new_g, partner, mask_p, mix=dcfg.gossip_mix,
                          ok=ok, quant_dtype=dcfg.outer_grad_dtype,
                          kernel_mode=mode)

        # re-dispatch: active workers adopt their own mixed estimate
        # (their local update never left the node — nothing to drop)
        pol = precision.policy_of(dcfg)
        adopt = active_mask
        new_rep = jax.tree.map(
            lambda g, r: jnp.where(
                adopt.reshape((k,) + (1,) * (g.ndim - 1)) > 0,
                g.astype(r.dtype), r), mixed, rp)
        new_inner = is_
        if masters is not None:
            new_masters = jax.tree.map(
                lambda g, w: jnp.where(
                    adopt.reshape((k,) + (1,) * (g.ndim - 1)) > 0,
                    g, w), mixed, masters)
            new_inner = is_._replace(master=new_masters)

        consensus = jax.tree.map(lambda g: g.mean(axis=0), mixed)
        spread = diloco._tree_norm(jax.tree.map(
            lambda g, c: g - c[None], mixed, consensus))
        metrics = {
            "inner_loss": ms["loss"].mean(),
            "inner_loss_last": ms["loss"][:, -1].mean(),
            "outer_gnorm": diloco._tree_norm(
                jax.tree.map(lambda d: d.mean(axis=0), deltas)),
            "drop_frac": 1.0 - drop_mask.mean(),
            "gossip_spread": spread,
            "gossip_frag": frag.astype(jnp.float32),
            "exchange_frac": ok.mean(),
        }
        return GossipState(
            global_est=mixed,
            outer_state=new_outer,
            replica_params=new_rep,
            inner_state=new_inner,
            outer_t=state.outer_t + 1,
            inner_steps_done=state.inner_steps_done + H), metrics

    return round_body


def frag_bytes(params, dcfg: DiLoCoConfig) -> list:
    """Per-fragment exchange bytes one worker RECEIVES per round (the
    pairwise payload: the partner's estimate restricted to the
    scheduled fragment, at the transport dtype)."""
    from repro.kernels import ops as kops
    P = max(1, int(dcfg.streaming_fragments))
    part = fragments.partition_params(params, P,
                                      overrides=dcfg.stream_overrides)
    return [kops.transport_bytes(int(n), dcfg.outer_grad_dtype)
            for n in part.sizes]
