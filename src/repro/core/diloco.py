"""DiLoCo (Algorithm 1): Distributed Low-Communication training.

Two optimization processes:
  * inner — every replica independently runs H steps of AdamW on its own
    data shard (no cross-replica communication);
  * outer — every H steps the per-replica parameter deltas
    Δ_i = θ^(t-1) − θ_i^(t) are averaged (the only cross-replica
    collective) and applied by an outer optimizer (Nesterov by default)
    to the global parameter copy, which is then re-dispatched.

The k replicas are carried *stacked* on a leading (k, ...) axis of every
parameter/optimizer leaf, and the inner step is ``vmap``-ed over that
axis. This one formulation serves both execution modes:

  * CPU / single host: vmap runs the k replicas as a batch dimension —
    the benchmark path used to reproduce the paper's figures;
  * TPU multi-pod: the leading axis is sharded over the mesh's "pod"
    axis (one replica per pod). GSPMD partitions the vmap so the inner
    step contains *zero* cross-pod collectives (verified structurally in
    the dry-run) while the outer step's replica-mean lowers to exactly
    one all-reduce over "pod" of model-size bytes — fired once every H
    steps, the paper's communication reduction.

Robustness features from the paper are first-class:
  * ``drop_mask`` (Fig 8) — replicas whose outer gradient is dropped keep
    training from their *own* parameters instead of the global copy;
  * ``active_mask`` (Fig 7, adaptive compute) — inactive replicas are
    parked on the global copy and excluded from the average;
  * ``prune_frac`` (Tab 6) — sign-consistent magnitude pruning of outer
    gradients before averaging (see ``core/compression.py``);
  * ``weights`` — shard-size-weighted averaging for imbalanced
    non-i.i.d. shards (paper §6.1).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DiLoCoConfig, TrainConfig
from repro.optim import adamw, precision
from repro.optim.schedule import make_warmup_cosine
from . import outer_opt
from .compression import sign_prune


class DiLoCoState(NamedTuple):
    """Carried across rounds. replica_* leaves have a leading (k,) axis.

    Under a mixed precision policy (``dcfg.param_dtype`` narrower than
    ``dcfg.master_dtype``) ``replica_params`` and the inner m/v moments
    ride at ``param_dtype`` while ``inner_state.master`` carries the
    per-replica ``master_dtype`` master copies; ``global_params`` and
    the outer state always stay at the caller's (f32) precision.
    """
    global_params: Any            # θ^(t-1), the shared copy
    outer_state: outer_opt.OuterState
    replica_params: Any           # (k, ...) per-replica θ_i
    inner_state: adamw.AdamWState  # (k, ...) per-replica AdamW m/v/count
    outer_t: jnp.ndarray          # outer step counter t
    inner_steps_done: jnp.ndarray  # per-replica scalar (shared schedule)


def broadcast_replicas(tree, k: int):
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p, (k,) + p.shape).copy(), tree)


def init_state(params, dcfg: DiLoCoConfig) -> DiLoCoState:
    """Start DiLoCo from (possibly pretrained) ``params``.

    ``params`` arrive at master precision (f32). Under a mixed policy
    (``dcfg.param_dtype`` narrower than ``dcfg.master_dtype``) the
    replica working params and AdamW moments are allocated at
    ``param_dtype`` and each replica's inner state carries a
    ``master_dtype`` master copy; the global params and outer state
    always stay at the caller's precision.

    ``global_params`` is a copy, not an alias of the caller's tree —
    the scanned driver (``make_run``) donates the state's buffers, and
    donating an aliased tree would delete the caller's params.
    """
    pol = precision.policy_of(dcfg)
    rep = broadcast_replicas(params, dcfg.k)
    # init allocates moments at param_dtype and a master only under a
    # mixed policy; the working replicas are the param_dtype cast
    inner = jax.vmap(functools.partial(adamw.init, policy=pol))(rep)
    rep = precision.cast_tree(rep, pol.param_dtype)
    return DiLoCoState(
        global_params=jax.tree.map(jnp.copy, params),
        outer_state=outer_opt.init(params),
        replica_params=rep,
        inner_state=inner,
        outer_t=jnp.zeros((), jnp.int32),
        inner_steps_done=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# inner optimization (lines 4-9)
# ---------------------------------------------------------------------------

def make_inner_step(loss_fn: Callable, tcfg: TrainConfig,
                    total_steps: int | None = None):
    """One AdamW step for ONE replica. loss_fn(params, batch) ->
    (loss, metrics). Returns step(params, opt_state, batch, step_idx),
    whose ops carry the ``diloco.inner`` scope (its AdamW update
    ``diloco.adamw`` inside it)."""
    sched = make_warmup_cosine(tcfg.inner_lr, tcfg.warmup_steps,
                               total_steps or tcfg.total_steps)
    pol = precision.policy_of(tcfg)

    @jax.named_scope("diloco.inner")
    def step(params, opt_state, batch, step_idx):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        grads, gnorm = adamw.clip_by_global_norm(grads, tcfg.grad_clip)
        lr = sched(step_idx)
        with jax.named_scope("diloco.adamw"):
            params, opt_state = adamw.update(
                grads, opt_state, params, lr=lr, b1=tcfg.b1, b2=tcfg.b2,
                eps=tcfg.eps, weight_decay=tcfg.weight_decay,
                mode=getattr(tcfg, "kernel_mode", "ref"), policy=pol)
        # metrics stay f32 whatever the replica dtype (no-op for f32)
        return params, opt_state, {"loss": loss.astype(jnp.float32),
                                   "gnorm": gnorm, "lr": lr}

    return step


@jax.named_scope("diloco.inner")
def inner_phase(inner_step, replica_params, inner_state, batches,
                step0, *, active_mask=None):
    """H inner steps for all k replicas (scan over H, one replica after
    another).

    batches: tokens (k, H, B, S) or a dict of such; step0: scalar global
    inner-step index of the phase start (for the shared lr schedule).
    ``active_mask`` (k,) float — inactive replicas keep params unchanged
    (adaptive compute pool; they burn no "real" compute on hardware
    because their island simply isn't there).
    Returns (replica_params, inner_state, metrics (k, H) dict).

    Each replica is read from the stacked (k, ...) carry, stepped, and
    written back in place, so the device holds one replica's working
    state and activations at a time beside the stacked state. A vmap
    over k would batch the replicas instead, but the layer scan inside
    the model then moves every stacked leaf to a layer-major layout: a
    second copy of all replicas' params and AdamW moments, which at the
    150M model with k=2 does not fit one 16 GB chip. Its ops (the loop
    over replicas, masks, write-back) carry the ``diloco.inner`` scope.
    """
    def one_replica(params, opt_state, batches_h, active):
        def body(carry, xs):
            p, s = carry
            batch, h = xs
            p2, s2, m = inner_step(p, s, batch, step0 + h)
            p2 = jax.tree.map(lambda a, b: jnp.where(active > 0, a, b),
                              p2, p)
            s2 = jax.tree.map(lambda a, b: jnp.where(active > 0, a, b),
                              s2, s)
            return (p2, s2), m

        H = jax.tree.leaves(batches_h)[0].shape[0]
        (params, opt_state), ms = jax.lax.scan(
            body, (params, opt_state), (batches_h, jnp.arange(H)))
        return params, opt_state, ms

    k = jax.tree.leaves(replica_params)[0].shape[0]
    if active_mask is None:
        active_mask = jnp.ones((k,), jnp.float32)
    take = lambda tree, i: jax.tree.map(lambda x: x[i], tree)
    ms_shape = jax.eval_shape(one_replica, take(replica_params, 0),
                              take(inner_state, 0), take(batches, 0),
                              active_mask[0])[2]
    ms0 = jax.tree.map(lambda s: jnp.zeros((k,) + s.shape, s.dtype),
                       ms_shape)

    def body(i, carry):
        out = one_replica(*take(carry[:2], i), take(batches, i),
                          active_mask[i])
        return jax.tree.map(lambda x, y: x.at[i].set(y), carry, out)

    return jax.lax.fori_loop(0, k, body,
                             (replica_params, inner_state, ms0))


# ---------------------------------------------------------------------------
# outer optimization (lines 11-14)
# ---------------------------------------------------------------------------

@jax.named_scope("diloco.outer")
def outer_step(state: DiLoCoState, dcfg: DiLoCoConfig, *,
               drop_mask=None, active_mask=None, weights=None,
               compute_cosine: bool = False, bomb_mask=None):
    """Average outer gradients and update the global copy.

    drop_mask (k,) float: 1 = outer grad communicated, 0 = dropped
    (replica keeps its own params for the next phase — Fig 8 semantics).
    active_mask (k,) float: 0 = replica not part of the pool this round.
    weights (k,) float: shard-size weights (uniform if None).
    bomb_mask (k,) float: fault injection — 1 poisons the replica's
    outer delta to NaN before the reduce (``faults.Scenario.nan_masks``
    rows; a corrupted-gradient stand-in the guard must catch).
    Returns (new_state, metrics). Its ops carry the ``diloco.outer``
    scope.
    """
    k = dcfg.k
    ones = jnp.ones((k,), jnp.float32)
    drop_mask = ones if drop_mask is None else drop_mask
    active_mask = ones if active_mask is None else active_mask
    weights = ones if weights is None else weights
    m = drop_mask * active_mask * weights                     # (k,)

    kernel_mode = getattr(dcfg, "kernel_mode", "ref")
    masters = state.inner_state.master       # None unless mixed policy

    # Δ_i = θ^(t-1) − θ_i^(t)   (line 12). Under a mixed policy the
    # deltas are computed master-vs-master at full precision — the bf16
    # working copies never enter the outer gradient.
    rep_src = masters if masters is not None else state.replica_params
    deltas = jax.tree.map(lambda g, r: g[None] - r,
                          state.global_params, rep_src)
    if bomb_mask is not None:
        deltas = jax.tree.map(
            lambda d: jnp.where(
                bomb_mask.reshape((k,) + (1,) * (d.ndim - 1)) > 0,
                jnp.asarray(jnp.nan, d.dtype), d), deltas)
    if dcfg.prune_frac > 0:
        deltas = jax.vmap(
            lambda d: sign_prune(d, dcfg.prune_frac, mode=kernel_mode)
        )(deltas)

    guard_metrics = {}
    if getattr(dcfg, "guard_outer", False):
        # per-replica sanity: a delta with ANY non-finite value is
        # excluded from the reduce (weight 0 — identical to the
        # drop-its-weight path, tested) and its values zeroed so
        # NaN·0 cannot leak through the contraction. On finite rounds
        # every op here is an exact identity, keeping the guarded
        # clean path bit-identical to the unguarded one.
        fin = jnp.stack([jnp.all(jnp.isfinite(
            d.astype(jnp.float32).reshape(k, -1)), axis=1)
            for d in jax.tree.leaves(deltas)]).all(axis=0)     # (k,)
        ok = fin.astype(jnp.float32)
        deltas = jax.tree.map(
            lambda d: jnp.where(jnp.isfinite(d.astype(jnp.float32)),
                                d, jnp.zeros((), d.dtype)), deltas)
        m = m * ok
        guard_metrics["guard_rejected"] = (1.0 - ok).sum()
        if getattr(dcfg, "guard_clip", 0.0) > 0:
            # norm-outlier clipping: scale any replica whose delta
            # norm exceeds guard_clip × the median (of surviving
            # replicas) down to that ceiling, before the reduce
            norms = jnp.sqrt(sum(
                jnp.sum(jnp.square(d.astype(jnp.float32)
                                   .reshape(k, -1)), axis=1)
                for d in jax.tree.leaves(deltas)))             # (k,)
            med = jnp.nanmedian(jnp.where(ok > 0, norms, jnp.nan))
            med = jnp.where(jnp.isfinite(med), med, 0.0)
            ceil = dcfg.guard_clip * med
            scale = jnp.where(norms > ceil,
                              ceil / jnp.maximum(norms, 1e-30), 1.0)
            deltas = jax.tree.map(
                lambda d: d * scale.reshape(
                    (k,) + (1,) * (d.ndim - 1)).astype(d.dtype),
                deltas)
            guard_metrics["guard_clipped"] = (scale < 1.0).sum()\
                .astype(jnp.float32)
    denom = jnp.maximum(m.sum(), 1e-9)

    # weighted average over communicating replicas. On the pod-sharded
    # path this contraction is THE cross-pod all-reduce.
    avg = jax.tree.map(
        lambda d: jnp.tensordot(m, d, axes=(0, 0)) / denom, deltas)

    new_global, new_outer = outer_opt.update(
        avg, state.outer_state, state.global_params,
        kind=dcfg.outer_opt, lr=dcfg.outer_lr,
        momentum=dcfg.outer_momentum, b2=dcfg.outer_adam_b2,
        eps=dcfg.outer_adam_eps, kernel_mode=kernel_mode)

    # re-dispatch (line 3 of next phase): communicated & active replicas
    # adopt θ^(t); dropped replicas continue from their own θ_i; inactive
    # replicas park on θ^(t) (they'll be reset when re-activated anyway).
    # The adopted copy is cast to the replica storage dtype (identity
    # under the f32 policy); masters adopt at full precision.
    adopt = jnp.maximum(drop_mask, 1.0 - active_mask)         # (k,)
    new_replicas = jax.tree.map(
        lambda g, r: jnp.where(
            adopt.reshape((k,) + (1,) * g.ndim) > 0,
            g[None].astype(r.dtype), r),
        new_global, state.replica_params)
    new_inner = state.inner_state
    if masters is not None:
        new_masters = jax.tree.map(
            lambda g, w: jnp.where(
                adopt.reshape((k,) + (1,) * g.ndim) > 0, g[None], w),
            new_global, masters)
        new_inner = state.inner_state._replace(master=new_masters)

    metrics = {
        "outer_gnorm": _tree_norm(avg),
        "drop_frac": 1.0 - drop_mask.mean(),
        **guard_metrics,
    }
    if compute_cosine:
        cos_mean, cos_std = _pairwise_cosine(deltas, m)
        metrics["cos_mean"] = cos_mean
        metrics["cos_std"] = cos_std

    return DiLoCoState(
        global_params=new_global,
        outer_state=new_outer,
        replica_params=new_replicas,
        inner_state=new_inner,
        outer_t=state.outer_t + 1,
        inner_steps_done=state.inner_steps_done,
    ), metrics


def _tree_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


def _pairwise_cosine(deltas, mask):
    """Mean/std of pairwise cosine similarity between replicas' outer
    gradients (Fig 10/11). deltas: tree of (k, ...) leaves."""
    flat = jnp.concatenate(
        [d.reshape(d.shape[0], -1).astype(jnp.float32)
         for d in jax.tree.leaves(deltas)], axis=1)           # (k, P)
    norm = jnp.linalg.norm(flat, axis=1, keepdims=True)
    unit = flat / jnp.maximum(norm, 1e-12)
    sim = unit @ unit.T                                        # (k, k)
    k = flat.shape[0]
    pair = mask[:, None] * mask[None, :] * (1 - jnp.eye(k))
    denom = jnp.maximum(pair.sum(), 1e-9)
    mean = (sim * pair).sum() / denom
    var = (jnp.square(sim - mean) * pair).sum() / denom
    return mean, jnp.sqrt(var)


# ---------------------------------------------------------------------------
# round drivers (one outer iteration = H inner steps + outer step)
# ---------------------------------------------------------------------------

def _make_round_body(loss_fn, sample_fn, dcfg: DiLoCoConfig,
                     tcfg: TrainConfig, *, total_steps=None,
                     compute_cosine=False, batch_size=None, seq_len=None,
                     mesh=None, nan_bombs=None):
    """Un-jitted round: the computation shared by ``make_round`` (one
    jit dispatch per round) and ``make_run`` (R rounds scanned inside
    one jit).

    When ``dcfg.streaming_fragments > 0`` the round is the *streaming*
    round (fragment-scheduled outer sync, see ``core/streaming.py``);
    the state is then a ``streaming.StreamState`` (build with
    ``streaming.init_state``). With ``dcfg.transport == "sharded"`` the
    streaming round runs under shard_map over ``mesh``'s "pod" axis
    and the fragment reductions are real cross-pod collectives
    (``core/pod_collectives.py``)."""
    if precision.policy_of(dcfg) != precision.policy_of(tcfg):
        raise ValueError(
            "DiLoCoConfig and TrainConfig precision policies disagree: "
            f"dcfg=({dcfg.param_dtype}, {dcfg.master_dtype}) vs "
            f"tcfg=({tcfg.param_dtype}, {tcfg.master_dtype}); the state "
            "layout (dcfg) must match the inner step (tcfg)")
    transport = getattr(dcfg, "transport", "simulated")
    if nan_bombs is not None and (transport != "simulated"
                                  or getattr(dcfg,
                                             "streaming_fragments", 0)):
        raise ValueError(
            "nan_bombs poison the classic outer reduce "
            "(transport='simulated', streaming_fragments=0); other "
            "transports would silently ignore the injection")
    if transport == "gossip":
        # gossip reuses streaming_fragments as its partial-averaging
        # schedule, so it must be routed before the streaming check
        from . import gossip
        return gossip.make_gossip_round_body(
            loss_fn, sample_fn, dcfg, tcfg, total_steps=total_steps,
            compute_cosine=compute_cosine, batch_size=batch_size,
            seq_len=seq_len, mesh=mesh)
    if transport == "async":
        raise ValueError(
            "transport='async' is barrier-free — there is no round to "
            "build: drive it with core.async_diloco.AsyncEngine (or "
            "run_async) and a faults.Scenario")
    if getattr(dcfg, "streaming_fragments", 0):
        from . import streaming
        return streaming.make_stream_round_body(
            loss_fn, sample_fn, dcfg, tcfg, total_steps=total_steps,
            compute_cosine=compute_cosine, batch_size=batch_size,
            seq_len=seq_len, mesh=mesh)
    if transport != "simulated":
        raise ValueError(
            "transport='sharded' is a streaming-path feature: set "
            "streaming_fragments >= 1 (the classic synchronous outer "
            "step gets its cross-pod all-reduce from GSPMD — see "
            "launch/dryrun.py build_outer_step)")
    inner_step_tok = make_inner_step(
        lambda p, b: loss_fn(p, b), tcfg, total_steps)
    B = batch_size or tcfg.batch_size
    S = seq_len or tcfg.seq_len
    bombs_const = (None if nan_bombs is None
                   else np.asarray(nan_bombs, np.float32))

    def round_body(state: DiLoCoState, key, drop_mask=None,
                   active_mask=None, weights=None):
        H = dcfg.H
        keys = jax.random.split(key, H)
        toks = jax.vmap(lambda kk: sample_fn(kk, B, S))(keys)  # (H,k',B,S)
        toks = jnp.swapaxes(toks, 0, 1)[:dcfg.k]               # (k,H,B,S)
        batches = {"tokens": toks}
        rp, is_, ms = inner_phase(
            inner_step_tok, state.replica_params, state.inner_state,
            batches, state.inner_steps_done, active_mask=active_mask)
        state = state._replace(
            replica_params=rp, inner_state=is_,
            inner_steps_done=state.inner_steps_done + H)
        bomb = None
        if bombs_const is not None:
            # indexed by the state's own round counter (not the scan
            # index) so a resumed run picks up the schedule in place
            bomb = jnp.take(jnp.asarray(bombs_const),
                            jnp.minimum(state.outer_t,
                                        bombs_const.shape[0] - 1),
                            axis=0)
        state, om = outer_step(state, dcfg, drop_mask=drop_mask,
                               active_mask=active_mask, weights=weights,
                               compute_cosine=compute_cosine,
                               bomb_mask=bomb)
        om["inner_loss"] = ms["loss"].mean()
        om["inner_loss_last"] = ms["loss"][:, -1].mean()
        return state, om

    return round_body


def make_round(loss_fn, sample_fn, dcfg: DiLoCoConfig, tcfg: TrainConfig,
               *, total_steps: int | None = None,
               compute_cosine: bool = False,
               batch_size: int | None = None,
               seq_len: int | None = None,
               mesh=None, nan_bombs=None):
    """Build the jitted DiLoCo round.

    sample_fn(key, batch, seq_len) -> (k, B, S) int32 tokens, one batch
    per shard. Returns round(state, key, drop_mask, active_mask, weights)
    -> (state, metrics). Data for all H steps is sampled *inside* the
    round via fold_in so the jitted function stays closed over the
    sampler constants only. ``mesh`` is required (and only used) by the
    sharded streaming transport. ``nan_bombs`` ((rounds, k) float mask,
    classic transport only) injects NaN outer gradients on the masked
    (round, worker) cells — rows indexed by the state's own ``outer_t``.
    """
    round_body = _make_round_body(
        loss_fn, sample_fn, dcfg, tcfg, total_steps=total_steps,
        compute_cosine=compute_cosine, batch_size=batch_size,
        seq_len=seq_len, mesh=mesh, nan_bombs=nan_bombs)
    return jax.jit(round_body)


def split_chain(key, n: int):
    """((2,) carry, (n, 2) subs) uint32 — the carry key and sub-keys
    the sequential host pattern ``key, sub = jax.random.split(key)``
    would produce over n iterations, computed in-graph. Lets the
    scanned driver consume the exact same randomness as the legacy
    per-round Python loop; the carry (returned as ``next_key`` in
    ``make_run`` metrics) seeds the next chunk of a chunked run."""
    def body(carry, _):
        carry, sub = jax.random.split(carry)
        return carry, sub

    return jax.lax.scan(body, key, None, length=n)


def make_run(loss_fn, sample_fn, dcfg: DiLoCoConfig, tcfg: TrainConfig,
             *, rounds_per_call: int,
             total_steps: int | None = None,
             compute_cosine: bool = False,
             batch_size: int | None = None,
             seq_len: int | None = None,
             eval_tokens=None, eval_every: int = 1,
             donate: bool = True, mesh=None, nan_bombs=None):
    """Build the scanned multi-round driver: R = ``rounds_per_call``
    full DiLoCo rounds execute inside ONE jitted call via ``lax.scan``,
    so the host dispatches once per R rounds instead of once per round
    (and never blocks on a host-side eval between rounds).

    Returns ``run(state, key, drop_masks, active_masks, weights) ->
    (state, metrics)`` where drop/active masks are stacked ``(R, k)``
    arrays (or None for all-ones) and every metric comes back stacked
    along a leading (R,) axis, plus ``metrics["next_key"]`` — the
    advanced carry key that seeds the next chunk of a chunked run.
    Round t consumes the key the legacy pattern ``key, sub =
    split(key)`` would have given it, so one ``run`` call is
    bit-identical to R iterations of ``make_round``.

    ``eval_tokens`` (B, S) enables in-graph periodic eval: rounds where
    the *global* round index ``(round_offset + t + 1) % eval_every == 0``
    (and the last round of the call) report ``val_loss``; skipped
    rounds report NaN and pay no eval FLOPs (``lax.cond``). Chunked
    callers (several ``run`` calls covering one logical training run)
    pass ``round_offset`` = rounds already completed so the cadence
    stays aligned across chunk boundaries; the offset is a traced
    scalar, so every chunk reuses one compiled function.

    ``donate=True`` donates the DiLoCoState carry — the k×(params +
    AdamW m/v) replica buffers are updated in place instead of
    double-buffered, halving steady-state optimizer memory.

    When ``dcfg.streaming_fragments > 0`` the scanned rounds are
    streaming rounds (``core/streaming.py``): pass/expect a
    ``streaming.StreamState`` instead of a ``DiLoCoState``. With
    ``dcfg.transport == "sharded"`` pass ``mesh`` (a mesh with a "pod"
    axis) and place the state with
    ``pod_collectives.shard_stream_state`` first — the scanned rounds
    then issue real per-fragment pod-axis collectives from inside the
    one jit.
    """
    round_body = _make_round_body(
        loss_fn, sample_fn, dcfg, tcfg, total_steps=total_steps,
        compute_cosine=compute_cosine, batch_size=batch_size,
        seq_len=seq_len, mesh=mesh, nan_bombs=nan_bombs)
    R = int(rounds_per_call)
    ev_toks = None if eval_tokens is None else jnp.asarray(eval_tokens)

    @jax.named_scope("diloco.eval")
    def eval_loss(p):
        return loss_fn(p, {"tokens": ev_toks})[0].astype(jnp.float32)

    def run_fn(state: DiLoCoState, key, drop_masks=None,
               active_masks=None, weights=None, round_offset=0):
        ones = jnp.ones((R, dcfg.k), jnp.float32)
        drop_masks = ones if drop_masks is None else drop_masks
        active_masks = ones if active_masks is None else active_masks
        round_offset = jnp.asarray(round_offset, jnp.int32)
        next_key, subs = split_chain(key, R)

        def body(st, xs):
            sub, drop, act, t = xs
            st, m = round_body(st, sub, drop, act, weights)
            if ev_toks is not None:
                g = round_offset + t + 1          # global 1-based round
                do_eval = (g % eval_every == 0) | (t == R - 1)
                m["val_loss"] = jax.lax.cond(
                    do_eval, eval_loss,
                    lambda p: jnp.full((), jnp.nan, jnp.float32),
                    st.global_params)
            return st, m

        state, ms = jax.lax.scan(
            body, state,
            (subs, drop_masks, active_masks, jnp.arange(R)))
        ms["next_key"] = next_key     # seeds the next chunk (not (R,))
        return state, ms

    if donate:
        return jax.jit(run_fn, donate_argnums=(0,))
    return jax.jit(run_fn)


def make_eval(loss_fn):
    @jax.jit
    @jax.named_scope("diloco.eval")
    def eval_fn(params, tokens):
        loss, _ = loss_fn(params, {"tokens": tokens})
        return loss
    return eval_fn


# ---------------------------------------------------------------------------
# single-worker pretraining / baselines share the same inner step
# ---------------------------------------------------------------------------

def make_single_worker_step(loss_fn, tcfg: TrainConfig,
                            total_steps: int | None = None, *,
                            donate: bool = True):
    """Plain (non-DiLoCo) training step — used for the paper's pretraining
    stage and the single-worker baselines of Table 2 / Fig 2.

    ``donate=True`` donates (params, opt_state), so the per-step update
    runs in place instead of double-buffering params + AdamW m/v —
    callers must rebind both to the returned values (every in-repo loop
    already does)."""
    inner = make_inner_step(lambda p, b: loss_fn(p, b), tcfg, total_steps)

    def step(params, opt_state, batch, idx):
        return inner(params, opt_state, batch, idx)

    if donate:
        return jax.jit(step, donate_argnums=(0, 1))
    return jax.jit(step)


def outer_wire_bytes(params, dcfg: DiLoCoConfig) -> float:
    """Bytes ONE replica ships for the CLASSIC synchronous outer step:
    the full outer gradient at the transport dtype (the config
    validation in launch/train.py pins that to float32 off the
    streaming path — quantized wire lives on the fragment transports,
    which account per fragment via ``streaming.sync_plan`` /
    ``gossip.frag_bytes``). The telemetry layer stamps this on each
    round's transfer span so every transport's trace carries byte
    annotations from the same ``kops.transport_bytes`` accounting."""
    from repro.kernels import ops as kops
    n = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    return float(kops.transport_bytes(n, dcfg.outer_grad_dtype))
