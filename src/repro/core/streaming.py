"""Streaming outer sync: fragment-scheduled, overlap-capable, quantized
DiLoCo communication (Streaming DiLoCo, Douillard et al., 2025).

Classic DiLoCo's one remaining cost is the every-H-steps outer
all-reduce of full model-size bytes — a full-model barrier. This module
replaces it with a *stream* of fragment-sized collectives:

  * the parameter tree is split into P contiguous fragments
    (``core/fragments.py``), each with its own outer Nesterov state;
  * fragment p's outer step fires at inner offset p·H/P of the round,
    so at any instant only ~1/P of the model is on the wire — peak
    bytes-per-sync drop P×;
  * the collective is *overlapped* with compute: the fragment's outer
    gradient is snapshotted at the send offset, and the reduced result
    is applied ``tau`` inner steps later (possibly in the next round) —
    modeling an all-reduce that runs concurrently with inner training
    on stale fragment params;
  * instead of hard-resetting replicas to the new global fragment, the
    synced fragment is *merged* with each replica's local progress;
  * outer gradients take a per-replica quantize→dequantize round trip
    at the transport precision before the simulated all-reduce
    (``kernels/quantize.py``), cutting wire bytes another 2×–7.5×.
    int4 scale blocks are formed over each replica's flattened leaf, so
    they never mix two replicas' values; blocks may still span a leaf's
    fragment-band boundary within one replica — a known approximation
    of a sender that packs each fragment region separately.

Knob ↔ paper-term map (DiLoCoConfig):

  streaming_fragments  P, the paper's number of fragments; 0 = classic
                       synchronous DiLoCo, 1 = one full-model fragment
                       (bit-identical to synchronous with the defaults
                       below — tested).
  stream_alpha         α, the mixing weight of the merge
                       θ_i ← α·θ_global + (1−α)·θ_i  (paper eq. 4;
                       α=1 recovers the classic hard reset).
  stream_tau           the overlap window in inner steps between a
                       fragment's snapshot and its application (the
                       paper simulates the collective finishing within
                       τ steps of compute; τ=0 = blocking collective).
  outer_grad_dtype     transport precision of the outer gradients on
                       the wire: float32 | bfloat16 | int4 (per-block
                       f32 scales; the paper's low-precision
                       collectives).
  stream_overrides     ((path-regex, fragment), ...) pattern overrides
                       for the fragment partitioner.
  transport            collective backend: "simulated" (replica-stacked
                       averaging on one device — this module's original
                       semantics) or "sharded" (each replica on its own
                       "pod" mesh slice, fragments reduced by real
                       pod-axis collectives under shard_map — see
                       core/pod_collectives.py; pass mesh=... to
                       make_round/make_run).
  pack_wire            sharded quantized transport only: True (default)
                       ships the real packed payload — every leaf
                       region's int4 codes+scales (or bf16 elements)
                       coalesced into ONE wire buffer per fragment,
                       reduced by a single pod-axis all-gather — so the
                       lowered HLO carries exactly the bytes the packed
                       static model charges; False keeps the legacy
                       per-leaf dequantized-f32 gathers for comparison.

The streaming round plugs into the scanned driver: ``diloco.make_run``
(and ``make_round``) dispatch here when ``streaming_fragments > 0``, so
R streaming rounds still execute inside ONE jit. State is
``StreamState`` (build with ``init_state``), which carries the classic
``DiLoCoState`` plus the in-flight reduced fragments (``pending``) and
a per-fragment first-send latch (``armed``).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DiLoCoConfig, TrainConfig
from repro.optim import precision
from . import diloco, fragments, outer_opt, pod_collectives
from .compression import sign_prune


class StreamState(NamedTuple):
    """Streaming carry = classic DiLoCo state + stream bookkeeping.

    pending: param-shaped tree holding, per fragment region, the most
    recently reduced (averaged, transport-quantized) outer gradient —
    written at the fragment's send, consumed at its apply τ steps later.
    armed: (P,) float latch, 1 after a fragment's first send — applies
    before the first send (wrapped applies in round 0) are no-ops.
    residual: per-replica (k, ...) error-feedback accumulator for the
    quantized transport (``dcfg.error_feedback``): each replica keeps
    the rounding error its quantizer introduced and adds it to the next
    round's delta, so the mean transport bias decays to zero at no wire
    cost. None when error feedback is off or transport is float32.
    inflight: the double-buffered in-flight collective slot (quantized
    transports at τ>0 only, else None). One entry per fragment, each
    ``(payload, mask)``: the RAW gathered wire — the (k, W) packed byte
    buffer on the packed transport, the (k, ...) per-leaf stacked
    payload elsewhere — plus the (k,) communication-mask snapshot taken
    at the send. The collective is *issued* at the fragment's send
    offset and its result is first *consumed* (decoded + mask-reduced
    into ``pending``) at the apply τ inner steps later, so the τ
    inner-step dots sit between collective-start and first use in
    program order. None entries mark override-emptied fragments. The
    mask snapshot makes wrapped fragments (applied in the NEXT round,
    under a different drop mask) reduce with the mask of the round
    that sent them — exactly the values the eager path produced.
    """
    base: diloco.DiLoCoState
    pending: Any
    armed: jnp.ndarray
    residual: Any = None
    inflight: Any = None

    # conveniences so StreamState is a drop-in for DiLoCoState readers
    @property
    def global_params(self):
        return self.base.global_params

    @property
    def outer_state(self):
        return self.base.outer_state

    @property
    def replica_params(self):
        return self.base.replica_params

    @property
    def inner_state(self):
        return self.base.inner_state

    @property
    def outer_t(self):
        return self.base.outer_t

    @property
    def inner_steps_done(self):
        return self.base.inner_steps_done


def deferred_consume(dcfg: DiLoCoConfig) -> bool:
    """True when the streaming round runs the real issue/consume split:
    each fragment's collective is issued at the send offset and its raw
    result is first consumed τ inner steps later at the apply. Only the
    quantized transports defer — their sharded reduction is already a
    gather + local decode, so the decode moves wholesale to the apply;
    f32 keeps the eager weighted psum whose bit-identity to the
    simulated tensordot is a standing cross-commit gate. τ=0 has no
    window to overlap, so it keeps the eager path (and the PR 7 state
    tree) too."""
    return (int(dcfg.streaming_fragments) >= 1
            and int(dcfg.stream_tau) > 0
            and dcfg.outer_grad_dtype in ("bfloat16", "int4"))


def _packed_wire(dcfg: DiLoCoConfig) -> bool:
    return (getattr(dcfg, "transport", "simulated") == "sharded"
            and getattr(dcfg, "pack_wire", True)
            and dcfg.outer_grad_dtype in ("bfloat16", "int4"))


def _init_inflight(params, dcfg: DiLoCoConfig):
    """Zero-filled in-flight slots matching what round_core stores per
    fragment: the packed transport buffers the (k, W) gathered wire
    bytes, every other transport the (k, ...) stacked per-leaf payload
    restricted to the fragment's active leaves; both pair the buffer
    with a (k,) mask snapshot. None when the config has no deferral."""
    from repro.kernels import ops as kops
    if not deferred_consume(dcfg):
        return None
    P = max(1, int(dcfg.streaming_fragments))
    part = fragments.partition_params(params, P,
                                      overrides=dcfg.stream_overrides)
    k = int(dcfg.k)
    mask0 = lambda: jnp.zeros((k,), jnp.float32)
    slots = []
    if _packed_wire(dcfg):
        regs = fragments.fragment_regions(part, params)
        wdt = kops.wire_dtype(dcfg.outer_grad_dtype)
        for p in range(P):
            W = sum(kops.wire_elems(r.elems, dcfg.outer_grad_dtype)
                    for r in regs[p])
            slots.append(None if W == 0 else
                         (jnp.zeros((k, W), wdt), mask0()))
    else:
        leaves = jax.tree_util.tree_leaves(params)
        for p in range(P):
            mk_l = jax.tree_util.tree_leaves(part.masks[p])
            active = [bool(np.any(np.asarray(mm))) for mm in mk_l]
            if not any(active):
                slots.append(None)
                continue
            payload = tuple(
                jnp.zeros((k,) + l.shape, jnp.float32) if on else None
                for on, l in zip(active, leaves))
            slots.append((payload, mask0()))
    return tuple(slots)


def init_state(params, dcfg: DiLoCoConfig) -> StreamState:
    """Start streaming DiLoCo from ``params`` (cf. diloco.init_state)."""
    P = max(1, int(dcfg.streaming_fragments))
    residual = None
    if dcfg.error_feedback and dcfg.outer_grad_dtype != "float32":
        residual = jax.tree.map(
            lambda p: jnp.zeros((dcfg.k,) + p.shape, jnp.float32),
            params)
    return StreamState(
        base=diloco.init_state(params, dcfg),
        pending=jax.tree.map(jnp.zeros_like, params),
        armed=jnp.zeros((P,), jnp.float32),
        residual=residual,
        inflight=_init_inflight(params, dcfg))


def quantize_with_feedback(d, res, dtype: str, *, mode: str = "ref"):
    """One error-feedback transport step: quantize ``d + res`` (the
    fresh delta plus the residual the quantizer left behind last time)
    and return (quantized, new_residual). Over repeated rounds the
    residual re-injects every rounding error into a later transport, so
    the *mean* transported value converges to the true mean delta —
    the quantization bias vanishes at no wire cost."""
    from repro.kernels import ops as kops
    d_in = d + res
    q = kops.quant_roundtrip(d_in, dtype, mode=mode)
    return q, d_in - q


def make_stream_round_body(loss_fn, sample_fn, dcfg: DiLoCoConfig,
                           tcfg: TrainConfig, *, total_steps=None,
                           compute_cosine: bool = False,
                           batch_size=None, seq_len=None, mesh=None):
    """Un-jitted streaming round, signature-compatible with
    ``diloco._make_round_body``: round_body(StreamState, key, drop_mask,
    active_mask, weights) -> (StreamState, metrics).

    The round is a static sequence of inner-step segments delimited by
    the fragment schedule's send/apply events; with P=1, α=1, τ=0 and
    float32 transport it is one full-H segment followed by a full-tree
    send+apply — bit-identical to the synchronous round (tested).

    ``dcfg.transport`` selects the collective backend: "simulated"
    averages the replica-stacked arrays on one device; "sharded" runs
    the round under ``shard_map`` over ``mesh``'s "pod" axis — each pod
    carries a contiguous band of k/pods replicas, inner steps are pure
    pod-local compute, and every fragment is reduced by a real pod-axis
    collective (``core/pod_collectives.py``) at its staggered offset.
    """
    P = int(dcfg.streaming_fragments)
    if P < 1:
        raise ValueError("make_stream_round_body needs "
                         "streaming_fragments >= 1")
    if dcfg.outer_opt != "nesterov":
        raise NotImplementedError(
            "streaming outer sync supports outer_opt='nesterov' only "
            f"(got {dcfg.outer_opt!r})")
    transport = getattr(dcfg, "transport", "simulated")
    if transport not in ("simulated", "sharded"):
        raise ValueError(f"unknown transport {transport!r}: expected "
                         "'simulated' or 'sharded'")
    sharded = transport == "sharded"
    if sharded:
        n_pods = pod_collectives.validate_mesh(mesh, dcfg.k)
        if compute_cosine:
            raise NotImplementedError(
                "compute_cosine needs cross-pod delta gathers; run it "
                "on transport='simulated'")
        axis = pod_collectives.POD_AXIS
    else:
        n_pods, axis = 1, None
    # packed wire: the sharded quantized transport ships real
    # codes+scales bytes, one coalesced all-gather per fragment
    packed = _packed_wire(dcfg)
    # defer: issue the collective at the send, first consume its raw
    # result at the apply τ steps later (see deferred_consume)
    defer = deferred_consume(dcfg)
    k_loc = dcfg.k // n_pods
    sched = fragments.schedule(P, dcfg.H, dcfg.stream_tau)
    alpha = float(dcfg.stream_alpha)
    qdtype = dcfg.outer_grad_dtype
    kernel_mode = getattr(dcfg, "kernel_mode", "ref")
    mixed = precision.policy_of(dcfg).mixed
    inner_step_tok = diloco.make_inner_step(
        lambda p, b: loss_fn(p, b), tcfg, total_steps)
    B = batch_size or tcfg.batch_size
    S = seq_len or tcfg.seq_len

    @jax.named_scope("diloco.outer")
    def round_core(sstate: StreamState, key, drop_mask,
                   active_mask, weights):
        from repro.kernels import ops as kops

        st = sstate.base
        part = fragments.partition_params(
            st.global_params, P, overrides=dcfg.stream_overrides)
        k, H = dcfg.k, dcfg.H
        # masks/weights stay full (k,) on every pod — the mask algebra
        # (denom, drop_frac) is then the exact op sequence of the
        # simulated path; only replica-banded tensors go local
        m = drop_mask * active_mask * weights
        denom = jnp.maximum(m.sum(), 1e-9)
        adopt = jnp.maximum(drop_mask, 1.0 - active_mask)
        if axis is not None:
            m_loc = pod_collectives.band_slice(m, k_loc, axis)
            act_loc = pod_collectives.band_slice(active_mask, k_loc,
                                                 axis)
            adopt_loc = pod_collectives.band_slice(adopt, k_loc, axis)
        else:
            m_loc, act_loc, adopt_loc = m, active_mask, adopt

        keys = jax.random.split(key, H)
        toks = jax.vmap(lambda kk: sample_fn(kk, B, S))(keys)
        toks = jnp.swapaxes(toks, 0, 1)                    # (k',H,B,S)
        if axis is not None:
            # every pod samples the full shard set (replicated compute,
            # bitwise the simulated data) and keeps its own band
            toks = pod_collectives.band_slice(toks, k_loc, axis)
        else:
            toks = toks[:k]                                 # (k,H,B,S)
        batches = {"tokens": toks}

        gp = st.global_params
        rp = st.replica_params
        ist = st.inner_state
        buf = st.outer_state.buf
        buf2 = st.outer_state.buf2
        count = st.outer_state.count
        pending = sstate.pending
        armed = sstate.armed
        residual = sstate.residual
        if defer and sstate.inflight is None:
            raise ValueError(
                "deferred streaming round (quantized, tau>0) needs the "
                "in-flight slot: build the state with "
                "streaming.init_state under the same DiLoCoConfig")
        inflight = (list(sstate.inflight) if sstate.inflight is not None
                    else None)
        pos = 0
        seg_ms = []
        deltas_acc = (jax.tree.map(jnp.zeros_like, rp)
                      if compute_cosine else None)

        # per-fragment static leaf activity: a sync only computes on
        # leaves its fragment touches (masks are concrete at trace
        # time), so whole-leaf work for the other fragments is skipped
        # outright; the residual waste is confined to stacked leaves a
        # fragment splits by layer.
        treedef = jax.tree_util.tree_structure(gp)
        leaves = jax.tree_util.tree_leaves
        leaf_active = [tuple(bool(np.any(np.asarray(l))) for l in
                             leaves(mk)) for mk in part.masks]
        lr_, mu = dcfg.outer_lr, dcfg.outer_momentum
        frag_regions = (fragments.fragment_regions(part, gp)
                        if packed else None)

        @jax.named_scope("diloco.sync")
        def packed_issue(frag, gp_, src_, residual_):
            """Issue one packed-wire fragment collective: per leaf
            region, quantize the local band's delta (+ error-feedback
            residual) to the real wire format (``kops.wire_encode``),
            concatenate every region's buffer, and start ONE pod-axis
            all-gather of the coalesced bytes. Scale blocks are formed
            per replica per region on the local shard (pod-local by
            construction); residuals never touch the wire. Returns the
            RAW gathered (k, W) wire — undecoded, so the consumer can
            run τ steps later — and the updated residual; (None,
            residual) for an override-emptied fragment."""
            regs = frag_regions[frag]
            if not regs:          # override-emptied fragment: no wire
                return None, residual_
            gp_l, src_l = leaves(gp_), leaves(src_)
            res_l = (list(leaves(residual_))
                     if residual_ is not None else None)
            comm = (m_loc > 0)[:, None]
            wires, res_entries = [], []
            for r in regs:
                d = gp_l[r.leaf][None] - src_l[r.leaf]
                if dcfg.prune_frac > 0:
                    d = jax.vmap(lambda dd: sign_prune(
                        dd, dcfg.prune_frac, mode=kernel_mode))(d)
                d_r = fragments.region_take(d, r, lead_axes=1)
                if res_l is not None:
                    res_r = fragments.region_take(res_l[r.leaf], r,
                                                  lead_axes=1)
                    d_r = d_r + res_r
                wire, local = jax.vmap(lambda v: kops.wire_encode(
                    v, qdtype, mode=kernel_mode))(d_r)
                wires.append(wire)
                if res_l is not None:
                    # communicating replicas consume their residual;
                    # dropped/inactive ones keep accumulating (their
                    # payload never enters the mean)
                    res_entries.append((r, jnp.where(
                        comm, d_r - local, res_r)))
            gathered = pod_collectives.gather_wire(
                jnp.concatenate(wires, axis=1), axis=axis)
            for r, nres in res_entries:
                res_l[r.leaf] = fragments.region_put(
                    res_l[r.leaf], r, nres, lead_axes=1)
            new_res = (jax.tree_util.tree_unflatten(treedef, res_l)
                       if res_l is not None else None)
            return gathered, new_res

        @jax.named_scope("diloco.sync")
        def packed_reduce(frag, gathered, m_r, denom_r, pending_):
            """Consume one fragment's gathered wire: dequantize each
            region and mask-reduce in the simulated path's op order,
            writing the result into ``pending``. ``m_r``/``denom_r``
            are the communication mask and its sum AT THE SEND (the
            in-flight snapshot when deferred) so a wrapped fragment is
            reduced with the round that produced it."""
            regs = frag_regions[frag]
            pend_l = list(leaves(pending_))
            off = 0
            for r in regs:
                W = kops.wire_elems(r.elems, qdtype)
                # the simulated transport's decode+reduce, verbatim
                # (fused to one kernel launch under kernel_mode)
                a = kops.wire_reduce(
                    gathered[:, off:off + W], r.elems, qdtype,
                    m_r, denom_r, mode=kernel_mode)
                off += W
                pend_l[r.leaf] = fragments.region_put(
                    pend_l[r.leaf], r, a)
            return jax.tree_util.tree_unflatten(treedef, pend_l)

        @jax.named_scope("diloco.sync")
        def wire_roundtrip(d, res):
            """The simulated wire of one leaf's stacked deltas, per
            replica, with error feedback where ``res`` is kept: returns
            (payload, new residual or None)."""
            if res is None:
                return jax.vmap(lambda dd: kops.quant_roundtrip(
                    dd, qdtype, mode=kernel_mode))(d), None
            return jax.vmap(lambda dd, rr: quantize_with_feedback(
                dd, rr, qdtype, mode=kernel_mode))(d, res)

        @jax.named_scope("diloco.sync")
        def wire_mean(m_r, d, denom_r):
            """The simulated all-reduce: the masked replica mean."""
            return jnp.tensordot(m_r, d, axes=(0, 0)) / denom_r

        for steps, acts in sched.phases:
            if steps:
                seg = jax.tree.map(lambda t: t[:, pos:pos + steps],
                                   batches)
                rp, ist, ms = diloco.inner_phase(
                    inner_step_tok, rp, ist, seg,
                    st.inner_steps_done + pos, active_mask=act_loc)
                seg_ms.append(ms)
                pos += steps
            for ev in acts:
                mk_l = leaves(part.masks[ev.fragment])
                act_l = leaf_active[ev.fragment]
                if ev.kind == "send" and packed:
                    gathered, residual = packed_issue(
                        ev.fragment, gp,
                        ist.master if mixed else rp, residual)
                    if gathered is None:
                        pass          # override-emptied fragment
                    elif defer:
                        # double-buffer: park the RAW wire + the mask
                        # snapshot; the decode runs at the apply, τ
                        # inner steps of dots from here
                        inflight[ev.fragment] = (gathered, m)
                    else:
                        pending = packed_reduce(
                            ev.fragment, gathered, m, denom, pending)
                    armed = armed.at[ev.fragment].set(1.0)
                elif ev.kind == "send":
                    # snapshot Δ_i = θ_frag − θ_i,frag (master-vs-master
                    # under a mixed policy), quantize for the wire, and
                    # reduce — the simulated all-reduce starts here and
                    # lands τ steps later at the apply
                    da_l = (leaves(deltas_acc) if compute_cosine
                            else [None] * len(mk_l))
                    src_l = (leaves(ist.master) if mixed
                             else leaves(rp))
                    res_l = (leaves(residual) if residual is not None
                             else [None] * len(mk_l))
                    new_pd, new_da, new_res, new_il = [], [], [], []
                    for on, q, g, r, pe, da, res in zip(
                            act_l, mk_l, leaves(gp), src_l,
                            leaves(pending), da_l, res_l):
                        if not on:
                            new_pd.append(pe)
                            new_da.append(da)
                            new_res.append(res)
                            new_il.append(None)
                            continue
                        d = g[None] - r
                        if dcfg.prune_frac > 0:
                            d = jax.vmap(
                                lambda dd: sign_prune(
                                    dd, dcfg.prune_frac,
                                    mode=kernel_mode))(d)
                        # quantize per replica (vmap over the k axis):
                        # a real sender's int4 scale blocks never span
                        # two replicas' deltas, so neither do ours
                        d, nres = wire_roundtrip(d, res)
                        if res is not None:
                            # only replicas whose packet enters the
                            # average consume their residual; dropped /
                            # inactive replicas never sent, so their
                            # error keeps accumulating for later rounds
                            comm = (m_loc > 0).reshape(
                                (k_loc,) + (1,) * (nres.ndim - 1))
                            new_res.append(
                                jnp.where((q > 0) & comm, nres, res))
                        else:
                            new_res.append(res)
                        if defer:
                            # issue only: gather the stacked payload
                            # (identity on the simulated transport) and
                            # park it; the reduce runs at the apply
                            new_il.append(
                                pod_collectives.fragment_gather(
                                    d, dtype=qdtype, axis=axis)
                                if axis is not None else d)
                            new_pd.append(pe)
                        else:
                            if axis is not None:
                                # THE cross-pod collective: psum for
                                # f32, gather + local dequant-reduce
                                # for the quantized wire (pod-local
                                # scale blocks)
                                a = pod_collectives.fragment_mean(
                                    d, m, m_loc, denom, dtype=qdtype,
                                    axis=axis)
                            else:
                                a = wire_mean(m, d, denom)
                            new_pd.append(jnp.where(q > 0, a, pe))
                        if compute_cosine:
                            new_da.append(jnp.where(q > 0, d, da))
                    if defer and any(x is not None for x in new_il):
                        inflight[ev.fragment] = (tuple(new_il), m)
                    pending = jax.tree_util.tree_unflatten(treedef,
                                                           new_pd)
                    if residual is not None:
                        residual = jax.tree_util.tree_unflatten(
                            treedef, new_res)
                    if compute_cosine:
                        deltas_acc = jax.tree_util.tree_unflatten(
                            treedef, new_da)
                    armed = armed.at[ev.fragment].set(1.0)
                else:                                       # apply
                    if defer and inflight[ev.fragment] is not None:
                        # CONSUME: first use of the collective issued
                        # τ inner steps ago — decode the raw payload
                        # and mask-reduce with the mask snapshotted at
                        # the send (a wrapped fragment crossed a round
                        # boundary; this round's drop mask is not the
                        # one that sent it)
                        payload, m_snap = inflight[ev.fragment]
                        # pin the consume AFTER the overlap window in
                        # the schedule, not just the source: the decode
                        # depends only on the gathered bytes, so
                        # without this barrier the backend is free to
                        # hoist it back next to the collective and
                        # re-serialize the wire. Tying it to the
                        # post-window replica params (an output of the
                        # τ inner steps) makes "issued at the send,
                        # consumed τ dots later" a dataflow fact the
                        # lowered program order must honor (identity on
                        # values; HLO-gated in hlo_analysis)
                        payload = jax.lax.optimization_barrier(
                            (payload, leaves(rp)[0]))[0]
                        denom_snap = jnp.maximum(m_snap.sum(), 1e-9)
                        if packed:
                            pending = packed_reduce(
                                ev.fragment, payload, m_snap,
                                denom_snap, pending)
                        else:
                            pend_l = list(leaves(pending))
                            for li, (on, q) in enumerate(
                                    zip(act_l, mk_l)):
                                if not on:
                                    continue
                                a = wire_mean(m_snap, payload[li],
                                              denom_snap)
                                pend_l[li] = jnp.where(q > 0, a,
                                                       pend_l[li])
                            pending = jax.tree_util.tree_unflatten(
                                treedef, pend_l)
                    # fused-dispatch Nesterov (same math as
                    # outer_opt.update(kind="nesterov")) on the
                    # fragment's leaves only, latched on the first send
                    ok = armed[ev.fragment] > 0
                    mst_l = leaves(ist.master) if mixed else None
                    new_gp, new_buf, new_rp, new_mst = [], [], [], []
                    for li, (on, q, g, b, pe, r) in enumerate(zip(
                            act_l, mk_l, leaves(gp), leaves(buf),
                            leaves(pending), leaves(rp))):
                        w = mst_l[li] if mixed else None
                        if not on:
                            new_gp.append(g)
                            new_buf.append(b)
                            new_rp.append(r)
                            new_mst.append(w)
                            continue
                        if kernel_mode != "ref":
                            g2, b2 = kops.nesterov_update_tree(
                                g, pe, b, lr=lr_, momentum=mu,
                                mode=kernel_mode)
                        else:
                            b2 = mu * b + pe
                            g2 = g - lr_ * (mu * b2 + pe)
                        sel = (q > 0) & ok
                        g2 = jnp.where(sel, g2, g)
                        new_gp.append(g2)
                        new_buf.append(jnp.where(sel, b2, b))
                        # merge against the high-precision copy when
                        # one exists; the replica working copy adopts
                        # the result at its storage dtype
                        hp = w if mixed else r
                        tgt = (jnp.broadcast_to(g2[None], hp.shape)
                               if alpha >= 1.0
                               else alpha * g2[None] + (1.0 - alpha) * hp)
                        c = (sel & (adopt_loc.reshape(
                            (k_loc,) + (1,) * g2.ndim) > 0))
                        new_rp.append(jnp.where(c, tgt.astype(r.dtype),
                                                r))
                        if mixed:
                            new_mst.append(jnp.where(c, tgt, w))
                    gp = jax.tree_util.tree_unflatten(treedef, new_gp)
                    buf = jax.tree_util.tree_unflatten(treedef, new_buf)
                    rp = jax.tree_util.tree_unflatten(treedef, new_rp)
                    if mixed:
                        ist = ist._replace(
                            master=jax.tree_util.tree_unflatten(
                                treedef, new_mst))
                    count = jnp.where(ok, count + 1, count)

        ms = {key_: jnp.concatenate([sm[key_] for sm in seg_ms], axis=1)
              for key_ in seg_ms[0]}
        new_base = diloco.DiLoCoState(
            global_params=gp,
            outer_state=outer_opt.OuterState(buf, buf2, count),
            replica_params=rp,
            inner_state=ist,
            outer_t=st.outer_t + 1,
            inner_steps_done=st.inner_steps_done + H)

        if axis is not None:
            # loss metrics live per local replica band: fold the bands
            # into the global replica mean (equal bands, exact mean)
            loss_mean = pod_collectives.replica_mean(ms["loss"],
                                                     axis=axis)
            loss_last = pod_collectives.replica_mean(ms["loss"][:, -1],
                                                     axis=axis)
        else:
            loss_mean = ms["loss"].mean()
            loss_last = ms["loss"][:, -1].mean()
        om = {
            "outer_gnorm": diloco._tree_norm(pending),
            "drop_frac": 1.0 - drop_mask.mean(),
            "inner_loss": loss_mean,
            "inner_loss_last": loss_last,
            # wire bytes one replica sends: peak per sync event and
            # total over the round's P syncs (exact: int4's per-block
            # f32 scales are charged per contiguous leaf region, the
            # unit the sender packs and quantizes; on the packed
            # transport this is the byte-exact size of the gathered
            # buffers, on the simulated paths the legacy static model)
            "stream_peak_sync_bytes":
                jnp.float32(max(sum(kops.transport_bytes(e, qdtype,
                                                         packed=packed)
                                    for e in regs)
                                for regs in part.region_sizes)),
            "stream_round_sync_bytes":
                jnp.float32(sum(kops.transport_bytes(e, qdtype,
                                                     packed=packed)
                                for regs in part.region_sizes
                                for e in regs)),
        }
        if compute_cosine:
            cm, cs = diloco._pairwise_cosine(deltas_acc, m)
            om["cos_mean"], om["cos_std"] = cm, cs
        return StreamState(new_base, pending, armed, residual,
                           tuple(inflight) if inflight is not None
                           else None), om

    def round_body(sstate: StreamState, key, drop_mask=None,
                   active_mask=None, weights=None):
        ones = jnp.ones((dcfg.k,), jnp.float32)
        drop_mask = ones if drop_mask is None else drop_mask
        active_mask = ones if active_mask is None else active_mask
        weights = ones if weights is None else weights
        if not sharded:
            return round_core(sstate, key, drop_mask, active_mask,
                              weights)
        specs = pod_collectives.stream_state_specs(sstate)
        fn = pod_collectives.shard_round_body(round_core, mesh, specs)
        return fn(sstate, key, drop_mask, active_mask, weights)

    return round_body


def sync_plan(params, dcfg: DiLoCoConfig) -> tuple:
    """Static per-fragment outer-sync plan for one streaming round —
    the tick-domain schedule telemetry draws (``obs/trace.py``) and
    the run manifest ships. One dict per fragment: send/apply
    inner-step offsets (``fragments.schedule``), element count,
    contiguous region count, and the per-replica wire bytes one sync
    event ships — the SAME per-region charge the round metrics
    ``stream_peak_sync_bytes`` / ``stream_round_sync_bytes`` use
    (byte-exact packed accounting on the packed sharded transport,
    the legacy static model elsewhere), so trace annotations, round
    metrics, and the HLO-measured gather bytes all reconcile."""
    from repro.kernels import ops as kops
    P = max(1, int(dcfg.streaming_fragments))
    part = fragments.partition_params(params, P,
                                      overrides=dcfg.stream_overrides)
    sched = fragments.schedule(P, dcfg.H, dcfg.stream_tau)
    packed = (getattr(dcfg, "transport", "simulated") == "sharded"
              and getattr(dcfg, "pack_wire", True)
              and dcfg.outer_grad_dtype in ("bfloat16", "int4"))
    plan = []
    for p in range(P):
        regs = part.region_sizes[p]
        plan.append({
            "fragment": p,
            "send_step": int(sched.send_offsets[p]),
            "apply_step": int(sched.apply_offsets[p]),
            "elems": int(part.sizes[p]),
            "regions": len(regs),
            "wire_dtype": dcfg.outer_grad_dtype,
            "packed": packed,
            "wire_bytes": float(sum(
                kops.transport_bytes(int(e), dcfg.outer_grad_dtype,
                                     packed=packed) for e in regs)),
            "crosses_round": int(sched.apply_offsets[p]) > int(dcfg.H),
            # True when the collective's raw result is first consumed
            # at the apply (real issue/consume overlap) rather than
            # decoded eagerly at the send
            "deferred": deferred_consume(dcfg),
        })
    return tuple(plan)
