"""Real pod-axis collectives for the streaming outer sync.

``core/streaming.py``'s simulated transport averages replica-stacked
arrays on one device — nothing crosses a mesh boundary. This module is
the deployable counterpart: each DiLoCo replica lives on its own slice
of the mesh's ``"pod"`` axis (``launch/mesh.py``'s multi-pod layout),
the streaming round runs under ``shard_map``, inner steps are pure
pod-local compute (manual sharding makes "zero cross-pod collectives
during inner training" *definitional*, not emergent), and each
fragment's outer gradient is reduced by a genuine cross-pod collective
at its staggered offset inside the scanned round.

Per transport precision the fragment reduction is:

  float32   weighted psum — ``lax.psum`` of each pod's partial
            ``tensordot(m_local, Δ_local)`` over the pod axis, i.e. a
            real all-reduce of fragment-size bytes.  With 0/1
            drop/active masks and uniform weights this is *bit-identical*
            to the simulated ``tensordot(m, Δ)`` (masked products are
            exact, and XLA's sequential all-reduce matches the dot's
            FMA accumulation order — tested); fractional per-shard
            weights round differently under FMA and agree to ~1 ulp.
  bfloat16  the per-replica quantized payload is exactly representable
            in bf16, so the wire carries real bf16: ``all_gather`` the
            bf16 fragment over the pod axis, upcast (exact), and reduce
            locally with the simulated path's op sequence.
  int4      per-replica payloads (scale blocks are formed on each pod's
            local shard, so they can never mix two pods' values) are
            all-gathered and reduced locally. With ``pack_wire`` (the
            default) the gather ships the REAL packed pair — nibble-
            packed int8 codes + per-block f32 scales laid out in ONE
            byte buffer per fragment (``ops.wire_encode``), all leaf
            regions coalesced, so the lowered HLO carries exactly the
            bytes ``ops.transport_bytes(..., packed=True)`` charges and
            issues one pod-axis all-gather per fragment per sync.
            ``pack_wire=False`` keeps the legacy fake-quant transport:
            the gather ships dequantized f32 (≈7.5× the packed bytes)
            and the wire is charged by the static model only.

Quantized transports agree with the simulated path within quant-error
bounds rather than bitwise: the payload *values* are identical, but XLA
re-fuses the quantize arithmetic into different surrounding ops per
program, so an element sitting exactly on a rounding tie may take the
adjacent code (one transport quantization step) — tested.

Quantized collectives gather rather than psum because summing encoded
payloads is meaningless (per-block scales differ per pod) — gather +
local dequant-reduce is how production quantized all-reduces work, and
the local reduction doubles as a run-to-run-deterministic reduction
order, independent of topology.

Error-feedback residuals (``StreamState.residual``) and AdamW moments
are pod-local state: they are sharded over the pod axis and never
touch the wire.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

POD_AXIS = "pod"


def pods_of(mesh) -> int:
    """Size of the mesh's pod axis (1 when absent)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(POD_AXIS, 1)


def validate_mesh(mesh, k: int) -> int:
    """Check ``mesh`` can host ``k`` replicas on its pod axis; returns
    the pod count. Replicas are laid out in contiguous bands of
    ``k // pods`` per pod, so pods must divide k."""
    if mesh is None:
        raise ValueError(
            "transport='sharded' needs a mesh with a 'pod' axis: pass "
            "mesh=... to make_round/make_run (see launch/mesh.py)")
    if POD_AXIS not in mesh.axis_names:
        raise ValueError(
            f"transport='sharded' needs a '{POD_AXIS}' mesh axis, got "
            f"axes {mesh.axis_names}")
    pods = pods_of(mesh)
    if k % pods != 0:
        raise ValueError(
            f"k={k} replicas cannot be banded over {pods} pods: pods "
            "must divide k (one contiguous replica band per pod)")
    return pods


def local_band(k_local: int, axis: str = POD_AXIS):
    """Start index of this pod's replica band (traced; shard_map only)."""
    return jax.lax.axis_index(axis) * k_local


def band_slice(x, k_local: int, axis_name: str = POD_AXIS):
    """This pod's (k_local, ...) band of a replicated (k, ...) array."""
    return jax.lax.dynamic_slice_in_dim(
        x, local_band(k_local, axis_name), k_local, 0)


@jax.named_scope("diloco.sync")
def fragment_mean(d_local, m_full, m_local, denom, *, dtype: str,
                  axis: str = POD_AXIS):
    """Reduce one fragment leaf's outer gradient across pods.

    d_local: (k_local, ...) per-replica deltas, already transport-
    quantized (``quant_roundtrip`` values). m_full/m_local: the (k,)
    communication mask and this pod's band of it. denom: the (exact,
    replicated) mask sum. Returns the masked mean, replicated.
    """
    if dtype == "float32":
        part = jnp.tensordot(m_local, d_local, axes=(0, 0))
        return jax.lax.psum(part, axis) / denom
    gathered = fragment_gather(d_local, dtype=dtype, axis=axis)
    # the exact op the simulated transport runs on its stacked array —
    # bit-identical reduction, deterministic order on any topology
    return jnp.tensordot(m_full, gathered, axes=(0, 0)) / denom


@jax.named_scope("diloco.sync")
def fragment_gather(d_local, *, dtype: str, axis: str = POD_AXIS):
    """The collective half of the quantized ``fragment_mean``: gather
    one fragment leaf's per-replica payload over the pod axis WITHOUT
    reducing it. The deferred streaming round (quantized, τ>0) issues
    this at the send offset and runs the mask-reduce τ inner steps
    later at the apply, so the gather's result has no consumer until
    the overlap window has elapsed. Returns (k, ...) in replica order,
    replicated."""
    if dtype == "bfloat16":
        # the quantized payload is on the bf16 grid: ship real bf16
        # bytes and upcast losslessly on arrival
        wire = jax.lax.all_gather(d_local.astype(jnp.bfloat16), axis,
                                  axis=0, tiled=True)
        return wire.astype(d_local.dtype)
    # int4 fake-quant payload; codes+scales packing is modeled by
    # the static wire accounting (ops.transport_bytes)
    return jax.lax.all_gather(d_local, axis, axis=0, tiled=True)


@jax.named_scope("diloco.sync")
def gather_wire(wire_local, *, axis: str = POD_AXIS):
    """THE packed-wire collective: all-gather one fragment's coalesced
    per-replica wire buffers over the pod axis. wire_local:
    (k_local, W) — every leaf region's packed payload concatenated —
    returns (k, W) with every pod's band in replica order. One call per
    fragment per sync is the whole cross-pod bill of the quantized
    sharded transport."""
    return jax.lax.all_gather(wire_local, axis, axis=0, tiled=True)


def replica_mean(x_local, *, axis: str = POD_AXIS):
    """Global mean of a metric carried per local replica band."""
    return jax.lax.pmean(x_local.mean(), axis)


# ---------------------------------------------------------------------------
# state sharding specs / placement
# ---------------------------------------------------------------------------

def stream_state_specs(state, axis: str = POD_AXIS):
    """PartitionSpec pytree matching a ``streaming.StreamState``:
    per-replica leaves (working params, AdamW m/v/count/master,
    error-feedback residual) band-sharded over the pod axis on their
    leading (k,) dim; global params, outer state, pending fragments,
    the armed latch and the in-flight collective buffers replicated
    (every pod computes them identically from the replicated collective
    results — an all-gather's output is the same on every pod)."""
    shard = lambda t: jax.tree.map(lambda _: P(axis), t)
    rep = lambda t: jax.tree.map(lambda _: P(), t)
    base = state.base._replace(
        global_params=rep(state.base.global_params),
        outer_state=rep(state.base.outer_state),
        replica_params=shard(state.base.replica_params),
        inner_state=shard(state.base.inner_state),
        outer_t=P(),
        inner_steps_done=P())
    return state._replace(
        base=base,
        pending=rep(state.pending),
        armed=P(),
        residual=(None if state.residual is None
                  else shard(state.residual)),
        inflight=(None if getattr(state, "inflight", None) is None
                  else rep(state.inflight)))


def init_on_mesh(init_fn, params, mesh, axis: str = POD_AXIS):
    """Run ``init_fn(params) -> StreamState`` with its outputs placed on
    ``mesh`` per ``stream_state_specs``: each pod builds only its own
    replica band, so the k stacked replicas never sit on one device."""
    shapes = jax.eval_shape(init_fn, params)
    shardings = jax.tree.map(lambda _, s: NamedSharding(mesh, s), shapes,
                             stream_state_specs(shapes, axis))
    return jax.jit(init_fn, out_shardings=shardings)(params)


def shard_stream_state(state, mesh, axis: str = POD_AXIS):
    """Place a StreamState on ``mesh``: replica state banded over the
    pod axis, shared state replicated. Use before the first sharded
    ``make_run`` call so the donated carry starts resident.

    Every returned leaf is a FRESH buffer: ``jax.device_put`` is the
    identity when a leaf already carries the target sharding, and
    handing an aliased leaf to the donated run would delete the
    caller's array with it (the donated-carry footgun) — so identity
    placements are copied explicitly."""
    validate_mesh(mesh, jax.tree.leaves(state.base.replica_params)[0]
                  .shape[0])
    specs = stream_state_specs(state, axis)

    def place(x, s):
        y = jax.device_put(x, NamedSharding(mesh, s))
        return y.copy() if y is x else y

    return jax.tree.map(place, state, specs)


def shard_round_body(core, mesh, state_specs):
    """Wrap an un-jitted streaming round core in shard_map over the pod
    axis: state per ``state_specs``; key, masks and weights replicated;
    outputs (state, metrics) with metrics replicated (they are pmean'd
    inside). check_vma=False: replication of the shared state is
    guaranteed by construction (all pods consume identical collective
    results), which the static checker cannot see."""
    return jax.shard_map(core, mesh=mesh,
                         in_specs=(state_specs, P(), P(), P(), P()),
                         out_specs=(state_specs, P()),
                         check_vma=False)
