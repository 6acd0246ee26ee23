"""Multi-device test subsystem for the sharded streaming transport
(core/pod_collectives.py + the transport="sharded" path through
core/streaming.py), on the 8 fake CPU devices tests/conftest.py forces.

What is pinned here:
  * EQUIVALENCE — with one replica per pod (the paper's deployment:
    the "pod" mesh axis IS the replica axis) the sharded transport is
    *bit-identical* to the simulated transport for f32, P ∈ {1, 2, 4},
    across drop masks, mid-run joins and τ-overlap; the quantized
    transports (bf16/int4) gather per-pod payloads whose scale blocks
    are identical to the simulated path's, but XLA re-fuses the
    quantize math into different surroundings, so agreement is within
    quant-error bounds (a near-tie element may round to the adjacent
    code). Banded pods (k > pods) regroup the f32 psum's partial sums
    and agree to float tolerance.
  * QUANT STRUCTURE — int4 scale blocks are formed per replica on each
    pod's local shard, so a pod with tiny deltas is never flattened by
    a neighbor pod's large amax (the blocks-never-mix-pods property).
  * ROBUSTNESS (paper §"robust to resources becoming unavailable") —
    worker dropout and mid-run joins on the sharded path preserve the
    dropped pod's error-feedback residual and AdamW moments pod-locally
    and keep the loss improving.
  * HLO STRUCTURE — the compiled scanned round contains ≥ P pod-axis
    all-reduces *interleaved* with inner-step compute (not clustered at
    round end), and zero cross-pod collectives inside the inner-step
    scan bodies (launch/hlo_analysis.stream_interleaving).
  * PACKED WIRE — the default quantized sharded transport coalesces
    every fragment's leaf regions into ONE packed codes+scales buffer
    and all-gathers it once per fragment per sync; the gathered bytes
    in the lowered HLO equal k × the packed static model, the values
    match the simulated transport within the quant-error bound (bf16
    bitwise), and the pack_wire=False legacy transport stays live.
  * SCHEDULE × PARTITION properties (hypothesis) — every parameter
    element of every communicating replica is reduced exactly once per
    round for arbitrary P, non-divisible H, override patterns and pod
    bandings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DiLoCoConfig, TrainConfig, ModelConfig
from repro.core import diloco, fragments, pod_collectives, streaming
from repro.data.sharding import make_regime
from repro.kernels import ops as kops
from repro.launch import hlo_analysis as H_hlo
from repro.launch.mesh import make_mesh, pods_of
from repro.models.registry import Arch

H, B, S, VOCAB = 4, 2, 16, 64

# Deliberately NO module-level skip on the device count: if
# tests/conftest.py regresses (jax initialized before it sets
# XLA_FLAGS), this whole suite must FAIL loudly, not silently skip and
# leave tier-1 green with the sharded-transport coverage gone.


def test_conftest_provides_fake_devices():
    """Guards the conftest XLA_FLAGS fix: if any import initializes jax
    before conftest sets the flag, every test in this module fails —
    this one first, with the diagnosis in its message."""
    assert len(jax.devices()) >= 8, (
        "tests/conftest.py no longer forces "
        "--xla_force_host_platform_device_count=8 before jax "
        "initializes — the multi-device suite cannot run")
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    assert pods_of(mesh) == 2
    assert pod_collectives.pods_of(mesh) == 2


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=VOCAB, remat=False, attn_chunk=32)
    arch = Arch(cfg=cfg)
    loss_fn = lambda p, b: arch.loss(p, b)
    params, _ = arch.init(jax.random.PRNGKey(0), cfg)
    return arch, loss_fn, params


def _tcfg(rounds):
    return TrainConfig(inner_lr=3e-3, warmup_steps=2,
                       total_steps=rounds * H, batch_size=B, seq_len=S)


def _masks(R, k, *, seed=0, join_last=True):
    """0/1 drop masks (replica 0 always communicates) plus an
    active-mask schedule where the last replica joins after round 1."""
    rng = np.random.default_rng(seed)
    drops = (rng.random((R, k)) >= 0.4).astype(np.float32)
    drops[:, 0] = 1.0
    acts = np.ones((R, k), np.float32)
    if join_last:
        acts[0, k - 1] = 0.0
    return jnp.asarray(drops), jnp.asarray(acts)


def _pod_mesh(pods):
    return make_mesh((pods, 8 // pods), ("pod", "data"))


def _run_pair(loss_fn, params, dcfg_kw, tcfg, *, pods, R, drops, acts,
              weights=None):
    """(simulated state+metrics, sharded state+metrics) for one config."""
    sampler = make_regime("non_iid", k=dcfg_kw["k"], vocab_size=VOCAB,
                          seed=0)
    sim_cfg = DiLoCoConfig(**dcfg_kw)
    run = diloco.make_run(loss_fn, sampler.sample_all_shards, sim_cfg,
                          tcfg, rounds_per_call=R, total_steps=R * H,
                          batch_size=B, seq_len=S, donate=False)
    sim = run(streaming.init_state(params, sim_cfg),
              jax.random.PRNGKey(5), drops, acts, weights)

    sh_cfg = DiLoCoConfig(transport="sharded", **dcfg_kw)
    mesh = _pod_mesh(pods)
    run_s = diloco.make_run(loss_fn, sampler.sample_all_shards, sh_cfg,
                            tcfg, rounds_per_call=R, total_steps=R * H,
                            batch_size=B, seq_len=S, donate=False,
                            mesh=mesh)
    state0 = pod_collectives.shard_stream_state(
        streaming.init_state(params, sh_cfg), mesh)
    sh = run_s(state0, jax.random.PRNGKey(5), drops, acts, weights)
    return sim, sh


def _assert_state_bitwise(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _assert_states_quant_close(sim_st, sh_st, params, kw, *, dt,
                               rtol=5e-3, atol=5e-3):
    """Compare a simulated vs sharded StreamState within quant error.

    The deferred in-flight slot (quantized, τ>0) holds each transport's
    own RAW representation — the packed byte wire on the packed sharded
    transport, the stacked f32 payload elsewhere — so it is compared
    through its DECODED per-replica values rather than leaf-by-leaf:
    the last round's wrapped send is still in flight at the state
    boundary, and this checks the sharded wire decodes to the simulated
    payload (every earlier send is covered via pending/params once its
    apply consumed it)."""
    for la, lb in zip(jax.tree.leaves(sim_st._replace(inflight=None)),
                      jax.tree.leaves(sh_st._replace(inflight=None))):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   rtol=rtol, atol=atol)
    if sim_st.inflight is None:
        assert sh_st.inflight is None
        return
    P = kw["streaming_fragments"]
    part = fragments.partition_params(params, P)
    regs = fragments.fragment_regions(part, params)
    leaves = jax.tree_util.tree_leaves
    for p, (es, eh) in enumerate(zip(sim_st.inflight,
                                     sh_st.inflight)):
        if es is None and eh is None:
            continue
        np.testing.assert_array_equal(np.asarray(es[1]),
                                      np.asarray(eh[1]))  # mask snap
        sim_payload = es[0]
        if kw.get("pack_wire", True):
            wire = np.asarray(eh[0])
            off = 0
            for r in regs[p]:
                W = kops.wire_elems(r.elems, dt)
                dec = np.stack([np.asarray(kops.wire_decode(
                    jnp.asarray(w), r.elems, dt, mode="ref"))
                    for w in wire[:, off:off + W]])
                off += W
                ref_vals = np.asarray(fragments.region_take(
                    sim_payload[r.leaf], r, lead_axes=1))
                np.testing.assert_allclose(dec, ref_vals,
                                           rtol=rtol, atol=atol)
        else:
            for ls, lh in zip(sim_payload, eh[0]):
                assert (ls is None) == (lh is None)
                if ls is not None:
                    np.testing.assert_allclose(
                        np.asarray(ls), np.asarray(lh),
                        rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# equivalence: sharded ≡ simulated
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pods", [2, 4])
@pytest.mark.parametrize("P", [1, 2, 4])
def test_sharded_f32_bit_identical(setup, P, pods):
    """One replica per pod, f32 transport: the per-fragment psum
    all-reduce is bit-identical to the simulated stacked tensordot —
    masked 0/1 products are exact, so only the (matching) accumulation
    order is in play. Covers drop masks, a mid-run join, and τ-overlap
    with α-mixing for P > 1."""
    arch, loss_fn, params = setup
    R, k = 3, pods
    drops, acts = _masks(R, k)
    tau = 0 if P == 1 else 1
    alpha = 1.0 if P == 1 else 0.5
    kw = dict(k=k, H=H, streaming_fragments=P, stream_tau=tau,
              stream_alpha=alpha)
    sim, sh = _run_pair(loss_fn, params, kw, _tcfg(R), pods=pods, R=R,
                        drops=drops, acts=acts)
    _assert_state_bitwise(sim[0], sh[0])
    for key in ("outer_gnorm", "drop_frac"):
        np.testing.assert_array_equal(np.asarray(sim[1][key]),
                                      np.asarray(sh[1][key]))
    np.testing.assert_allclose(np.asarray(sim[1]["inner_loss"]),
                               np.asarray(sh[1]["inner_loss"]),
                               rtol=1e-6)


@pytest.mark.parametrize("dt", ["bfloat16", "int4"])
def test_sharded_quantized_within_quant_error(setup, dt):
    """Quantized transports gather the per-pod payloads and reduce
    locally: the payloads are identical to the simulated path's (scale
    blocks never mix pods), but XLA re-fuses the quantize math into
    different surroundings, so a near-tie element may round to the
    adjacent code — sharded and simulated states agree within a few
    transport quantization steps (the satellite's quant-error bound),
    and both stay finite and training."""
    arch, loss_fn, params = setup
    R, k, pods, P = 3, 4, 4, 2
    drops, acts = _masks(R, k)
    kw = dict(k=k, H=H, streaming_fragments=P, stream_tau=1,
              stream_alpha=0.5, outer_grad_dtype=dt, error_feedback=True)
    sim, sh = _run_pair(loss_fn, params, kw, _tcfg(R), pods=pods, R=R,
                        drops=drops, acts=acts)
    _assert_states_quant_close(sim[0], sh[0], params, kw, dt=dt)
    assert np.isfinite(np.asarray(sh[1]["inner_loss"])).all()
    np.testing.assert_allclose(np.asarray(sim[1]["inner_loss"]),
                               np.asarray(sh[1]["inner_loss"]),
                               rtol=1e-2)


def test_sharded_banded_pods_within_tolerance(setup):
    """k=4 replicas on 2 pods (two-replica bands): the f32 psum now
    adds pre-reduced band partials, which regroups the simulated FMA
    chain — equal to float tolerance, not bitwise (documented)."""
    arch, loss_fn, params = setup
    R, k, pods = 2, 4, 2
    drops, acts = _masks(R, k)
    kw = dict(k=k, H=H, streaming_fragments=2, stream_tau=1,
              stream_alpha=0.5)
    sim, sh = _run_pair(loss_fn, params, kw, _tcfg(R), pods=pods, R=R,
                        drops=drops, acts=acts)
    for la, lb in zip(jax.tree.leaves(sim[0]), jax.tree.leaves(sh[0])):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   rtol=2e-5, atol=2e-6)


def test_sharded_fractional_weights_within_tolerance(setup):
    """Shard-size weights are fractional, so the masked products round
    before the wire: psum and the simulated FMA'd tensordot agree to
    ~1 ulp per element (exactness needs 0/1 masks — documented)."""
    arch, loss_fn, params = setup
    R, k, pods = 2, 2, 2
    drops, acts = _masks(R, k, join_last=False)
    weights = jnp.asarray([0.75, 0.25])
    kw = dict(k=k, H=H, streaming_fragments=2, stream_tau=1,
              stream_alpha=0.5)
    sim, sh = _run_pair(loss_fn, params, kw, _tcfg(R), pods=pods, R=R,
                        drops=drops, acts=acts, weights=weights)
    for la, lb in zip(jax.tree.leaves(sim[0]), jax.tree.leaves(sh[0])):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# int4 scale blocks never mix pods
# ---------------------------------------------------------------------------

def test_int4_scale_blocks_are_pod_local():
    """A pod holding tiny deltas next to a pod holding huge deltas: if
    any scale block mixed the two pods, the tiny pod's values would
    quantize to zero. The transport quantizes per replica on the local
    shard, so the tiny pod's payload survives with its own amax."""
    mesh = _pod_mesh(2)
    big = np.full((1, 256), 1000.0, np.float32)
    tiny = np.full((1, 256), 1e-3, np.float32)
    d = jnp.asarray(np.concatenate([big, tiny]))            # (k=2, 256)

    from jax.sharding import PartitionSpec as P, NamedSharding

    def body(d_local):
        q = jax.vmap(lambda x: kops.quant_roundtrip(x, "int4"))(d_local)
        return jax.lax.all_gather(q, "pod", axis=0, tiled=True)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("pod"),),
                               out_specs=P(), check_vma=False))
    out = np.asarray(fn(jax.device_put(
        d, NamedSharding(mesh, P("pod")))))
    # per-replica blocks: every element within amax/14 of its own value
    assert np.abs(out[0] - 1000.0).max() <= 1000.0 / 13.99
    assert np.abs(out[1] - 1e-3).max() <= 1e-3 / 13.99
    assert (out[1] != 0).all()            # a mixed block would zero it
    # and the wire payload equals the simulated per-replica round trip
    sim = np.asarray(jax.vmap(
        lambda x: kops.quant_roundtrip(x, "int4"))(d))
    np.testing.assert_array_equal(out, sim)


# ---------------------------------------------------------------------------
# robustness: dropout + mid-run join on the sharded path
# ---------------------------------------------------------------------------

def test_sharded_drop_preserves_pod_local_state(setup):
    """Round 2 drops replica 1's outer packet entirely: its
    error-feedback residual must NOT be consumed (it never sent) and
    its AdamW moments must keep evolving pod-locally (it keeps
    training on its own params — Fig 8 semantics), while loss keeps
    improving through the drop."""
    arch, loss_fn, params = setup
    k = pods = 2
    sampler = make_regime("non_iid", k=k, vocab_size=VOCAB, seed=0)
    dcfg = DiLoCoConfig(k=k, H=H, streaming_fragments=2, stream_tau=1,
                        stream_alpha=0.5, outer_grad_dtype="int4",
                        error_feedback=True, transport="sharded")
    mesh = _pod_mesh(pods)
    tcfg = _tcfg(4)
    run1 = diloco.make_run(loss_fn, sampler.sample_all_shards, dcfg,
                           tcfg, rounds_per_call=1, total_steps=4 * H,
                           batch_size=B, seq_len=S, donate=False,
                           mesh=mesh)
    state = pod_collectives.shard_stream_state(
        streaming.init_state(params, dcfg), mesh)
    key = jax.random.PRNGKey(5)
    ones = jnp.ones((1, k), jnp.float32)
    drop_r2 = jnp.asarray([[1.0, 0.0]], jnp.float32)

    # round 1: everyone communicates (arms fragments, seeds residuals)
    state, m1 = run1(state, key, ones, ones)
    key = m1["next_key"]
    res_before = jax.tree.map(
        lambda r: np.asarray(r)[1].copy(), state.residual)
    mom_before = jax.tree.map(
        lambda r: np.asarray(r)[1].copy(), state.inner_state.m)

    # round 2: replica 1 dropped
    state, m2 = run1(state, key, drop_r2, ones)
    key = m2["next_key"]
    # dropped replica's residual survives every send event untouched
    # where it had pending error (it consumed nothing, sent nothing)
    changed = [not np.array_equal(np.asarray(r)[1], rb) for r, rb in zip(
        jax.tree.leaves(state.residual),
        jax.tree.leaves(res_before))]
    assert not any(changed), "dropped pod's residual was consumed"
    # but its inner moments kept training pod-locally
    assert any(not np.array_equal(np.asarray(r)[1], mb) for r, mb in zip(
        jax.tree.leaves(state.inner_state.m),
        jax.tree.leaves(mom_before)))

    # rounds 3-4: replica 1 rejoins; loss keeps improving vs round 1
    state, m3 = run1(state, m2["next_key"], ones, ones)
    state, m4 = run1(state, m3["next_key"], ones, ones)
    l1 = float(np.asarray(m1["inner_loss"])[-1])
    l4 = float(np.asarray(m4["inner_loss"])[-1])
    assert np.isfinite(l4) and l4 < l1
    for leaf in jax.tree.leaves(state):
        assert np.isfinite(np.asarray(leaf)).all()


def test_sharded_mid_run_join_parks_then_merges(setup):
    """A replica inactive in round 1 (mid-run capacity join): it parks
    on the merged fragments, joins the pool from round 2 on, and the
    run matches the simulated path bit-for-bit throughout."""
    arch, loss_fn, params = setup
    R = 3
    k = pods = 4
    drops = jnp.ones((R, k), jnp.float32)
    acts = np.ones((R, k), np.float32)
    acts[0, 3] = 0.0                       # replica 3 joins in round 2
    kw = dict(k=k, H=H, streaming_fragments=2, stream_tau=1,
              stream_alpha=0.5)
    sim, sh = _run_pair(loss_fn, params, kw, _tcfg(R), pods=pods, R=R,
                        drops=drops, acts=jnp.asarray(acts))
    _assert_state_bitwise(sim[0], sh[0])
    losses = np.asarray(sh[1]["inner_loss"])
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# HLO structure: real all-reduces, interleaved, none inside inner steps
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_hlo_pod_all_reduces_interleave(setup):
    """Compile the scanned sharded round on a (2,2,2) mesh and assert
    the paper's overlap structure on the HLO itself: ≥ P pod-crossing
    all-reduces in the round body, all but the round-final fragment's
    followed by inner-step compute (a re-serialized implementation
    would cluster them at round end with 0 compute after), and zero
    cross-pod collectives inside the inner-step scan loops."""
    arch, loss_fn, params = setup
    P_frag = 4
    k = pods = 2
    sampler = make_regime("non_iid", k=k, vocab_size=VOCAB, seed=0)
    dcfg = DiLoCoConfig(k=k, H=H, streaming_fragments=P_frag,
                        transport="sharded")
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    run = diloco.make_run(loss_fn, sampler.sample_all_shards, dcfg,
                          _tcfg(2), rounds_per_call=2, total_steps=2 * H,
                          batch_size=B, seq_len=S, donate=False,
                          mesh=mesh)
    state = pod_collectives.shard_stream_state(
        streaming.init_state(params, dcfg), mesh)
    hlo = run.lower(state, jax.random.PRNGKey(5)).compile().as_text()
    st = H_hlo.stream_interleaving(hlo, chips_per_pod=4)
    assert st["pod_all_reduces"] >= P_frag, st
    assert st["compute_events"] > 0, st
    assert st["syncs_with_compute_after"] >= P_frag - 1, st
    assert st["syncs_inside_compute"] == 0, st
    # and the generic collective accounting sees cross-pod bytes
    coll = H_hlo.collective_stats(hlo, chips_per_pod=4)
    assert coll.cross_pod_bytes > 0


# ---------------------------------------------------------------------------
# packed wire: coalesced per-fragment gathers of real codes+scales
# ---------------------------------------------------------------------------

def _toy_tree(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    return {"embed": mk(7, 4), "stack_w": mk(5, 3, 2),
            "stack_b": mk(5, 2), "head": mk(4, 3)}


def _packed_mean_tree(params, d, m, P, pods, dt):
    """Pending tree from the packed transport: per fragment, encode
    every region of the local band, concatenate, ONE gather_wire,
    decode + masked mean — the exact op sequence of
    streaming.packed_send, at the wire level."""
    from jax.sharding import PartitionSpec as Pspec

    part = fragments.partition_params(params, P)
    regions = fragments.fragment_regions(part, params)
    denom = jnp.maximum(m.sum(), 1e-9)
    mesh = _pod_mesh(pods)
    treedef = jax.tree_util.tree_structure(params)

    def body(d_loc):
        leaves_d = jax.tree_util.tree_leaves(d_loc)
        pend = [jnp.zeros(l.shape[1:], jnp.float32) for l in leaves_d]
        for regs in regions:
            wires = [jax.vmap(lambda v: kops.wire_encode(
                v, dt, mode="ref")[0])(
                fragments.region_take(leaves_d[r.leaf], r, lead_axes=1))
                for r in regs]
            g = pod_collectives.gather_wire(
                jnp.concatenate(wires, axis=1))
            off = 0
            for r in regs:
                W = kops.wire_elems(r.elems, dt)
                vals = jax.vmap(lambda w: kops.wire_decode(
                    w, r.elems, dt, mode="ref"))(g[:, off:off + W])
                off += W
                a = jnp.tensordot(m, vals, axes=(0, 0)) / denom
                pend[r.leaf] = fragments.region_put(pend[r.leaf], r, a)
        return jax.tree_util.tree_unflatten(treedef, pend)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: Pspec("pod"), d),),
        out_specs=jax.tree.map(lambda _: Pspec(), params),
        check_vma=False))
    return fn(d)


@pytest.mark.parametrize("pods", [2, 4])
@pytest.mark.parametrize("P", [1, 2, 4])
def test_packed_wire_mean_matches_simulated(P, pods):
    """Packed-wire reduction vs the simulated transport across
    P ∈ {1,2,4} × pods ∈ {2,4}: bf16 payload values are exact on the
    wire, so the reduced means agree to reassociation (XLA lowers the
    (k,)·(k,region) dot with a different accumulation blocking than
    the (k,)·(k,leaf-shape) reference — ~1 ulp); int4 agrees within
    the transport's own quant-error bound — region-wise scale blocks
    may cut a leaf's 128-block lattice differently than the simulated
    whole-leaf blocks, shifting each side at most amax/14 from the
    true delta."""
    params = _toy_tree()
    k = pods
    rng = np.random.default_rng(P * 10 + pods)
    d = jax.tree.map(lambda l: jnp.asarray(
        rng.normal(size=(k,) + l.shape).astype(np.float32)), params)
    m = jnp.asarray((rng.random(k) > 0.3).astype(np.float32))
    m = m.at[0].set(1.0)
    denom = jnp.maximum(m.sum(), 1e-9)

    def simulated(dt):
        q = jax.tree.map(lambda l: jax.vmap(
            lambda v: kops.quant_roundtrip(v, dt, mode="ref"))(l), d)
        return jax.tree.map(
            lambda l: jnp.tensordot(m, l, axes=(0, 0)) / denom, q)

    got = _packed_mean_tree(params, d, m, P, pods, "bfloat16")
    for a, b in zip(jax.tree.leaves(simulated("bfloat16")),
                    jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    got = _packed_mean_tree(params, d, m, P, pods, "int4")
    for leaf, a, b in zip(jax.tree.leaves(d),
                          jax.tree.leaves(simulated("int4")),
                          jax.tree.leaves(got)):
        bound = float(jnp.max(jnp.abs(leaf))) / 7.0 + 1e-7
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=bound)


def test_packed_wire_is_default_and_legacy_still_works(setup):
    """pack_wire=False keeps the PR 4 fake-quant transport alive for
    comparison: the legacy int4 sharded run still matches simulated
    within quant tolerance, and the config default is packed."""
    assert DiLoCoConfig(k=2, H=4).pack_wire is True
    arch, loss_fn, params = setup
    R, k, pods, P = 2, 2, 2, 2
    drops, acts = _masks(R, k)
    kw = dict(k=k, H=H, streaming_fragments=P, stream_tau=1,
              stream_alpha=0.5, outer_grad_dtype="int4",
              error_feedback=True, pack_wire=False)
    sim, sh = _run_pair(loss_fn, params, kw, _tcfg(R), pods=pods, R=R,
                        drops=drops, acts=acts)
    _assert_states_quant_close(sim[0], sh[0], params, kw, dt="int4")


@pytest.mark.slow
def test_packed_wire_hlo_one_gather_byte_exact(setup):
    """The acceptance gate, on the lowered HLO itself: the packed int4
    round issues EXACTLY one pod-axis all-gather per fragment per sync,
    the gathered bytes equal k × the packed static model (measured,
    not modeled), and the real wire is ≥ 5× smaller than the same
    regions at f32."""
    arch, loss_fn, params = setup
    k = pods = 2
    P_frag = 2
    sampler = make_regime("non_iid", k=k, vocab_size=VOCAB, seed=0)
    dcfg = DiLoCoConfig(k=k, H=H, streaming_fragments=P_frag,
                        stream_tau=1, stream_alpha=0.5,
                        outer_grad_dtype="int4", transport="sharded")
    mesh = _pod_mesh(pods)
    run = diloco.make_run(loss_fn, sampler.sample_all_shards, dcfg,
                          _tcfg(1), rounds_per_call=1, total_steps=H,
                          batch_size=B, seq_len=S, donate=False,
                          mesh=mesh)
    state = pod_collectives.shard_stream_state(
        streaming.init_state(params, dcfg), mesh)
    hlo = run.lower(state, jax.random.PRNGKey(5)).compile().as_text()
    cpp = 8 // pods
    inter = H_hlo.stream_interleaving(hlo, chips_per_pod=cpp)
    assert inter["sync_by_op"].get("all-gather", 0) == P_frag, inter
    coll = H_hlo.collective_stats(hlo, chips_per_pod=cpp)
    part = fragments.partition_params(params, P_frag)
    model = k * sum(kops.transport_bytes(e, "int4", packed=True)
                    for regs in part.region_sizes for e in regs)
    meas = coll.cross_by_op.get("all-gather", 0)
    # two-sided: under-shipping the model is as much a regression as
    # over-shipping (the gather output is k×W bytes by construction)
    assert 0.95 * model <= meas <= 1.35 * model, (meas, model)
    f32_model = k * sum(kops.transport_bytes(e, "float32")
                        for regs in part.region_sizes for e in regs)
    assert f32_model / meas >= 5.0, (f32_model, meas)


def _lower_round(loss_fn, params, dcfg, *, pods, rounds=1):
    sampler = make_regime("non_iid", k=dcfg.k, vocab_size=VOCAB, seed=0)
    mesh = _pod_mesh(pods)
    run = diloco.make_run(loss_fn, sampler.sample_all_shards, dcfg,
                          _tcfg(rounds), rounds_per_call=rounds,
                          total_steps=rounds * H, batch_size=B,
                          seq_len=S, donate=False, mesh=mesh)
    state = pod_collectives.shard_stream_state(
        streaming.init_state(params, dcfg), mesh)
    return run.lower(state, jax.random.PRNGKey(5))


@pytest.mark.slow
def test_hlo_overlap_issue_consume_separation(setup):
    """The tentpole acceptance gate: for τ>0 on the sharded quantized
    transport, every fragment's collective issue and its opt-barrier
    consume are separated by ≥τ inner steps' worth of dot ops in the
    emitted program order (pre-optimization HLO, where instruction ids
    record emission order and the barriers still exist). The wrapped
    fragment's wire must leave through the carry and be consumed next
    round; metric all-reduces stay eager and outside the gate."""
    arch, loss_fn, params = setup
    k = pods = 2
    P_frag, tau = 2, 1
    cpp = 8 // pods

    dcfg = DiLoCoConfig(k=k, H=H, streaming_fragments=P_frag,
                        stream_tau=tau, stream_alpha=0.5,
                        outer_grad_dtype="int4", transport="sharded")
    assert streaming.deferred_consume(dcfg)
    unopt = _lower_round(loss_fn, params, dcfg, pods=pods) \
        .compiler_ir("hlo").as_hlo_text()
    ov = H_hlo.stream_overlap(unopt, chips_per_pod=cpp, tau=tau)
    assert ov["ok"], ov
    wire = [r for r in ov["rows"] if r["deferred"]]
    assert len(wire) == P_frag, ov
    assert all(r["op"] == "all-gather" for r in wire), ov
    # the round-final fragment wraps: issued at offset H, consumed at
    # offset τ of the NEXT round through the scan carry
    assert sum(r["wrapped"] for r in wire) == 1, ov
    assert all(r["steps_between"] >= tau for r in wire), ov
    assert all(r["dots_between"] > 0 for r in wire), ov

    # legacy (unpacked) quantized transport defers identically: one
    # consume barrier per fragment, per-leaf gathers behind it
    dcfg_l = DiLoCoConfig(k=k, H=H, streaming_fragments=P_frag,
                          stream_tau=tau, stream_alpha=0.5,
                          outer_grad_dtype="bfloat16",
                          transport="sharded", pack_wire=False)
    unopt_l = _lower_round(loss_fn, params, dcfg_l, pods=pods) \
        .compiler_ir("hlo").as_hlo_text()
    ov_l = H_hlo.stream_overlap(unopt_l, chips_per_pod=cpp, tau=tau)
    assert ov_l["ok"], ov_l
    assert ov_l["n_deferred"] >= P_frag, ov_l


@pytest.mark.slow
def test_hlo_overlap_tau0_stays_eager(setup):
    """τ=0 has no overlap window: the deferral predicate is off, the
    lowering carries no opt-barriers, and every collective is consumed
    where it is issued — the PR 7 eager schedule, bit-for-bit."""
    arch, loss_fn, params = setup
    k = pods = 2
    dcfg = DiLoCoConfig(k=k, H=H, streaming_fragments=2, stream_tau=0,
                        stream_alpha=0.5, outer_grad_dtype="int4",
                        transport="sharded")
    assert not streaming.deferred_consume(dcfg)
    unopt = _lower_round(loss_fn, params, dcfg, pods=pods) \
        .compiler_ir("hlo").as_hlo_text()
    ov = H_hlo.stream_overlap(unopt, chips_per_pod=8 // pods)
    assert ov["n_barriers"] == 0, ov
    assert ov["n_deferred"] == 0, ov
    assert ov["n_collectives"] >= 2, ov


# Hypothesis property tests for Partition × schedule × pod banding live
# in tests/test_pod_properties.py — a module-level importorskip there
# must not take this whole multi-device suite down with it.


def test_sharded_cli_run_compiles_each_chunk_once(caplog):
    """A chunked sharded run through the CLI driver compiles its round
    program once: the carry key handed back between chunks keeps the
    placement of the key the first chunk was called with."""
    import logging
    from repro.launch import train
    args = train.make_parser().parse_args(
        ["--arch", "diloco_150m", "--smoke", "--k", "2", "--H", "2",
         "--rounds", "3", "--rounds-per-call", "1", "--seq", "16",
         "--batch", "2", "--eval-batch", "2", "--transport", "sharded",
         "--stream-fragments", "2", "--pods", "2"])
    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING, logger="jax"):
            rounds = [r for r in train.run(args) if r["kind"] == "round"]
    finally:
        jax.config.update("jax_log_compiles", False)
    compiles = [r for r in caplog.records
                if r.getMessage().startswith("Compiling jit(run_fn)")]
    assert len(rounds) == 3 and len(compiles) == 1


def test_sharded_cli_resume_is_bit_identical(tmp_path):
    """A sharded CLI run resumed from a mid-run snapshot re-places the
    restored state on the pod mesh and ends in the same state, bit for
    bit, as the run that was never interrupted."""
    import json
    from repro.launch import train
    base = ["--arch", "diloco_150m", "--smoke", "--k", "2", "--H", "2",
            "--rounds", "3", "--seq", "16", "--batch", "2",
            "--eval-batch", "2", "--transport", "sharded",
            "--stream-fragments", "2", "--pods", "2",
            "--log-format", "json"]
    ckpt = ["--checkpoint-dir", str(tmp_path / "ckpt"),
            "--checkpoint-every", "2"]
    clean, resumed = tmp_path / "clean.json", tmp_path / "resumed.json"
    train.run(train.make_parser().parse_args(
        base + ckpt + ["--state-hash-out", str(clean)]))
    train.run(train.make_parser().parse_args(
        base + ckpt + ["--resume", "2", "--state-hash-out",
                       str(resumed)]))
    a, b = json.loads(clean.read_text()), json.loads(resumed.read_text())
    assert b["resumed_from_step"] == 2
    assert b["state_sha256"] == a["state_sha256"]
