"""Substrate tests: data pipeline, sharding rules, optimizer, schedule,
checkpointing, compute/drop schedules."""
from __future__ import annotations

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import schedules
from repro.data.pipeline import MarkovMixture
from repro.data.sharding import make_regime, shard_weights
from repro.optim import adamw
from repro.optim.schedule import warmup_cosine, make_warmup_cosine
from repro.sharding.spec import (Boxed, logical_to_pspec, unbox,
                                 batch_pspec)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_markov_deterministic():
    s = MarkovMixture(vocab_size=64, k=4, alpha=1.0, seed=0)
    a = s.sample_all_shards(jax.random.PRNGKey(1), 4, 32)
    b = s.sample_all_shards(jax.random.PRNGKey(1), 4, 32)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (4, 4, 32)
    assert a.dtype == jnp.int32
    assert (a >= 0).all() and (a < 64).all()


def test_iid_shards_share_distribution():
    """alpha=0 (iid): per-shard bigram statistics agree closely."""
    s = make_regime("iid", k=2, vocab_size=16, seed=0)
    toks = np.asarray(s.sample_all_shards(jax.random.PRNGKey(0), 64, 256))

    def bigram(t):
        h = np.zeros((16, 16))
        for row in t.reshape(-1, t.shape[-1]):
            np.add.at(h, (row[:-1], row[1:]), 1)
        return h / h.sum()

    d = np.abs(bigram(toks[0]) - bigram(toks[1])).sum()
    assert d < 0.15, d


def test_non_iid_shards_differ():
    s = make_regime("non_iid", k=2, vocab_size=16, seed=0)
    toks = np.asarray(s.sample_all_shards(jax.random.PRNGKey(0), 64, 256))

    def bigram(t):
        h = np.zeros((16, 16))
        for row in t.reshape(-1, t.shape[-1]):
            np.add.at(h, (row[:-1], row[1:]), 1)
        return h / h.sum()

    d = np.abs(bigram(toks[0]) - bigram(toks[1])).sum()
    assert d > 0.5, d


def test_entropy_floor_reachable():
    s = MarkovMixture(vocab_size=32, k=2, alpha=0.0, seed=0)
    floor = s.entropy_floor()
    assert 0 < floor < np.log(32) + 1e-6


def test_shard_weights():
    s = make_regime("non_iid", k=4, vocab_size=16, imbalanced=True)
    w = shard_weights(s, weighted=True)
    assert w.shape == (4,)
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
    assert w[0] > w[-1]          # Zipf profile
    u = shard_weights(s, weighted=False)
    np.testing.assert_allclose(u, 0.25)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, sizes: dict):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()))


def test_logical_to_pspec_divisibility_fallback():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # starcoder2 KV: 4 kv heads don't divide 16 -> embed rows take model
    spec = logical_to_pspec(("embed", "kv_heads", None), (4608, 4, 128),
                            mesh)
    assert tuple(spec) == ("model", None, None)
    # whisper embed table: vocab 51866 doesn't divide -> embed gets it
    spec = logical_to_pspec(("vocab", "embed"), (51866, 1280), mesh)
    assert tuple(spec) == (None, "model")
    # clean case: heads win over embed
    spec = logical_to_pspec(("embed", "heads", None), (4096, 32, 128),
                            mesh)
    assert tuple(spec) == (None, "model", None)


def test_replica_axis_maps_to_pod():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    spec = logical_to_pspec(("replica", "embed", "ff"), (2, 1024, 4096),
                            mesh)
    assert tuple(spec) == ("pod", None, "model")


def test_batch_pspec_divisibility():
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert tuple(batch_pspec(mesh, 256, 2)) == (("data",), None) \
        or tuple(batch_pspec(mesh, 256, 2)) == ("data", None)
    # batch=1 cannot shard
    spec = batch_pspec(mesh, 1, 2)
    assert spec[0] is None


def test_boxed_unbox_roundtrip():
    tree = {"a": Boxed(jnp.ones((2, 3)), ("embed", "ff")),
            "b": {"c": Boxed(jnp.zeros((4,)), (None,))}}
    params, axes = unbox(tree)
    assert params["a"].shape == (2, 3)
    assert axes["a"] == ("embed", "ff")
    assert axes["b"]["c"] == (None,)


# ---------------------------------------------------------------------------
# optimizer & schedule
# ---------------------------------------------------------------------------

def test_adamw_matches_numpy_reference():
    key = jax.random.PRNGKey(0)
    p = {"w": jax.random.normal(key, (8, 4))}
    st = adamw.init(p)
    lr, b1, b2, eps, wd = 1e-3, 0.9, 0.95, 1e-8, 0.1
    pn = np.array(p["w"])
    m = np.zeros_like(pn)
    v = np.zeros_like(pn)
    cur = p
    for t in range(1, 5):
        g = {"w": jnp.full((8, 4), 0.5)}
        cur, st = adamw.update(g, st, cur, lr=lr, b1=b1, b2=b2, eps=eps,
                               weight_decay=wd)
        gn = np.full((8, 4), 0.5)
        m = b1 * m + (1 - b1) * gn
        v = b2 * v + (1 - b2) * gn * gn
        mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
        pn = pn - lr * (mh / (np.sqrt(vh) + eps) + wd * pn)
        np.testing.assert_allclose(cur["w"], pn, rtol=1e-5, atol=1e-6)


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, gn = adamw.clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(gn), np.sqrt(90 + 160), rtol=1e-6)
    total = np.sqrt(sum(np.sum(np.square(x))
                        for x in jax.tree.leaves(clipped)))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def test_warmup_cosine_shape():
    sched = make_warmup_cosine(1e-3, 100, 1000)
    assert float(sched(0)) == 0.0
    np.testing.assert_allclose(float(sched(100)), 1e-3, rtol=1e-5)
    assert float(sched(1000)) < float(sched(500)) < 1e-3
    np.testing.assert_allclose(float(sched(1000)), 1e-4, rtol=1e-2)


# ---------------------------------------------------------------------------
# schedules (Fig 7 / Fig 8)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,first,last", [
    ("constant_local", 1, 1), ("constant_distributed", 8, 8),
    ("doubling", 4, 8), ("halving", 8, 4),
    ("ramp_up", 1, 8), ("ramp_down", 8, 1)])
def test_compute_schedules(kind, first, last):
    s = schedules.compute_schedule(kind, 8, 10)
    assert s[0] == first and s[-1] == last
    assert s.min() >= 1 and s.max() <= 8


def test_doubling_equals_halving_total():
    a = schedules.compute_schedule("doubling", 8, 10)
    b = schedules.compute_schedule("halving", 8, 10)
    assert a.sum() == b.sum()


@given(p=st.floats(0.05, 0.9), seed=st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_drop_masks_rate(p, seed):
    rng = np.random.default_rng(seed)
    m = schedules.drop_masks(rng, p, 16, 200)
    rate = 1.0 - m.mean()
    assert abs(rate - p) < 0.08


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip():
    from repro.checkpoint import checkpoint as ckpt
    tree = {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                       "b": jnp.ones((3,))},
            "step": jnp.asarray(7)}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        ckpt.save(path, tree, metadata={"note": "test"})
        like = jax.tree.map(jnp.zeros_like, tree)
        out = ckpt.restore(path, like)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(a, b)
        assert ckpt.load_metadata(path)["note"] == "test"


def test_checkpoint_shape_mismatch_raises():
    from repro.checkpoint import checkpoint as ckpt
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        ckpt.save(path, {"w": jnp.ones((2, 2))})
        with pytest.raises(ValueError):
            ckpt.restore(path, {"w": jnp.ones((3, 3))})


def _transition_probs(s, toks, worker=None):
    """(n, V) next-token probabilities of stream ``worker``'s chain (the
    validation mixture when None) at current tokens ``toks``."""
    from repro.data import pipeline
    members, logw = (s._validation() if worker is None
                     else s._mixture(s.groups[worker:worker + 1]))
    return pipeline._mixture_rows(s, members[0], logw[0],
                                  jnp.asarray(toks, jnp.int32))


def test_markov_regroup_holds_process_fixed():
    """regroup(k) keeps the validation mixture identical and gives the
    k=1 worker exactly the mixture distribution."""
    s16 = MarkovMixture(vocab_size=32, k=16, alpha=1.0, seed=0)
    s4 = s16.regroup(4)
    s1 = s16.regroup(1)
    toks = jnp.arange(32)
    np.testing.assert_array_equal(np.asarray(_transition_probs(s16, toks)),
                                  np.asarray(_transition_probs(s4, toks)))
    np.testing.assert_allclose(np.asarray(_transition_probs(s1, toks, 0)),
                               np.asarray(_transition_probs(s16, toks)),
                               rtol=1e-5)
    t = s4.sample_all_shards(jax.random.PRNGKey(0), 2, 16)
    assert t.shape == (4, 2, 16)
    assert s16.entropy_floor() == s4.entropy_floor()


def _avals(jaxpr):
    """Every intermediate aval of a closed jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(getattr(sub, "jaxpr", sub))


def test_markov_published_vocab_has_no_square_array():
    """At V=32000 the generator builds quickly, holds and traces no
    V×V array (sampling and entropy floor), and samples valid tokens."""
    import time
    V = 32_000
    t0 = time.time()
    s = make_regime("non_iid", k=8, vocab_size=V)
    key = jax.random.PRNGKey(0)
    toks = np.asarray(s.sample_all_shards(key, 2, 8))
    assert time.time() - t0 < 30
    assert toks.shape == (8, 2, 8) and toks.dtype == np.int32
    assert (toks >= 0).all() and (toks < V).all()
    held = [np.size(v) for v in vars(s).values()]
    assert max(held) < V * V
    for fn in (lambda kk: s.sample_all_shards(kk, 2, 8),
               lambda kk: s.sample_validation(kk, 2, 8),
               lambda kk: s.regroup(2).sample_shard(kk, 1, 2, 8),
               lambda kk: _transition_probs(s, jnp.arange(4)),
               lambda kk: _entropy_floor_graph(s)):
        jaxpr = jax.make_jaxpr(fn)(key).jaxpr
        biggest = max(int(np.prod(a.shape)) for a in _avals(jaxpr)
                      if hasattr(a, "shape"))
        assert biggest < V * V, biggest


def _entropy_floor_graph(s):
    from repro.data import pipeline
    members, logw = s._validation()
    return pipeline._entropy_floor(s, members[0], logw[0])
