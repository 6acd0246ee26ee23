"""The trace reduction and the peak table, on synthetic intervals and on
a small trace recorded on a TPU v5e (``record_small_trace.py``)."""
import os

import pytest

from bench import peaks
from bench import trace as tr
from bench.metrics.adamw_kernel_roofline import is_adamw
from bench.metrics.outer_nesterov_roofline import is_nesterov

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_merges_overlaps_and_clips():
    ivs = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)]
    assert tr.union(ivs, 1, 25) == [(1, 3), (5, 12), (20, 25)]
    assert tr.busy_ns(ivs, 1, 25) == 2 + 7 + 5


def test_gaps_are_the_complement():
    ivs = [(2, 4), (3, 6), (8, 9)]
    assert tr.gaps(ivs, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_self_time_subtracts_nested_ops():
    ops = [tr.Op("loop", 0, 100), tr.Op("a", 10, 30), tr.Op("b", 40, 90),
           tr.Op("c", 50, 60), tr.Op("d", 120, 130)]
    st = tr.self_times(ops)
    assert st == {"loop": 30, "a": 20, "b": 40, "c": 10, "d": 10}


def test_collective_exposure_counts_only_uncovered_time():
    ops = [tr.Op("%all-reduce.1 = f32[4]", 0, 10),
           tr.Op("%fusion.2 = f32[4]", 4, 6),
           tr.Op("%all-gather.3 = f32[4]", 20, 30),
           tr.Op("%fusion.4 = f32[4]", 25, 40)]
    assert tr.is_collective(ops[0].name) and not tr.is_collective(ops[1].name)
    assert tr.exposed_ns(ops, tr.is_collective, 0, 40) == 8 + 5


def test_kernel_calling_conventions():
    adamw = ('%closed_call.4 = (f32[84,128]{1,0:T(8,128)}, f32[84,128]{1,0}, '
             'f32[84,128]{1,0}) custom-call(f32[3]{0:T(128)S(1)} %a, '
             'f32[84,128]{1,0} %b), custom_call_target="tpu_custom_call"')
    nest = ('%closed_call.9 = (f32[7,128]{1,0}, f32[7,128]{1,0}) '
            'custom-call(f32[1]{0:T(128)} %s, f32[7,128]{1,0} %p), '
            'custom_call_target="tpu_custom_call"')
    assert is_adamw(adamw) and not is_nesterov(adamw)
    assert is_nesterov(nest) and not is_adamw(nest)
    assert not is_adamw("%fusion.1 = f32[3] fusion(f32[3] %x)")


def test_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v99 imaginary")


@pytest.fixture(scope="module")
def small():
    return tr.load(SMALL)


def test_small_trace_window_spans_and_idle_gap(small):
    assert sorted(small.ops) == [0]
    names = [s.name for s in small.spans]
    assert names == ["bench.dispatch", "bench.ingest"] * 2
    lo, hi = tr.window(small)
    ops = [(o.start, o.end) for o in small.ops[0]]
    busy = tr.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    longest = max(tr.gaps(ops, lo, hi), key=lambda g: g[1] - g[0])
    # the 20 ms sleep between the two dispatches leaves the chip idle
    assert longest[1] - longest[0] >= 19e6
    assert tr.label_gap(small, *longest) == "host between calls"


def test_small_trace_kernels_found_by_calling_convention(small):
    ops = small.ops[0]
    assert sum(is_adamw(o.name) for o in ops) == 1
    assert sum(is_nesterov(o.name) for o in ops) == 1
    st = tr.self_times(ops)
    assert all(v >= 0 for v in st.values())
    assert sum(st.values()) == pytest.approx(
        tr.busy_ns([(o.start, o.end) for o in ops], -1e18, 1e18), rel=1e-6)


def test_step_mfu_divides_by_device_busy_time_not_the_window():
    import types
    from bench.metrics import step_mfu
    from bench.blocks.dense import flops_per_token
    ops = {0: [tr.Op("a", 0, 4e9), tr.Op("b", 6e9, 8e9)],
           1: [tr.Op("a", 0, 6e9)]}
    cfg = {"n_layers": 2, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
           "head_dim": 32, "d_ff": 256, "vocab_size": 100,
           "mlp_gated": False, "tie_embeddings": True}
    run = types.SimpleNamespace(
        trace=tr.Trace(ops=ops), chips=[0, 1], lo=0, hi=10e9, tokens=1000,
        cfg=cfg, job={"seq": 16}, peaks={"bf16_flops_per_s": 1e6})
    want = 100 * flops_per_token(cfg, 16) * 1000 / (6.0 * 2 * 1e6)
    assert step_mfu.read(run) == pytest.approx(want)
