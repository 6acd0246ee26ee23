"""The configuration's model keys and its block file: every model key a
file states reaches the trainer and is checked against the program, an
unknown key is refused, the dense block's counts and refusals, the
parameter-count guard, and a block file added where the lookup looks
serving the counts, the reference model and the check."""
import importlib
import json
import os
import sys
import textwrap
import types

import pytest

from bench import blocks, run
from bench import trace as tr
from bench.blocks import dense
from bench.run import TracedRun
from bench.tests.test_rehearsal import cells

DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the model keys the harness applied and checked before blocks had files
OLD_KEYS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads",
            "head_dim", "d_ff", "vocab_size", "pos_emb", "rope_theta",
            "norm", "act", "mlp_gated", "tie_embeddings", "init_scale",
            "compute_dtype", "remat")
SEED = 2**31 + 11


def file_cfg(config):
    with open(os.path.join(run.ROOT, "bench", "configs",
                           f"{config}.json")) as f:
        return json.load(f)


def trainer():
    from repro.launch import train
    return train


def built(job, cfg, smoke=True):
    """(train, args): the trainer and its command line for ``job`` and
    ``cfg``."""
    train = trainer()
    args = train.make_parser().parse_args(
        run.train_argv(job, cfg, SEED, smoke))
    return train, args


@pytest.fixture
def block_dir(tmp_path, monkeypatch):
    """An empty directory where the lookup looks for block files (the
    dense block stays imported)."""
    monkeypatch.setattr(blocks, "__path__", [str(tmp_path)])
    yield tmp_path
    for path in tmp_path.glob("*.py"):
        sys.modules.pop(f"{blocks.__name__}.{path.stem}", None)
        if hasattr(blocks, path.stem):
            delattr(blocks, path.stem)


def write_block(directory, name, body):
    (directory / f"{name}.py").write_text(textwrap.dedent(body))
    importlib.invalidate_caches()


# ---------------------------------------------------------------------------
# model keys
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", ["diloco_150m", "diloco_400m"])
def test_the_files_apply_and_check_the_old_seventeen_keys(config):
    assert set(run.model_keys(file_cfg(config))) == set(OLD_KEYS)


@pytest.mark.parametrize("name", cells())
def test_every_free_dense_key_is_checked_against_the_program(name):
    job, cfg = run.load_cell(name, smoke=True)
    train, args = built(job, cfg)
    undo = run.use_config(train, cfg)
    try:
        assert run.program_gaps(train, args, job, cfg) == {}
        assert blocks.load(cfg) is dense
        other = {k: (not v if isinstance(v, bool) else
                     "gelu" if k == "act" else v * 2) for k, v in cfg.items()
                 if k in dense.KEYS}
        gaps = run.program_gaps(train, args, job, dict(cfg, **other))
    finally:
        undo()
    assert set(dense.KEYS) <= set(gaps)
    assert gaps["d_model"] == (cfg["d_model"], 2 * cfg["d_model"])


@pytest.mark.parametrize("key,value", [("window", 128), ("qk_norm", True),
                                       ("logit_softcap", 30.0)])
def test_a_program_off_the_blocks_fixed_values_is_refused(key, value,
                                                          monkeypatch):
    from repro.models.registry import Arch
    job, cfg = run.load_cell(cells()[0], smoke=True)
    assert key not in cfg
    train, args = built(job, cfg)
    get = train.get_smoke_arch
    monkeypatch.setattr(train, "get_smoke_arch", lambda name: Arch(
        cfg=get(name).cfg.replace(**{key: value})))
    undo = run.use_config(train, cfg)
    try:
        gaps = run.program_gaps(train, args, job, cfg)
        with pytest.raises(SystemExit, match=key):
            run.check_program(train, args, job, cfg)
    finally:
        undo()
    assert gaps[key] == (value, dense.FIXED[key])


# DeepSeek-V2-Lite's smoke sizes as a configuration file would state
# them, with 8 experts where the registry's smoke model has 4
DEEPSEEK = {
    "arch": "deepseek_v2_lite_16b", "block": "mla_moe",
    "family": "moe", "n_layers": 2, "d_model": 128, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 32, "v_head_dim": 32, "d_ff": 128,
    "vocab_size": 256, "mla": True, "kv_lora_rank": 64,
    "rope_head_dim": 16, "n_experts": 8, "n_shared_experts": 1, "top_k": 2,
    "moe_d_ff": 128, "capacity_factor": 4.0,
    "param_dtype": "float32", "master_dtype": "float32"}
MLA_MOE = """
    KEYS = {keys!r}
    FIXED = {{}}


    def n_params(cfg):
        return 0            # this block's counts are not under test
    """


@pytest.fixture
def deepseek(block_dir):
    keys = tuple(k for k in DEEPSEEK if k not in blocks.BOOKKEEPING)
    write_block(block_dir, "mla_moe", MLA_MOE.format(keys=keys))
    job = dict(run.load_cell(cells()[0], smoke=True)[0])
    return job, dict(DEEPSEEK)


def test_a_files_moe_and_mla_keys_reach_the_trainer(deepseek):
    from repro.models.registry import get_smoke_arch
    job, cfg = deepseek
    assert get_smoke_arch(cfg["arch"]).cfg.n_experts == 4
    assert {"n_experts", "kv_lora_rank", "mla", "top_k"} <= set(
        run.model_keys(cfg))
    train = trainer()
    undo = run.use_config(train, cfg)
    try:
        mcfg = train.get_smoke_arch(cfg["arch"]).cfg
    finally:
        undo()
    assert mcfg.n_experts == 8 and mcfg.kv_lora_rank == 64 and mcfg.mla


@pytest.mark.parametrize("key,value", [("n_experts", 6),
                                       ("kv_lora_rank", 32)])
def test_check_refuses_a_program_that_differs_in(deepseek, key, value):
    job, cfg = deepseek
    train, args = built(job, cfg)
    undo = run.use_config(train, cfg)
    try:
        same = run.program_gaps(train, args, job, cfg)
        gaps = run.program_gaps(train, args, job, dict(cfg, **{key: value}))
        with pytest.raises(SystemExit, match=key):
            run.check_program(train, args, job, dict(cfg, **{key: value}))
    finally:
        undo()
    assert set(same) == {"n_params (block)"}
    assert set(gaps) == {key, "n_params (block)"}
    assert gaps[key] == (cfg[key], value)


@pytest.mark.parametrize("key", ["n_expert", "kernel_mode", "hidden_size"])
def test_an_unknown_file_key_is_refused_with_its_name(key):
    cfg = dict(file_cfg("diloco_150m"), **{key: 8})
    with pytest.raises(SystemExit, match=key):
        run.model_keys(cfg)
    with pytest.raises(SystemExit, match=key):
        run.use_config(trainer(), cfg)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config,params,flops", [
    ("diloco_150m", 149_804_928, 1_049_690_112),
    ("diloco_400m", 388_929_024, 2_559_836_160)])
def test_dense_counts(config, params, flops):
    cfg = file_cfg(config)
    assert blocks.load(cfg) is dense
    assert dense.n_params(cfg) == params == cfg["n_params"]
    assert dense.flops_per_token(cfg, 1024) == flops


@pytest.fixture(scope="module")
def scoped():
    """The recorded round program of ``test_scopes.py`` with the
    configuration's parameter dtype."""
    from repro.models.registry import get_smoke_arch
    m = get_smoke_arch("diloco_150m").cfg
    cfg = {k: getattr(m, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
        "vocab_size", "mlp_gated", "tie_embeddings")}
    cfg["head_dim"] = m.resolved_head_dim
    cfg["param_dtype"] = "float32"
    job = {"replicas": 2, "inner_steps": 2, "batch": 2, "seq": 128}
    path = os.path.join(DATA, "scoped.xplane.pb")
    traced = TracedRun(tr.load(path), job, cfg, PEAKS, [0], 2)
    traced.trace_path = path
    return traced


@pytest.mark.parametrize("name,value", [
    ("step_mfu", 1.3691646456033442),
    ("inner_mfu", 3.1308536042805435),
    ("adamw_kernel_roofline", 213.01002381858999),
    ("outer_nesterov_roofline", 336.645892123666)])
def test_count_readers_read_what_they_read_before_blocks(scoped, name, value):
    reader = importlib.import_module(f"bench.metrics.{name}")
    assert reader.read(scoped) == value


@pytest.mark.parametrize("extra", [{"mla": True}, {"n_experts": 64},
                                   {"kv_lora_rank": 512},
                                   {"family": "moe"}])
def test_the_dense_block_refuses_a_non_dense_file(extra):
    with pytest.raises(ValueError, match=next(iter(extra))):
        blocks.load(dict(file_cfg("diloco_150m"), **extra))


def test_a_block_names_its_file():
    with pytest.raises(ValueError, match="no_such_block"):
        blocks.load({"block": "no_such_block"})


def test_the_count_guard_refuses_a_block_off_by_one(block_dir):
    write_block(block_dir, "dense_off", """
        from bench.blocks import dense
        KEYS, FIXED = dense.KEYS, dense.FIXED


        def n_params(cfg):
            return dense.n_params(cfg) + 1
        """)
    job, cfg = run.load_cell(cells()[0], smoke=True)
    off = dict(cfg, block="dense_off")
    train, args = built(job, off)
    undo = run.use_config(train, off)
    try:
        gaps = run.program_gaps(train, args, job, off)
        with pytest.raises(SystemExit, match="n_params"):
            run.check_program(train, args, job, off)
    finally:
        undo()
    size = dense.n_params(cfg)
    assert gaps == {"n_params (block)": (size, size + 1)}


def test_the_count_guard_holds_the_files_count_at_full_size():
    for name in cells():
        job, cfg = run.load_cell(name)
        train, args = built(job, cfg, smoke=False)
        undo = run.use_config(train, cfg)
        try:
            assert run.program_gaps(train, args, job, cfg) == {}
            gaps = run.program_gaps(train, args, job,
                                    dict(cfg, n_params=cfg["n_params"] - 1))
        finally:
            undo()
        assert gaps == {"n_params": (cfg["n_params"], cfg["n_params"] - 1)}


# ---------------------------------------------------------------------------
# a new kind of block is a new file
# ---------------------------------------------------------------------------

TOY = """
    from bench.blocks import dense

    KEYS, FIXED = dense.KEYS, dense.FIXED
    CALLS = []


    def _recorded(fn):
        def call(*a, **kw):
            CALLS.append(fn.__name__)
            return fn(*a, **kw)
        return call


    n_params = _recorded(dense.n_params)
    flops_per_token = _recorded(dense.flops_per_token)
    init_params = _recorded(dense.init_params)
    loss = _recorded(dense.loss)
    row_block = _recorded(dense.row_block)
    """


def test_a_block_file_added_serves_counts_reference_and_check(
        block_dir, monkeypatch):
    write_block(block_dir, "toy", TOY)
    name = cells()[0]
    load_cell = run.load_cell

    def with_toy(cell, smoke=False):
        job, cfg = load_cell(cell, smoke)
        return job, dict(cfg, block="toy")

    monkeypatch.setattr(run, "load_cell", with_toy)
    r = run.run_cell(name, SEED, 0.0, False, smoke=True)
    assert r["correct"], r["compared"]
    toy = blocks.load({"block": "toy"})
    assert {"n_params", "init_params", "loss", "row_block"} <= set(toy.CALLS)

    from bench.metrics import step_mfu
    cfg = with_toy(name, smoke=True)[1]
    traced = types.SimpleNamespace(
        trace=tr.Trace(ops={0: [tr.Op("a", 0, 1e9)]}), chips=[0], lo=0,
        hi=1e9, tokens=1000, cfg=cfg, job={"seq": 16},
        peaks={"bf16_flops_per_s": 1e12})
    del toy.CALLS[:]
    assert step_mfu.read(traced) == pytest.approx(
        100 * blocks.load(cfg).flops_per_token(cfg, 16) * 1000 / 1e12)
    assert toy.CALLS[0] == "flops_per_token"
