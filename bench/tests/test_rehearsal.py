"""CPU rehearsal of every cell of BENCHMARK.json at smoke size with
interpret-mode kernels, and the refusal of a real run without a chip."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", cells())
def test_cell_rehearses_to_a_correct_check(name):
    r = run.run_cell(name, 2**31 + 5, 0.0, False, smoke=True)
    assert r["correct"], r["compared"]
    assert r["metrics"] == {}
    assert r["window"]["rounds"] >= 1
    assert r["window"]["compiles_in_window"] == 0


def test_real_run_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_chip_prints_no_result(monkeypatch, capsys):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99 x")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake] * 4)
    assert run.main(["--workload", cells()[0], "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "TPU v99 x" in out.err
