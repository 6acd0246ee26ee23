"""Subprocess harness for crash-grade experiments.

The resilience claims are about surviving the *process* dying, so the
benchmarks cannot run in-process: this module launches real
``repro.launch.train`` subprocesses, lets the injected ``Crash`` event
SIGKILL them mid-run, corrupts their newest snapshot on purpose, and
relaunches them with ``--resume auto`` — then reads back the
``--state-hash-out`` JSON to compare final states bit-for-bit.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# SIGKILL'd processes exit -9 from the harness's point of view; the
# launcher's own crash path uses os.kill(os.getpid(), SIGKILL).
SIGKILL_RC = -9


def train_cmd(args) -> list:
    return [sys.executable, "-m", "repro.launch.train",
            *[str(a) for a in args]]


def train_env(*, devices: int | None = None) -> dict:
    """Environment for a train subprocess: src on PYTHONPATH, the
    parent's platform, optionally a forced host device count (the
    sharded transport's pods)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    if devices is not None:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}").strip()
    return env


def run_train(args, *, devices: int | None = None, check: bool = True,
              timeout: float = 1200.0) -> subprocess.CompletedProcess:
    """Run one train subprocess to completion. ``check=False`` for
    runs that are EXPECTED to die (crash injection)."""
    proc = subprocess.run(
        train_cmd(args), env=train_env(devices=devices),
        capture_output=True, text=True, timeout=timeout)
    if check and proc.returncode != 0:
        raise RuntimeError(
            f"train subprocess failed rc={proc.returncode}\n"
            f"cmd: {' '.join(train_cmd(args))}\n"
            f"stdout:\n{proc.stdout[-4000:]}\n"
            f"stderr:\n{proc.stderr[-4000:]}")
    return proc


def run_until_crash(args, *, devices: int | None = None,
                    timeout: float = 1200.0) -> subprocess.CompletedProcess:
    """Run a subprocess that carries a crash injection and assert it
    really died by SIGKILL (a clean exit means the injection never
    fired — a harness bug worth failing loudly on)."""
    proc = run_train(args, devices=devices, check=False, timeout=timeout)
    if proc.returncode == 0:
        raise RuntimeError(
            "crash-injected run exited cleanly — the Crash event "
            f"never fired\nstdout:\n{proc.stdout[-4000:]}")
    return proc


def corrupt_latest(ckpt_dir: str, *, mode: str = "truncate") -> str:
    """Damage the newest snapshot in ``ckpt_dir`` so its manifest no
    longer verifies. ``truncate`` chops the npz mid-file (the classic
    mid-write kill artifact); ``bitflip`` flips one payload byte
    (bit rot — the file still opens, the hashes disagree)."""
    from .manager import CheckpointManager
    mgr = CheckpointManager(ckpt_dir)
    steps = mgr.steps()
    if not steps:
        raise FileNotFoundError(f"no snapshots in {ckpt_dir}")
    path = mgr.path_of(steps[-1])
    size = os.path.getsize(path)
    if mode == "truncate":
        with open(path, "r+b") as f:
            f.truncate(max(1, size // 2))
    elif mode == "bitflip":
        with open(path, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0xFF]))
    else:
        raise ValueError(f"unknown corruption mode {mode!r}")
    return path


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
