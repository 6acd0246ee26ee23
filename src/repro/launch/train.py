"""DiLoCo training driver (CLI).

Runs the paper's algorithm end-to-end: optional single-worker
pretraining phase, then T rounds of (H inner AdamW steps × k replicas +
one outer Nesterov step), with the paper's robustness features
switchable from the command line (data regime, communication drops,
adaptive compute schedule, outer-gradient pruning, outer optimizer).

On CPU this drives the reduced-scale models (--smoke, default) used by
the benchmark suite; the same functions lower onto the production mesh
(see dryrun.py) for TPU execution.

Example:
  PYTHONPATH=src python -m repro.launch.train \
      --arch diloco_150m --smoke --k 4 --H 20 --rounds 30 \
      --regime non_iid --outer-opt nesterov
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import resilience
from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import DiLoCoConfig, TrainConfig
from repro.core import diloco, faults, pod_collectives, schedules
from repro.data.sharding import make_regime, shard_weights
from repro.models.registry import get_arch, get_smoke_arch
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself), else the fixed ``<repo>/.jax_cache`` — a fixed path, since
    the path is part of the cache key. Call from entry points only."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _int_list(spec: str, k: int, name: str) -> tuple:
    """Parse a comma list of ints; a single value broadcasts to k."""
    try:
        vals = [int(x) for x in spec.split(",") if x.strip()]
    except ValueError:
        raise SystemExit(f"{name} wants comma-separated ints, "
                         f"got {spec!r}")
    if len(vals) == 1:
        vals = vals * k
    if len(vals) != k:
        raise SystemExit(f"{name} needs 1 or k={k} values, "
                         f"got {len(vals)}")
    return tuple(vals)


def scenario_of(args) -> faults.Scenario | None:
    """Build the ``faults.Scenario`` scripted by the CLI fault flags,
    or None when no fault flag is set (the legacy mask path — kept
    bit-identical for existing sync/streaming/sharded defaults).

    Round-driven transports project the scenario onto per-round masks
    (``Scenario.round_masks``); the async engine consumes its full
    event timeline. ``--drop-prob`` alone does NOT trigger a scenario
    (the legacy i.i.d. drop-mask path keeps its exact rng stream);
    combined with any other fault flag it becomes the scenario's
    per-send drop probability with retry/backoff semantics.
    """
    used = (args.speeds or args.link_latency
            or args.latency_jitter > 0 or args.max_retries > 0
            or args.preempt or args.transport == "async"
            or args.crash_at_tick >= 0 or args.crash_at_round >= 0
            or args.nan_bomb)
    if not used:
        return None
    k = args.k
    preempts = []
    for spec in args.preempt:
        parts = spec.split(":")
        if len(parts) not in (2, 3):
            raise SystemExit(
                f"--preempt wants WORKER:LEAVE[:REJOIN], got {spec!r}")
        w, leave = int(parts[0]), int(parts[1])
        rejoin = int(parts[2]) if len(parts) == 3 else 0
        preempts.append((w, leave, rejoin))
    scen = faults.Scenario(
        speeds=_int_list(args.speeds, k, "--speeds")
        if args.speeds else (1,) * k,
        latency=_int_list(args.link_latency, k, "--link-latency")
        if args.link_latency else (),
        latency_jitter=args.latency_jitter,
        drop_prob=args.drop_prob,
        max_retries=args.max_retries,
        retry_backoff=args.retry_backoff,
        preemptions=tuple(preempts),
        seed=args.seed)
    # crash / NaN-bomb injections ride the scenario too. Round-domain
    # flags convert through the barrier pacing T (one round = T ticks),
    # so Scenario.crash_round / nan_masks project them right back.
    T = scen.sync_round_ticks(k)
    crash_tick = args.crash_at_tick
    if args.crash_at_round >= 0:
        crash_tick = args.crash_at_round * T
    bombs = []
    for spec in args.nan_bomb:
        parts = spec.split(":")
        if len(parts) != 2:
            raise SystemExit(
                f"--nan-bomb wants WORKER:ROUND, got {spec!r}")
        bombs.append((int(parts[0]), int(parts[1]) * T))
    if crash_tick >= 0 or bombs:
        scen = dataclasses.replace(scen, crash_tick=crash_tick,
                                   nan_bombs=tuple(bombs))
    return scen


def build(args):
    arch = (get_smoke_arch if args.smoke else get_arch)(args.arch)
    # the model's attention takes the Pallas kernels where the job does
    arch = dataclasses.replace(
        arch, cfg=arch.cfg.replace(kernel_mode=args.kernel_mode))
    cfg = arch.cfg
    if not args.stream_fragments and args.transport in ("simulated",
                                                        "sharded"):
        # these knobs only act on the streaming outer path — silently
        # running the classic full-precision outer step while the CLI
        # says "int4" would mislabel every reported number
        ignored = [flag for flag, on in (
            ("--outer-grad-dtype", args.outer_grad_dtype != "float32"),
            ("--stream-alpha", args.stream_alpha != 1.0),
            ("--stream-tau", args.stream_tau != 0),
            ("--error-feedback", args.error_feedback),
            ("--transport", args.transport != "simulated"),
            ("--no-pack-wire", not args.pack_wire),
            ("--pods", args.pods != 0)) if on]
        if ignored:
            raise SystemExit(
                f"{', '.join(ignored)} require(s) --stream-fragments "
                ">= 1 (streaming outer sync); the classic outer step "
                "would ignore them")
    if args.transport in ("async", "gossip"):
        # barrier-free transports: streaming mechanics that have no
        # meaning off the fragment-round path are rejected, not ignored
        bad = [flag for flag, on in (
            ("--stream-alpha", args.stream_alpha != 1.0),
            ("--stream-tau", args.stream_tau != 0),
            ("--no-pack-wire", not args.pack_wire),
            ("--pods", args.pods != 0),
            ("--legacy-loop", args.legacy_loop),
            ("--cosine-stats", args.cosine_stats)) if on]
        if args.transport == "async" and args.stream_fragments:
            bad.insert(0, "--stream-fragments")
        if bad:
            raise SystemExit(
                f"{', '.join(bad)} do(es) not act on "
                f"--transport {args.transport}")
    if args.pods and args.transport != "sharded":
        # --pods only shapes the sharded-transport mesh; accepting it
        # on the simulated path would fake a multi-pod layout
        raise SystemExit("--pods requires --transport sharded")
    if args.restore and args.transport != "async":
        raise SystemExit("--restore resumes a full async engine state; "
                         "round transports resume from --checkpoint-dir "
                         "snapshots (--resume auto) instead")
    # ---- resilience flag validation ----
    if not args.checkpoint_dir:
        need_dir = [flag for flag, on in (
            ("--resume", bool(args.resume)),
            ("--checkpoint-every", args.checkpoint_every > 0)) if on]
        if need_dir:
            raise SystemExit(f"{', '.join(need_dir)} require(s) "
                             "--checkpoint-dir")
    if args.legacy_loop and (args.checkpoint_dir or args.guard
                             or args.crash_at_round >= 0
                             or args.nan_bomb):
        raise SystemExit("--checkpoint-dir/--guard/--crash-at-round/"
                         "--nan-bomb need the scanned driver's chunk "
                         "boundaries; drop --legacy-loop")
    if args.crash_at_tick >= 0 and args.crash_at_round >= 0:
        raise SystemExit("--crash-at-tick and --crash-at-round are "
                         "exclusive (tick = async domain, round = "
                         "barrier domain)")
    if args.crash_at_tick >= 0 and args.transport != "async":
        raise SystemExit("--crash-at-tick addresses the async event "
                         "timeline; round transports use "
                         "--crash-at-round")
    if args.nan_bomb and (args.transport != "simulated"
                          or args.stream_fragments):
        raise SystemExit("--nan-bomb injects into the classic outer "
                         "reduce (--transport simulated, no "
                         "--stream-fragments)")
    if args.guard_clip > 0 and not args.guard_outer:
        raise SystemExit("--guard-clip scales deltas inside the "
                         "in-graph guard; add --guard-outer")
    if args.resume and args.resume != "auto" \
            and not args.resume.isdigit():
        raise SystemExit(f"--resume wants 'auto' or a snapshot step, "
                         f"got {args.resume!r}")
    dcfg = DiLoCoConfig(k=args.k, H=args.H, outer_opt=args.outer_opt,
                        outer_lr=args.outer_lr,
                        outer_momentum=args.outer_momentum,
                        drop_prob=args.drop_prob,
                        prune_frac=args.prune_frac,
                        weighted_avg=args.weighted,
                        kernel_mode=args.kernel_mode,
                        streaming_fragments=args.stream_fragments,
                        stream_alpha=args.stream_alpha,
                        stream_tau=args.stream_tau,
                        outer_grad_dtype=args.outer_grad_dtype,
                        error_feedback=args.error_feedback,
                        transport=args.transport,
                        pack_wire=args.pack_wire,
                        param_dtype=args.param_dtype,
                        master_dtype=args.master_dtype,
                        staleness_lambda=args.staleness_lambda,
                        gossip_pairing=args.gossip_pairing,
                        gossip_mix=args.gossip_mix,
                        guard_outer=args.guard_outer,
                        guard_clip=args.guard_clip)
    total = args.pretrain_steps + args.rounds * args.H
    tcfg = TrainConfig(inner_lr=args.inner_lr, warmup_steps=args.warmup,
                       total_steps=total, batch_size=args.batch,
                       seq_len=args.seq, seed=args.seed,
                       kernel_mode=args.kernel_mode,
                       param_dtype=args.param_dtype,
                       master_dtype=args.master_dtype)
    sampler = make_regime(args.regime, k=args.k,
                          vocab_size=cfg.vocab_size, seed=args.seed,
                          imbalanced=args.weighted)
    return arch, cfg, dcfg, tcfg, sampler


def _run_async_phase(args, dcfg, tcfg, loss_fn, sampler, params,
                     ev, val, rec):
    """Barrier-free driver: the event loop replaces the round loop.

    One tick = the fastest worker's phase; ``--ticks 0`` matches the
    wall-clock budget a barrier-paced run of --rounds rounds would pay
    under the same scenario, so async-vs-sync numbers compare at equal
    simulated time. ``rec`` (the run's ``RunRecorder``) receives every
    engine event as it happens and owns the console output."""
    from repro.core import async_diloco
    scenario = scenario_of(args) or faults.Scenario.uniform(args.k)
    samplers = tuple(
        (lambda i: lambda kk, B, S: sampler.sample_shard(kk, i, B, S))(i)
        for i in range(args.k))
    eng = async_diloco.AsyncEngine(
        loss_fn, samplers, dcfg, tcfg, scenario=scenario,
        total_steps=tcfg.total_steps, eval_fn=ev, eval_tokens=val,
        seed=args.seed)
    mgr = (resilience.CheckpointManager(args.checkpoint_dir,
                                        retain=args.retain)
           if args.checkpoint_dir else None)
    resumed_from = -1
    if args.resume and mgr is not None:
        step = (mgr.latest_good() if args.resume == "auto"
                else int(args.resume))
        if step is None:
            rec.note("resume: no verified snapshot, starting fresh")
            state = eng.init_state(params)
        else:
            state = async_diloco.state_from_tree(
                mgr.load_tree(step), params)
            resumed_from = step
            rec.note(f"resumed async snapshot {step}: "
                     f"version={state.version} "
                     f"events_done={state.events_done}")
    elif args.restore:
        state = async_diloco.state_from_tree(
            ckpt.restore_tree(args.restore), params)
        rec.note(f"restored async state: version={state.version} "
                 f"events_done={state.events_done}")
    else:
        state = eng.init_state(params)
    ticks = args.ticks or scenario.sync_round_ticks(args.k) * args.rounds
    eng._bind(state)
    rec.attach_wire_plan([{"fragment": 0, "wire_bytes":
                           float(eng.wire_bytes()),
                           "wire_dtype": dcfg.outer_grad_dtype}])
    rec.note(f"async transport: lambda={dcfg.staleness_lambda} "
             f"k={args.k} {ticks} tick(s), {eng.wire_bytes()} B/apply")
    on_crash = None
    if scenario.crash_tick >= 0:
        def on_crash(_state):
            rec.note(f"crash: SIGKILL at tick {scenario.crash_tick}")
            os.kill(os.getpid(), signal.SIGKILL)
    t0 = time.time()
    if mgr is not None and args.checkpoint_every > 0:
        # sliced event loop: a durable snapshot every N events — the
        # engine's events_done cursor is the resume point
        hist = []
        while True:
            state, h = eng.run(state, ticks=ticks,
                               max_events=args.checkpoint_every,
                               recorder=rec, on_crash=on_crash)
            hist.extend(h)
            mgr.save(state.events_done,
                     async_diloco.state_to_tree(state),
                     metadata={"transport": "async", "k": args.k,
                               "events_done": state.events_done})
            if len(h) < args.checkpoint_every:
                break
    else:
        state, hist = eng.run(state, ticks=ticks, recorder=rec,
                              on_crash=on_crash)
    n_arr = sum(1 for r in hist if r["event"] == "arrival")
    rec.note(f"done in {time.time() - t0:.1f}s; {n_arr} applications "
             f"over {ticks} ticks; entropy floor = "
             f"{sampler.entropy_floor():.4f}")
    if args.trace:
        tb = obs_trace.async_trace(scenario, args.k, ticks,
                                   history=hist,
                                   wire_bytes=eng.wire_bytes())
        tb.write(args.trace, other_data={"manifest": rec.manifest})
        rec.note(f"trace: {args.trace}")
    if args.out:
        rec.dump(args.out, args=vars(args))
        rec.note(f"wrote {args.out}")
    if args.checkpoint:
        # FULL engine state (workers, snapshots, outer, cursor): a
        # later --restore resumes the identical event suffix
        ckpt.save(args.checkpoint, async_diloco.state_to_tree(state),
                  metadata={"transport": "async", "k": args.k,
                            "H": args.H, "ticks": ticks,
                            "events_done": state.events_done})
        rec.note(f"checkpoint: {args.checkpoint}")
    if args.state_hash_out:
        vals = [r["val_loss"] for r in hist if "val_loss" in r]
        ckpt.atomic_write_json(args.state_hash_out, {
            "state_sha256": resilience.tree_sha256(
                async_diloco.state_to_tree(state)),
            "final_val_loss": vals[-1] if vals else None,
            "resumed_from_step": resumed_from,
            "events_done": int(state.events_done),
            "ingest_calls": rec.ingest_calls,
            "rollbacks": 0}, indent=2)
        rec.note(f"state hash: {args.state_hash_out}")
    return rec.records


def _init_transport_state(args, dcfg, params, rec):
    """The DiLoCo phase's state for the configured transport, placed
    where it runs. Returns (state, mesh, plan, frag_wire,
    round_wire): the pod mesh of the sharded transport (else None),
    the streaming sync plan, the gossip per-fragment exchange bytes
    and the classic/streaming bytes per replica and round."""
    mesh = None
    frag_wire = None           # gossip: per-fragment exchange bytes
    round_wire = None          # classic/streaming: bytes/replica/round
    plan = ()
    if dcfg.transport == "gossip":
        from repro.core import gossip
        state = gossip.init_state(params, dcfg)
        frag_wire = gossip.frag_bytes(params, dcfg)
        rec.attach_wire_plan([{"fragment": i, "wire_bytes": float(b),
                               "wire_dtype": dcfg.outer_grad_dtype}
                              for i, b in enumerate(frag_wire)])
        rec.note(f"gossip transport: {dcfg.gossip_pairing} pairing, "
                 f"mix={dcfg.gossip_mix}, "
                 f"P={max(1, dcfg.streaming_fragments)} fragment(s), "
                 f"{max(frag_wire)} B/exchange")
    elif dcfg.streaming_fragments:
        from repro.core import streaming
        plan = streaming.sync_plan(params, dcfg)
        round_wire = sum(row["wire_bytes"] for row in plan)
        rec.attach_wire_plan(plan)
        if dcfg.transport == "sharded":
            from repro.launch.mesh import make_pod_mesh
            # default: the largest pod count that bands k evenly AND
            # tiles the visible devices (min(k, devices) alone crashes
            # on e.g. k=4 over 6 devices although pods=2 works)
            n_dev = jax.device_count()
            pods = args.pods or max(
                (p for p in range(2, args.k + 1)
                 if args.k % p == 0 and n_dev % p == 0), default=1)
            if pods < 2:
                raise SystemExit(
                    "--transport sharded needs >= 2 pods, but no pod "
                    f"count >= 2 divides both k={args.k} and the "
                    f"{jax.device_count()} visible device(s) — a "
                    "1-pod mesh would silently run zero real "
                    "cross-pod collectives. On a CPU host set "
                    "XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=N (a multiple of k) before jax starts")
            mesh = make_pod_mesh(pods)
            # each pod builds its own band: k stacked replicas would not
            # fit one device at published widths
            state = pod_collectives.init_on_mesh(
                lambda p: streaming.init_state(p, dcfg), params, mesh)
            rec.note(f"sharded transport: "
                     f"{pod_collectives.pods_of(mesh)} "
                     f"pods × {args.k // pod_collectives.pods_of(mesh)} "
                     "replicas/pod")
        else:
            state = streaming.init_state(params, dcfg)
    else:
        state = diloco.init_state(params, dcfg)
        round_wire = diloco.outer_wire_bytes(params, dcfg)
        rec.attach_wire_plan([{"fragment": 0, "send_step": args.H,
                               "apply_step": args.H,
                               "wire_bytes": float(round_wire),
                               "wire_dtype": dcfg.outer_grad_dtype}])
    return state, mesh, plan, frag_wire, round_wire


def run(args, recorder=None):
    """Drive the configured run end-to-end. ``recorder`` overrides the
    run's ``RunRecorder`` (benchmarks pass a silenced one and inspect
    its counters); by default one is built from ``--log-format``.
    Returns the unified record history (``recorder.records``).

    The run marks its host work with profiler spans (``obs/profile.py``
    lists them); the recorder keeps the set-up spans and the compile
    log for ``--out``."""
    rec = recorder if recorder is not None else obs_metrics.RunRecorder(
        transport=args.transport, log_format=args.log_format)
    with rec.setup("build"):
        arch, cfg, dcfg, tcfg, sampler = build(args)
    loss_fn = lambda p, b: arch.loss(p, b)
    key = jax.random.PRNGKey(args.seed)
    key, init_key = jax.random.split(key)
    with rec.setup("init"):
        params, _ = arch.init(init_key, cfg)
    ev = diloco.make_eval(loss_fn)
    with rec.setup("validation"):
        val = sampler.sample_validation(jax.random.PRNGKey(10_000),
                                        args.eval_batch, args.seq)
    rec.manifest.setdefault("config", dict(vars(args)))

    # ---- resilience: durable snapshots + resume picker ----
    mgr = (resilience.CheckpointManager(args.checkpoint_dir,
                                        retain=args.retain)
           if args.checkpoint_dir else None)
    resume_step = None
    if args.resume and mgr is not None and args.transport != "async":
        resume_step = (mgr.latest_good() if args.resume == "auto"
                       else int(args.resume))
        if resume_step is None:
            rec.note("resume: no verified snapshot, starting fresh")
        elif not mgr.verify(resume_step):
            raise SystemExit(f"--resume {resume_step}: snapshot fails "
                             "integrity verification")

    # ---- pretraining phase (paper: 24k steps before DiLoCo) ----
    # A resumed run skips it: the snapshot's state/key already carry
    # the pretrain phase's full effect (params and rng consumption).
    if args.pretrain_steps and resume_step is None:
        step = diloco.make_single_worker_step(loss_fn, tcfg,
                                              total_steps=tcfg.total_steps)
        from repro.optim import adamw, precision
        pol = precision.policy_of(tcfg)
        opt = adamw.init(params, policy=pol)
        # fresh=True: the step donates (work, opt); an identity cast
        # would alias params and the donation would delete them
        work = precision.cast_tree(params, pol.param_dtype, fresh=True)
        for i in range(args.pretrain_steps):
            key, sub = jax.random.split(key)
            batch = {"tokens": sampler.sample_validation(
                sub, args.batch, args.seq)}
            work, opt, m = step(work, opt, batch, jnp.asarray(i))
            if (i + 1) % args.log_every == 0:
                vl = float(ev(work, val))
                rec.pretrain(step=i + 1, loss=float(m["loss"]),
                             val_loss=vl)
        # hand the master-precision params to the DiLoCo phase (the
        # working copy is a rounded view under a mixed policy); the
        # upcast keeps the DiLoCo globals/outer state f32 even under
        # the pure-bf16 policy, where no master exists
        params = precision.cast_tree(adamw.master_params(work, opt),
                                     jnp.float32)

    # ---- DiLoCo phase ----
    if dcfg.transport == "async":
        return _run_async_phase(args, dcfg, tcfg, loss_fn, sampler,
                                params, ev, val, rec)
    with rec.setup("state"):
        state, mesh, plan, frag_wire, round_wire = \
            _init_transport_state(args, dcfg, params, rec)
    del params                # the state holds its own copy
    # ---- resume + (re-)placement ----
    # Snapshots live at HOST placement: the example captured here (only
    # shapes/dtypes, so it holds no device memory) restores a snapshot
    # saved under ANY pod count; shard_stream_state then re-places it
    # onto THIS run's mesh — the elastic-resize path.
    snapshot_example = jax.eval_shape(lambda: resilience.wrap(state, key,
                                                              0))
    rounds_done = 0
    if resume_step is not None:
        state, key, rounds_done = resilience.unwrap(
            mgr.load(resume_step, snapshot_example))
        rec.note(f"resumed snapshot {resume_step}: "
                 f"{rounds_done} round(s) done")
        if mesh is not None:
            state = pod_collectives.shard_stream_state(state, mesh)

    def place_key(kk):
        """The sharded run hands its carry key back replicated on the
        mesh; a key placed anywhere else is another input type, and the
        run's next call would compile it again."""
        if mesh is None:
            return kk
        return jax.device_put(kk, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))

    def load_snapshot(step):
        """Restore snapshot ``step`` and re-place it for this run
        (the guard's rollback path)."""
        st, kk, rd = resilience.unwrap(mgr.load(step, snapshot_example))
        if mesh is not None:
            st = pod_collectives.shard_stream_state(st, mesh)
        return st, place_key(kk), rd

    rng = np.random.default_rng(args.seed)
    drops = schedules.drop_masks(rng, args.drop_prob, args.k, args.rounds)
    sched = schedules.compute_schedule(args.compute_schedule, args.k,
                                       args.rounds)
    acts = schedules.active_masks(sched, args.k)
    scen = scenario_of(args)
    if scen is not None:
        # project the scripted fault scenario onto the barrier-paced
        # run: scenario drops (with retry semantics) replace the legacy
        # i.i.d. masks; preemption spans compose with the compute
        # schedule's active masks
        drops, s_acts = scen.round_masks(args.k, args.rounds)
        acts = np.asarray(acts) * s_acts
        rec.note(f"faults: barrier round = "
                 f"{scen.sync_round_ticks(args.k)} "
                 "tick(s) (slowest worker + slowest link)")
    weights = jnp.asarray(shard_weights(sampler, args.weighted))
    # crash / NaN-bomb injections projected onto the round domain
    nan_masks = None
    if scen is not None and scen.nan_bombs:
        nan_masks = scen.nan_masks(args.k, args.rounds)
        rec.note(f"nan bombs armed: {int(nan_masks.sum())} "
                 "(worker, round) cell(s)")
    crash_round = scen.crash_round(args.k) if scen is not None else -1
    guard = None
    if args.guard:
        guard = resilience.AnomalyGuard(
            resilience.GuardConfig(window=args.guard_window,
                                   spike=args.guard_spike,
                                   max_rollbacks=args.guard_rollbacks),
            recorder=rec)
    gossip_rounds = []

    def emit_round(t, m, i=None, evaled=True, round_key=None):
        """Emit the round-t record from metrics dict ``m`` (scalar
        entries for the legacy loop, (R,) stacked entries at index
        ``i`` for the scanned driver) through the recorder. ``evaled``
        False marks a round skipped by the eval cadence — a NaN on an
        *evaled* round is a genuine divergence and is reported as
        such. ``round_key`` (the round's split-chain sub-key) lets the
        gossip transport record the realized pairing edges."""
        pick = (lambda x: float(x)) if i is None else \
            (lambda x: float(x[i]))
        # optional transport metrics recorded under their own names —
        # the unified schema keeps them flat, one key space for all
        extras = {kk: pick(m[kk]) for kk in
                  ("inner_loss_last", "drop_frac", "gossip_spread",
                   "gossip_frag", "exchange_frac",
                   "stream_peak_sync_bytes", "stream_round_sync_bytes")
                  if kk in m}
        if args.cosine_stats:
            extras["cos_mean"] = pick(m["cos_mean"])
            extras["cos_std"] = pick(m["cos_std"])
        edges = None
        wire = round_wire
        if frag_wire is not None:       # gossip: the round's fragment
            P = len(frag_wire)
            wire = frag_wire[t % P]
            from repro.core import gossip
            edges = gossip.pairing_edges(args.k, t,
                                         args.gossip_pairing,
                                         round_key=round_key)
            gossip_rounds.append({"round": t, "fragment": t % P,
                                  "edges": [list(e) for e in edges]})
        rec.round(
            round=t + 1, rounds=args.rounds,
            inner_steps=args.pretrain_steps + (t + 1) * args.H,
            inner_loss=pick(m["inner_loss"]),
            val_loss=pick(m["val_loss"]),
            outer_gnorm=pick(m["outer_gnorm"]),
            # count from the final mask row, not the schedule: a
            # scenario preemption zeroes workers the schedule keeps
            active=int(np.asarray(acts[t]).sum()),
            dropped=int(args.k - np.asarray(drops[t]).sum()),
            wire_bytes=wire, gossip_edges=edges, extras=extras,
            evaled=evaled)

    t0 = time.time()
    if args.legacy_loop:
        # One jit dispatch + one blocking host eval per round — kept for
        # comparison (see benchmarks/wallclock.py).
        rnd = diloco.make_round(loss_fn, sampler.sample_all_shards, dcfg,
                                tcfg, total_steps=tcfg.total_steps,
                                compute_cosine=args.cosine_stats,
                                batch_size=args.batch, seq_len=args.seq,
                                mesh=mesh)
        for t in range(args.rounds):
            key, sub = jax.random.split(key)
            state, m = rnd(state, sub, jnp.asarray(drops[t]),
                           jnp.asarray(acts[t]), weights)
            m = dict(m, val_loss=ev(state.global_params, val))
            emit_round(t, m, round_key=sub)
    else:
        # Scanned driver: chunks of `rounds_per_call` rounds run inside
        # one jit each (donated carry, in-graph eval every round); the
        # host only touches metrics at chunk boundaries. All the
        # resilience hooks (snapshots, crash, guard) live at those same
        # boundaries — they add zero host syncs per chunk.
        rpc = max(1, min(args.rounds_per_call or args.rounds,
                         args.rounds))
        ckpt_every = args.checkpoint_every if mgr is not None else 0
        runs = {}
        guarded = False       # flips after a guard rollback: the
        #                       replay escalates to the in-graph guard

        def get_run(n):
            kk = (n, guarded)
            if kk not in runs:
                d = (dataclasses.replace(dcfg, guard_outer=True)
                     if guarded else dcfg)
                runs[kk] = diloco.make_run(
                    loss_fn, sampler.sample_all_shards, d, tcfg,
                    rounds_per_call=n, total_steps=tcfg.total_steps,
                    compute_cosine=args.cosine_stats,
                    batch_size=args.batch, seq_len=args.seq,
                    eval_tokens=val, eval_every=args.eval_every,
                    mesh=mesh, nan_bombs=nan_masks)
            return runs[kk]

        t = rounds_done
        key = place_key(key)
        while t < args.rounds:
            n = min(rpc, args.rounds - t)
            if ckpt_every:
                # land chunk boundaries on the snapshot cadence
                n = min(n, ckpt_every - t % ckpt_every)
            if 0 <= crash_round and t <= crash_round:
                # ... and on the scripted kill point
                n = min(n, crash_round + 1 - t)
            subs = None
            if frag_wire is not None:
                # host replica of the in-graph split_chain: the round
                # keys the body consumed, for the pairing-edge record
                subs, kk = [], key
                for _ in range(n):
                    kk, sub = jax.random.split(kk)
                    subs.append(sub)
            # round_offset keeps the in-graph eval cadence globally
            # aligned across chunk boundaries (traced: chunks of equal
            # size share one compiled function)
            with jax.profiler.TraceAnnotation("diloco.dispatch"):
                state, ms = get_run(n)(state, key,
                                       jnp.asarray(drops[t:t + n]),
                                       jnp.asarray(acts[t:t + n]),
                                       weights, round_offset=t)
            key = ms.pop("next_key")
            ms = rec.ingest_chunk(ms)
            with jax.profiler.TraceAnnotation("diloco.emit"):
                for i in range(n):
                    evaled = ((t + i + 1) % args.eval_every == 0
                              or i == n - 1)
                    emit_round(t + i, ms, i, evaled=evaled,
                               round_key=None if subs is None else subs[i])
            t += n
            # (1) scripted kill: BEFORE this boundary's snapshot, so
            # the resume has to replay the crashed round from the last
            # durable state
            if 0 <= crash_round < t:
                rec.note(f"crash: SIGKILL at round boundary {t}")
                os.kill(os.getpid(), signal.SIGKILL)
            # (2) anomaly guard: judge the chunk from metrics already
            # materialized; on anomaly, roll back to the last good
            # snapshot and replay with the in-graph guard armed
            if guard is not None:
                with jax.profiler.TraceAnnotation("diloco.guard"):
                    losses = [float(ms["val_loss"][i])
                              if ((t - n + i + 1) % args.eval_every == 0
                                  or i == n - 1)
                              else float(ms["inner_loss"][i])
                              for i in range(n)]
                    bad = guard.observe_chunk(t - n, losses)
                    if bad and mgr is not None and guard.can_rollback():
                        back = mgr.latest_good()
                        if back is not None and back < t:
                            state, key, t = load_snapshot(back)
                            guard.rolled_back(to_round=back,
                                              skip_round=bad[0]["round"])
                            guarded = True
                            continue
            # (3) durable snapshot at the cadence (host placement is
            # restored by the example on load, so a snapshot taken on
            # a pods=p mesh resumes under pods=p')
            if ckpt_every and (t % ckpt_every == 0 or t == args.rounds):
                with jax.profiler.TraceAnnotation("diloco.snapshot"):
                    mgr.save(t, resilience.wrap(state, key, t),
                             metadata={"transport": args.transport,
                                       "k": args.k, "H": args.H,
                                       "rounds_done": t})

    elapsed = time.time() - t0
    floor = sampler.entropy_floor()
    rec.note(f"done in {elapsed:.1f}s; "
             f"entropy floor = {floor:.4f} (ppl {np.exp(floor):.2f})",
             entropy_floor=floor)
    if args.trace:
        overlap = None
        if mesh is not None and plan \
                and any(row.get("deferred") for row in plan):
            # deferred sharded transport: overlay the MEASURED
            # issue→consume offsets on the fragment lanes. A dedicated
            # rounds_per_call=1 lowering (no compile — stream_overlap
            # reads the pre-optimization text) keeps the per-round
            # offsets exact regardless of the chunking above.
            from repro.launch import hlo_analysis as _hlo
            run1 = diloco.make_run(
                loss_fn, sampler.sample_all_shards, dcfg, tcfg,
                rounds_per_call=1, total_steps=tcfg.total_steps,
                batch_size=args.batch, seq_len=args.seq,
                donate=False, mesh=mesh)
            overlap = _hlo.stream_overlap(
                run1.lower(state, key).compiler_ir("hlo")
                .as_hlo_text(),
                chips_per_pod=(jax.device_count()
                               // pod_collectives.pods_of(mesh)),
                tau=dcfg.stream_tau)
            rec.note(
                f"overlap (HLO-measured): {overlap['n_deferred']} "
                f"deferred wires, min {overlap['min_steps_between']} "
                f"steps / {overlap['min_dots_between']} dots "
                f"issue->consume (tau={dcfg.stream_tau})")
        tb = obs_trace.round_trace(
            transport=args.transport, k=args.k, rounds=args.rounds,
            H=args.H, scenario=scen, drops=np.asarray(drops),
            acts=np.asarray(acts), history=rec.round_records(),
            plan=plan, wire_bytes=round_wire,
            gossip_rounds=gossip_rounds, overlap=overlap)
        tb.write(args.trace, other_data={"manifest": rec.manifest})
        rec.note(f"trace: {args.trace}")
    if args.out:
        rec.dump(args.out, args=vars(args))
        rec.note(f"wrote {args.out}")
    if args.checkpoint:
        ckpt.save(args.checkpoint,
                  {"params": state.global_params,
                   "outer_buf": state.outer_state.buf},
                  metadata={"rounds": args.rounds, "k": args.k,
                            "H": args.H})
        rec.note(f"checkpoint: {args.checkpoint}")
    if args.state_hash_out:
        rrecs = rec.round_records()
        vals = [r["val_loss"] for r in rrecs
                if r.get("val_loss") is not None]
        ckpt.atomic_write_json(args.state_hash_out, {
            "state_sha256": resilience.tree_sha256(state),
            "leaf_sha256": resilience.leaf_hashes(state),
            "final_val_loss": vals[-1] if vals else None,
            "final_inner_loss": (rrecs[-1]["inner_loss"]
                                 if rrecs else None),
            "resumed_from_step": (-1 if resume_step is None
                                  else int(resume_step)),
            "rounds_done": args.rounds,
            "ingest_calls": rec.ingest_calls,
            "rollbacks": 0 if guard is None else guard.rollbacks_used},
            indent=2)
        rec.note(f"state hash: {args.state_hash_out}")
    return rec.records


def make_parser():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="diloco_150m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--H", type=int, default=50)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--pretrain-steps", type=int, default=0)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eval-batch", type=int, default=64)
    ap.add_argument("--inner-lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--outer-opt", default="nesterov",
                    choices=["nesterov", "sgd", "sgdm", "adam"])
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--outer-momentum", type=float, default=0.9)
    ap.add_argument("--regime", default="non_iid",
                    choices=["iid", "non_iid"])
    ap.add_argument("--drop-prob", type=float, default=0.0)
    ap.add_argument("--prune-frac", type=float, default=0.0)
    ap.add_argument("--weighted", action="store_true")
    ap.add_argument("--compute-schedule", default="constant_distributed",
                    choices=["constant_local", "constant_distributed",
                             "doubling", "halving", "ramp_up", "ramp_down"])
    ap.add_argument("--cosine-stats", action="store_true")
    ap.add_argument("--kernel-mode", default="ref",
                    choices=["auto", "pallas", "interpret", "ref"],
                    help="Pallas kernels of attention and the fused "
                         "optimizers: auto=Pallas on TPU, ref=legacy jnp "
                         "(bit-identical)")
    ap.add_argument("--rounds-per-call", type=int, default=0,
                    help="rounds scanned inside one jit "
                         "(0 = all rounds in a single call)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="in-graph eval cadence in rounds (scanned "
                         "driver; globally aligned across chunks)")
    ap.add_argument("--stream-fragments", type=int, default=0,
                    help="streaming outer sync: number of parameter "
                         "fragments P (0 = classic synchronous outer "
                         "step; see core/streaming.py)")
    ap.add_argument("--stream-alpha", type=float, default=1.0,
                    help="streaming merge weight "
                         "θ_i <- α·θ_global + (1-α)·θ_i")
    ap.add_argument("--stream-tau", type=int, default=0,
                    help="inner steps between a fragment's snapshot "
                         "and its application (simulated in-flight "
                         "collective)")
    ap.add_argument("--outer-grad-dtype", default="float32",
                    choices=["float32", "bfloat16", "int4"],
                    help="transport precision of outer gradients on "
                         "the simulated wire")
    ap.add_argument("--error-feedback", action="store_true",
                    help="streaming: keep each replica's transport "
                         "quantization residual and add it to the next "
                         "round's delta (kills the int4/bf16 rounding "
                         "bias at no wire cost)")
    ap.add_argument("--transport", default="simulated",
                    choices=["simulated", "sharded", "async", "gossip"],
                    help="outer-sync backend: 'sharded' runs each "
                         "replica on its own pod mesh slice and "
                         "reduces every fragment with a real pod-axis "
                         "collective (needs >= --pods devices; on CPU "
                         "set --xla_force_host_platform_device_count); "
                         "'async' is the barrier-free event loop "
                         "(core/async_diloco.py) driven by the fault "
                         "flags below; 'gossip' is NoLoCo-style "
                         "pairwise partial averaging with no global "
                         "collective (core/gossip.py)")
    ap.add_argument("--staleness-lambda", type=float, default=1.0,
                    help="async transport: an outer gradient tau outer "
                         "steps stale is applied at weight lambda^tau/k")
    ap.add_argument("--gossip-pairing", default="butterfly",
                    choices=["butterfly", "random"],
                    help="gossip partner schedule: butterfly (hypercube "
                         "dims, k a power of 2, provably exact mixing "
                         "in log2 k rounds) or a fresh random perfect "
                         "matching per round")
    ap.add_argument("--gossip-mix", type=float, default=0.5,
                    help="gossip adoption rate: g_i <- g_i + "
                         "mix*(g_partner - g_i) on the scheduled "
                         "fragment")
    ap.add_argument("--ticks", type=int, default=0,
                    help="async horizon in wall-clock ticks (1 tick = "
                         "fastest worker's phase; 0 = the ticks a "
                         "barrier-paced run of --rounds would take "
                         "under the same scenario)")
    ap.add_argument("--speeds", default="",
                    help="fault scenario: comma per-worker phase "
                         "duration in ticks (single value broadcasts; "
                         "e.g. 1,1,1,4 = one 4x straggler)")
    ap.add_argument("--link-latency", default="",
                    help="fault scenario: comma per-worker one-way "
                         "link latency in ticks added to every send")
    ap.add_argument("--latency-jitter", type=float, default=0.0,
                    help="fault scenario: lognormal sigma multiplying "
                         "each send's latency draw")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="fault scenario: resends after a dropped "
                         "attempt; a payload whose every attempt drops "
                         "is permanently lost")
    ap.add_argument("--retry-backoff", type=int, default=1,
                    help="fault scenario: ticks between a dropped "
                         "attempt and its resend")
    ap.add_argument("--preempt", action="append", default=[],
                    metavar="W:LEAVE[:REJOIN]",
                    help="fault scenario: worker W leaves at tick "
                         "LEAVE and rejoins at REJOIN (omit/0 = gone "
                         "for good); repeatable")
    ap.add_argument("--restore", default="",
                    help="async transport: resume from a full-state "
                         "checkpoint written by --checkpoint (replays "
                         "the identical event suffix)")
    ap.add_argument("--no-pack-wire", dest="pack_wire",
                    action="store_false", default=True,
                    help="sharded quantized transport: gather the "
                         "legacy dequantized-f32 payload per leaf "
                         "instead of the packed int4 codes+scales / "
                         "bf16 wire buffer (default: packed — the "
                         "collective ships what the accounting charges)")
    ap.add_argument("--pods", type=int, default=0,
                    help="pod count of the sharded-transport mesh "
                         "(0 = min(k, device count); must divide k)")
    ap.add_argument("--param-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="storage dtype of the per-replica working "
                         "params + AdamW moments (bfloat16 halves the "
                         "donated params+moments carry)")
    ap.add_argument("--master-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="storage dtype of the master-side state; when "
                         "wider than --param-dtype each replica carries "
                         "a master copy in its AdamW state and outer "
                         "deltas are computed master-vs-master")
    ap.add_argument("--legacy-loop", action="store_true",
                    help="use the per-round Python loop instead of the "
                         "scanned driver")
    ap.add_argument("--log-every", type=int, default=200)
    ap.add_argument("--log-format", default="text",
                    choices=["text", "json"],
                    help="progress-line format: 'text' keeps the "
                         "classic console lines, 'json' prints one "
                         "JSON record per line (same unified schema "
                         "as --out)")
    ap.add_argument("--trace", default="",
                    help="write a tick-domain Chrome trace-event JSON "
                         "of the run (workers, fragments, transfers, "
                         "faults) — open in Perfetto / "
                         "chrome://tracing")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--checkpoint", default="")
    # ---- resilience (src/repro/resilience/) ----
    ap.add_argument("--checkpoint-dir", default="",
                    help="durable snapshot directory (atomic npz + "
                         "sha256 manifest per snapshot, retention, "
                         "resume picker) — all five transports")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot cadence: every N rounds (round "
                         "transports) / every N events (async); "
                         "0 = only what --checkpoint writes")
    ap.add_argument("--resume", default="",
                    help="'auto' resumes from the newest snapshot in "
                         "--checkpoint-dir that passes integrity "
                         "verification (falling back past corrupt "
                         "ones); a number resumes that exact step")
    ap.add_argument("--retain", type=int, default=3,
                    help="snapshots kept in --checkpoint-dir (oldest "
                         "deleted first)")
    ap.add_argument("--crash-at-round", type=int, default=-1,
                    help="fault injection: SIGKILL this process at the "
                         "chunk boundary right after the given round "
                         "completes, BEFORE that boundary's snapshot "
                         "(round transports)")
    ap.add_argument("--crash-at-tick", type=int, default=-1,
                    help="fault injection: splice a Crash event into "
                         "the async timeline at this tick (the engine "
                         "SIGKILLs the process when it reaches it)")
    ap.add_argument("--nan-bomb", action="append", default=[],
                    metavar="W:ROUND",
                    help="fault injection: poison worker W's outer "
                         "gradient to NaN in the given round "
                         "(repeatable; classic simulated transport)")
    ap.add_argument("--guard", action="store_true",
                    help="host-side anomaly guard: rolling loss spike "
                         "detection at chunk boundaries, with "
                         "rollback-to-last-snapshot + in-graph-guard "
                         "escalation when --checkpoint-dir is set")
    ap.add_argument("--guard-window", type=int, default=8,
                    help="guard rolling-statistics window (rounds)")
    ap.add_argument("--guard-spike", type=float, default=4.0,
                    help="guard spike threshold in rolling std devs")
    ap.add_argument("--guard-rollbacks", type=int, default=2,
                    help="guard escalation budget: rollbacks allowed "
                         "per run")
    ap.add_argument("--guard-outer", action="store_true",
                    help="in-graph guard: exclude replicas with "
                         "non-finite outer deltas from the outer "
                         "reduce (bit-identical on clean rounds)")
    ap.add_argument("--guard-clip", type=float, default=0.0,
                    help="with --guard-outer: clip each replica's "
                         "outer-delta norm to this multiple of the "
                         "median replica norm (0 = off)")
    ap.add_argument("--state-hash-out", default="",
                    help="write a JSON with the final state's sha256, "
                         "final losses and resume provenance — the "
                         "bit-identity gate the resilience benchmarks "
                         "compare across processes")
    return ap


if __name__ == "__main__":
    use_compile_cache()
    run(make_parser().parse_args())
