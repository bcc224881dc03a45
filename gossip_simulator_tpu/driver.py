"""Two-phase driver: overlay construction, then epidemic broadcast.

Mirrors the reference `main()` (simulator.go:207-255) with the same observable
output surface (§0 of SURVEY.md), plus a max-rounds liveness bound the
reference lacks (it spins forever if 99% is unreachable, simulator.go:243-251)
and optional profiling/checkpointing.
"""

from __future__ import annotations

import dataclasses
import os
import time
import uuid
from typing import Optional

from gossip_simulator_tpu import tuning as _tuning
from gossip_simulator_tpu.backends import make_stepper
from gossip_simulator_tpu.backends.base import Stepper, WINDOW_MS
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.utils import lifecycle as _lifecycle
from gossip_simulator_tpu.utils import telemetry as _telemetry
from gossip_simulator_tpu.utils import trace as _trace
from gossip_simulator_tpu.utils.metrics import ProgressPrinter, Stats


@dataclasses.dataclass
class RunResult:
    stats: Stats
    stabilize_ms: float  # simulated ms for overlay construction
    coverage_ms: float  # simulated ms to reach the coverage target
    converged: bool
    overlay_windows: int
    gossip_windows: int
    # Host-loss supervision (ISSUE 20): windows replayed after restoring
    # from the last snapshot, and wall-clock paused during recovery.
    # Zero / 0.0 unless -supervise recovered from a loss.
    recovered_windows: int = 0
    recovery_pause_ms: float = 0.0


def run_simulation(cfg: Config, printer: Optional[ProgressPrinter] = None,
                   stepper: Optional[Stepper] = None,
                   silent: bool = False) -> RunResult:
    """`silent` mutes ALL output (and skips the JSONL log) -- the non-zero
    ranks of a -distributed run, which compute the same replicated totals
    as rank 0."""
    cfg = cfg.validate()
    own_printer = printer is None
    printer = printer or ProgressPrinter(
        enabled=cfg.progress,
        jsonl_path=(cfg.log_jsonl_resolved or None) if not silent else None,
        silent=silent)
    # Flight recorder (utils/trace.py): one tracer per run, activated for
    # the module-level span() sites in backends/checkpoint.  Host-side
    # only -- the traced jitted programs are unchanged -- and skipped on
    # non-primary ranks (they would race on the same file).
    tracer = None
    if (cfg.trace_resolved or cfg.xprof_dir) and not silent:
        tracer = _trace.Tracer(path=cfg.trace_resolved,
                               xprof_dir=cfg.xprof_dir)
    try:
        # Ambient tuning config: cfg-less tunable lookups deeper in the
        # stack (exchange pad/rank path, pallas block rows) resolve this
        # run's tuning table instead of falling back to registry defaults.
        with _trace.activated(tracer), _tuning.ambient(cfg):
            return _run(cfg, printer, stepper)
    finally:
        # Close on ANY exit so a raised run still flushes the JSONL log
        # (and the trace file persists what a crashed run got through).
        if tracer is not None and tracer.path:
            tracer.write(metadata={"n": cfg.n, "backend": cfg.backend,
                                   "seed": cfg.seed})
        if own_printer:
            printer.close()


def _run(cfg: Config, printer: ProgressPrinter,
         stepper: Optional[Stepper]) -> RunResult:
    stepper = stepper or make_stepper(cfg)

    printer.params(cfg.parameter_dump())
    scen = cfg.scenario_resolved
    if scen.active or cfg.overlay_heal_resolved:
        # One-line fault-model banner (progress-only, like every note:
        # quiet runs and non-primary ranks skip it) so a scenario run's
        # transcript is self-describing.
        printer.note(
            f"scenario: {len(scen.crashes)} crash / {len(scen.churns)} "
            f"churn / {len(scen.partitions)} partition events, "
            f"groups={scen.groups}, downtime={scen.downtime}ms; "
            f"overlay-heal {cfg.overlay_heal}"
            + (f" (detect {cfg.heal_detect_ms}ms)"
               if cfg.overlay_heal_resolved else ""))
    for entry in _tuning.entries_for(cfg):
        if any(v != _tuning.REGISTRY[k].default
               for k, v in entry.get("values", {}).items()
               if k in _tuning.REGISTRY):
            # Same self-describing-transcript rationale as the scenario
            # banner: a run whose constants were MOVED by a table entry
            # says which one.  An all-defaults entry stays silent -- it
            # produces the identical program, and the golden transcripts
            # pin that.
            printer.note(f"tuning: table entry {entry['id']} active "
                         f"(table {cfg.tuning_table})")
    if cfg.backend == "sharded":
        # Same self-describing-transcript rationale: "auto" resolves per
        # host (device count), so the transcript records which schedule
        # this run's exchange actually compiled (CI greps this line to
        # confirm both gates were exercised).
        printer.note(f"exchange-pipeline: {cfg.exchange_pipeline_resolved} "
                     f"(requested {cfg.exchange_pipeline})")
    t_init = time.perf_counter()
    with _trace.span("init", cat="phase"):
        stepper.init()
    # The telemetry session (utils/telemetry.py) lets an observing run --
    # progress lines or JSONL -- take the device-side fast paths anyway:
    # the jitted loops record the full per-window trajectory on device and
    # the driver replays it through the SAME printer calls afterward,
    # producing output byte-identical to the windowed loop's.
    telem = getattr(stepper, "_telem", None)
    if telem is not None:
        telem.add_phase("init_s", time.perf_counter() - t_init)

    # Checkpoint provenance (ISSUE 20 satellite 2): the explicit -run-id,
    # else a generated token under supervision / worker mode.  Stamped into
    # every snapshot sidecar this run writes; empty for plain runs so their
    # sidecars stay byte-identical to pre-provenance builds.
    run_id = cfg.run_id
    if not run_id and (cfg.supervise or cfg.heartbeat_dir):
        run_id = uuid.uuid4().hex[:12]
    # Liveness beacon (distributed/heartbeat.py): a supervised worker
    # stamps its rank's beacon once per poll window in BOTH phases, so the
    # supervisor's staleness monitor sees progress, not just existence.
    beacon = None
    if cfg.heartbeat_dir and not cfg.supervise:
        from gossip_simulator_tpu.distributed import heartbeat as _heartbeat

        beacon = _heartbeat.Beacon.for_cfg(cfg)

    # --- Resume: from a phase-2 snapshot (skip straight into phase 2) or a
    # phase-1 overlay snapshot (continue construction mid-overlay) -------------
    resumed = False
    resume_window = 0
    overlay_windows = 0
    if cfg.resume:
        from gossip_simulator_tpu.utils import checkpoint

        # Under -distributed every rank reads the same snapshot (only rank 0
        # writes them), so the checkpoint dir must be on a filesystem all
        # hosts share -- the standard arrangement for multi-host training.
        # latest() prefers state_* (phase 2) over overlay_* (phase 1), so a
        # run interrupted in either phase resumes from its furthest point.
        path = checkpoint.latest(cfg.checkpoint_dir)
        if path is None:
            raise FileNotFoundError(
                f"-resume: no snapshot found in {cfg.checkpoint_dir}"
                + (" (every process of a -distributed run must see the "
                   "checkpoint dir; put it on a shared filesystem)"
                   if cfg.distributed else ""))
        tree, meta = checkpoint.load(path)
        # Provenance gate on an explicit -run-id: a relaunched survivor
        # (same -run-id as the original incarnation) passes; a snapshot
        # from some OTHER run is refused by name.  Staleness is the
        # supervisor's call (it knows the loss window), not resume's.
        if run_id:
            checkpoint.verify_provenance(meta, path=path, run_id=run_id,
                                         now_window=0, max_stale=0)
        # Phase detection falls back to tree contents (win_makeups exists
        # only on overlay state) so a snapshot whose .json sidecar was
        # lost in a copy still routes to the right restore path.
        phase1 = (int(meta["phase"]) == 1 if "phase" in meta
                  else "win_makeups" in tree)
        if phase1:
            overlay_windows = int(meta.get("window", 0))
            stepper.load_overlay_state_pytree(tree, windows=overlay_windows)
            printer.section(f"Resumed from {os.path.basename(path)} "
                            f"(overlay window {overlay_windows})")
            # resumed stays False: phase 1 continues below, then phase 2
            # runs normally (seed included).
        else:
            stepper.load_state_pytree(tree)
            resume_window = int(meta.get("window", 0))
            printer.section(f"Resumed from {os.path.basename(path)} "
                            f"(window {resume_window})")
            resumed = True

    # --- Phase 1: overlay (simulator.go:219-235) ------------------------------
    # Cooperative-shutdown bookkeeping (utils/lifecycle): a signalled run
    # finishes its current window, saves a final checkpoint and flushes
    # artifacts with reason "interrupted".  `p1_interrupted` marks a run
    # that never reached phase 2 (no epidemic state to report or seed).
    p1_interrupted = False
    if not resumed:
        printer.section("Constructing Overlay")
        if (cfg.graph == "overlay" and cfg.overlay_mode == "auto"
                and cfg.backend in ("jax", "sharded")
                and cfg.effective_time_mode == "ticks"
                and cfg.overlay_mode_resolved == "rounds"):
            # The size-banded default (config.OVERLAY_TICKS_AUTO_MAX,
            # raised to 10M in round 7) uses the estimated clock above
            # the band; say so once.  Gated on tick semantics: when
            # -time-mode rounds forced the rounds overlay, recommending
            # -overlay-mode ticks would point at a config validate()
            # rejects.
            printer.note("overlay clock estimated as rounds x mean delay "
                         "at this n; -overlay-mode ticks gives per-message-"
                         "faithful timing at ~2x the cost")
        max_overlay_windows = max(cfg.max_rounds, 1000)
        ckpt1 = _Checkpointer(cfg, stepper, run_id=run_id)
        # Same observability gate as the phase-2 fast path below: a quiet
        # run has no per-window output, so stabilization can run as bounded
        # device-side while_loops (one host sync per bounded call
        # -- overlay_ticks/overlay.run_call_budget windows -- instead of
        # one dispatch + device_get per 10 simulated ms).  With telemetry,
        # an OBSERVING run takes the same fast path: the loop records the
        # per-window membership counts on device and they replay below.
        # Checkpointing observes per-window state the history cannot carry,
        # so it keeps the windowed loop (same rule as phase 2's gate).
        if ((not printer.observing or telem is not None)
                and not cfg.checkpointing_enabled
                and hasattr(stepper, "overlay_run_to_quiescence")):
            with _trace.span("phase1.quiesce", cat="phase") as sp:
                overlay_windows, oq = stepper.overlay_run_to_quiescence(
                    max_overlay_windows)
                if sp is not None:
                    sp["windows"] = int(overlay_windows)
            if not oq:
                raise RuntimeError(
                    f"overlay did not stabilize within {max_overlay_windows} "
                    f"windows")
            # Static graphs quiesce without running a window; the windowed
            # loop still counts its one (immediately-quiesced) poll, so
            # match it -- RunResult.overlay_windows is path-independent.
            overlay_windows = max(overlay_windows, 1)
            if telem is not None and printer.observing:
                _telemetry.replay_overlay(
                    printer, telem.overlay_snapshot(),
                    clock_scale=getattr(stepper, "overlay_clock_scale", 1.0))
        else:
            while True:
                with _trace.span("phase1.window", cat="window") as sp:
                    makeups, breakups, quiesced = stepper.overlay_window()
                    if sp is not None:
                        sp.update(makeups=int(makeups),
                                  breakups=int(breakups))
                overlay_windows += 1
                if quiesced:
                    break
                # Reference prints the window line only when *not* quiescing
                # (simulator.go:227-230).
                printer.overlay_window(breakups, makeups,
                                       stepper.sim_time_ms())
                if beacon is not None:
                    beacon.stamp(overlay_windows)
                ckpt1.maybe_save_overlay(overlay_windows)
                if _lifecycle.shutdown_requested():
                    p1_interrupted = True
                    break
                if overlay_windows >= max_overlay_windows:
                    raise RuntimeError(
                        f"overlay did not stabilize within "
                        f"{max_overlay_windows} windows")
    stabilize_ms = 0.0 if resumed else stepper.sim_time_ms()
    if not resumed and not p1_interrupted:
        printer.stabilized(stabilize_ms)

    # --- Phase 2: broadcast (simulator.go:237-253) ----------------------------
    if not p1_interrupted:
        printer.section("Broadcast one message")
        if not resumed:
            # On a fresh stepper the seed program's first call is its
            # trace, lowering and compile or cache load.
            with _trace.span("seed", cat="device"):
                stepper.seed()
    target = cfg.coverage_target
    window_rounds = WINDOW_MS if cfg.effective_time_mode == "ticks" else 1
    # max_rounds caps simulated time at WINDOW granularity (both this loop
    # and the engines' run_to_coverage while_loops advance whole windows
    # between bound checks, so either path may overshoot the cap by up to
    # window_rounds-1 ticks -- consistently).  A resumed run gets only the
    # ceil of its remainder; a snapshot already at the cap runs zero windows.
    elapsed = int(stepper.sim_time_ms()) if resumed else 0
    max_windows = max(0, -(-(cfg.max_rounds - elapsed) // window_rounds))
    gossip_windows = 0
    converged = False
    ckpt = _Checkpointer(cfg, stepper, run_id=run_id)
    # Nothing on a quiet, uncheckpointed, unlogged run observes per-window
    # state, so the whole epidemic runs as bounded device-side while_loops
    # with a handful of host syncs total -- the windowed loop below pays a
    # full device->host stats round-trip per 10 simulated ms (~2x wall-clock
    # at n=1e7).  With telemetry, an OBSERVING run
    # (progress lines or JSONL) takes the fast path too: the device loop
    # records every poll window's counters and the trajectory replays
    # through the same printer calls right after -- byte-identical output,
    # fast-path wall clock.  Checkpointing still needs the real per-window
    # state, so it keeps the windowed loop.
    fast = (not resumed and not cfg.checkpointing_enabled
            and (not printer.observing or telem is not None)
            and hasattr(stepper, "run_to_target"))
    # Per-window trajectory rows for the run artifact (`-run-dir`): the
    # fast path derives them from the telemetry history afterward; the
    # windowed loop collects them here (artifact.TRAJECTORY_COLS order --
    # Stats.round IS the recorded tick, so the two bases are identical).
    window_rows: list = []
    collect_rows = bool(cfg.run_dir) and not printer.silent
    # Serve mode rebuilds the stepper across reshards, so the live config
    # (admission deferrals mutate the injection schedule) and the stepper
    # the final stats come from both ride the ServeOutcome.
    live_cfg = cfg
    serve_report = None
    hostloss_report = None
    interrupted = p1_interrupted
    if p1_interrupted:
        pass
    elif cfg.supervise:
        from gossip_simulator_tpu.distributed import supervisor as _sup

        outcome = _sup.run_supervised(cfg, stepper, printer,
                                      max_windows,
                                      collect_rows=collect_rows,
                                      run_id=run_id)
        stepper = outcome.stepper
        gossip_windows = outcome.windows
        converged = outcome.converged
        window_rows = outcome.rows
        hostloss_report = outcome.report
        interrupted = interrupted or outcome.interrupted
        # A recovery rebuilds the stepper; device-recorded telemetry
        # histories do not survive that (same rule as serve's
        # reshards), so the artifact trajectory uses the
        # host-collected rows.
        telem = None
    elif cfg.serve:
        from gossip_simulator_tpu import serve as _serve

        outcome = _serve.run_serve(cfg, stepper, printer, max_windows,
                                   resume_window=resume_window,
                                   collect_rows=collect_rows)
        stepper = outcome.stepper
        live_cfg = outcome.cfg
        gossip_windows = outcome.windows
        converged = outcome.converged
        window_rows = outcome.rows
        serve_report = outcome.report
        interrupted = interrupted or outcome.interrupted
        # Device-recorded telemetry histories do not survive a reshard
        # (each incarnation starts its own); the artifact trajectory
        # uses the host-collected rows instead (basis "windows" --
        # row-identical to a twin's telemetry basis).
        telem = None
    elif fast:
        with _trace.span("phase2.run_to_target", cat="phase") as sp:
            stats = stepper.run_to_target()
            if sp is not None:
                sp.update(rounds=int(stats.round),
                          messages=int(stats.total_message),
                          received=int(stats.total_received))
        hist2 = telem.gossip_snapshot() if telem is not None else None
        if hist2 and printer.observing:
            _telemetry.replay_gossip(printer, hist2, n=cfg.n)
        gossip_windows = (hist2["count"]
                          if hist2 and not hist2["truncated"]
                          else -(-stats.round // window_rounds))
        converged = stats.coverage >= target
        if _lifecycle.shutdown_requested():
            interrupted = True
    else:
        while gossip_windows < max_windows:
            with _trace.span("phase2.window", cat="window") as sp:
                stats = stepper.gossip_window()
                if sp is not None:
                    sp.update(round=int(stats.round),
                              received=int(stats.total_received),
                              messages=int(stats.total_message),
                              dropped=int(stats.mailbox_dropped))
            gossip_windows += 1
            if collect_rows:
                window_rows.append((stats.round, stats.total_received,
                                    stats.total_message,
                                    stats.total_crashed,
                                    stats.total_removed))
            pct = stats.coverage * 100.0
            printer.coverage_window(round(pct, 4), stepper.sim_time_ms())
            if beacon is not None:
                beacon.stamp(resume_window + gossip_windows)
            # Offset by the restored window so post-resume snapshot
            # numbers continue the sequence (checkpoint.latest is
            # lexicographic).
            ckpt.maybe_save(resume_window + gossip_windows, stats)
            if stats.coverage >= target:
                converged = True
                break
            if getattr(stepper, "exhausted", False):
                break  # no messages in flight and nothing can change
            if _lifecycle.shutdown_requested():
                interrupted = True
                break
    # A run interrupted mid-overlay has no epidemic state to read back.
    coverage_ms = 0.0 if p1_interrupted else stepper.sim_time_ms()
    if p1_interrupted:
        stats = Stats(n=cfg.n)
    else:
        with _trace.span("stats", cat="device"):
            stats = stepper.stats()
    if serve_report is not None:
        stats.shed = serve_report["shed"]
    # A snapshot restored at/after the cap may already be at target.
    converged = converged or stats.coverage >= target
    # The true cause rides Stats now (threaded by every backend), so both
    # paths -- and the replayed fast path -- report "exhausted" whenever
    # the wave died, even in the window the round cap was hit.  A signalled
    # run reports "interrupted" whatever else was true -- the exit is the
    # signal's doing, and the final checkpoint below makes it resumable.
    if interrupted:
        reason = "interrupted"
    else:
        reason = ("exhausted: no messages in flight"
                  if stats.exhausted else "max rounds")
    if interrupted and cfg.checkpoint_dir:
        _final_shutdown_checkpoint(cfg, stepper, stats, p1_interrupted,
                                   resume_window + gossip_windows,
                                   overlay_windows)
    printer.done(coverage_ms, stats, target_pct=target * 100.0,
                 converged=converged, reason=reason)
    result = RunResult(
        stats=stats,
        stabilize_ms=stabilize_ms,
        coverage_ms=coverage_ms,
        converged=converged,
        overlay_windows=overlay_windows,
        gossip_windows=gossip_windows,
    )
    # Terminal machine-consumable record: full RunResult + wall breakdown
    # (JSONL-only; consumers stop scraping the `totals` stdout line).
    payload = {
        "converged": converged,
        "stabilize_ms": stabilize_ms, "coverage_ms": coverage_ms,
        "overlay_windows": overlay_windows,
        "gossip_windows": gossip_windows,
        "reason": None if converged else reason,
        # Attribution without re-parsing argv: where this run's artifact
        # landed (None without -run-dir) and the resolved gate set.
        "run_dir": (os.path.abspath(cfg.run_dir) if cfg.run_dir else None),
        "gates": cfg.resolved_gates(),
        **stats.to_dict(),
    }
    if serve_report is not None:
        payload["reshard_pause_ms"] = serve_report["reshard_pause_ms"]
        payload["serve"] = {k: serve_report[k] for k in
                            ("arrivals", "final_shards", "resizes",
                             "reshard_pause_ms", "shed")}
    if hostloss_report is not None:
        # Replayed-window accounting (ISSUE 20): how many windows the
        # recovery re-ran from the snapshot and what the restore pause
        # cost -- top-level for compare_runs-adjacent tooling, full
        # detail under "hostloss".
        result.recovered_windows = hostloss_report["recovered_windows"]
        result.recovery_pause_ms = hostloss_report["recovery_pause_ms"]
        payload["recovered_windows"] = hostloss_report["recovered_windows"]
        payload["recovery_pause_ms"] = hostloss_report["recovery_pause_ms"]
        payload["hostloss"] = hostloss_report
    if cfg.multi_rumor and not p1_interrupted:
        # live_cfg, not cfg: admission deferrals rewrite the injection
        # schedule, and latency is measured against what actually ran.
        payload.update(_multi_rumor_report(live_cfg, stepper, stats,
                                           coverage_ms))
    if cfg.model == "pushsum" and not p1_interrupted:
        from gossip_simulator_tpu.models import pushsum

        payload.update(pushsum.report(stepper))
    if telem is not None:
        payload["phases_s"] = {k: round(v, 6)
                               for k, v in sorted(telem.phases.items())}
    printer.result(payload)
    if telem is not None:
        report = _telemetry.TelemetryReport(
            n=cfg.n, phases=telem.phases,
            overlay=telem.overlay_snapshot(),
            gossip=telem.gossip_snapshot(),
            overlay_clock_scale=getattr(stepper, "overlay_clock_scale", 1.0))
        printer.telemetry(report.summary())
        if cfg.telemetry_summary:
            printer.block(report.summary_block())
    if cfg.run_dir and not printer.silent:
        _write_run_dir(cfg, telem, window_rows, payload, stats,
                       serve_report, hostloss_report)
    return result


def _final_shutdown_checkpoint(cfg: Config, stepper: Stepper, stats: Stats,
                               phase1: bool, window: int,
                               overlay_windows: int) -> None:
    """The signal path's final atomic save (ISSUE 11 satellite 1): whatever
    phase the run was in, its furthest state lands on disk before the
    "interrupted" result goes out, so `-resume` continues where the signal
    struck.  Collective like every snapshot; pruned like every save."""
    from gossip_simulator_tpu.utils import checkpoint

    if phase1:
        tree = stepper.overlay_state_pytree()
        if tree is not None and stepper.primary_host:
            checkpoint.save(cfg.checkpoint_dir, overlay_windows, tree,
                            Stats(n=cfg.n), prefix="overlay",
                            extra_meta={"phase": 1, "interrupted": True,
                                        "sim_ms": stepper.sim_time_ms()})
            checkpoint.prune(cfg.checkpoint_dir, cfg.ckpt_keep,
                             prefix="overlay")
    else:
        tree = stepper.state_pytree()
        if tree is not None and stepper.primary_host:
            checkpoint.save(cfg.checkpoint_dir, window, tree, stats,
                            extra_meta={"interrupted": True})
            checkpoint.prune(cfg.checkpoint_dir, cfg.ckpt_keep)


def _write_run_dir(cfg: Config, telem, window_rows: list, payload: dict,
                   stats: Stats, serve_report: Optional[dict] = None,
                   hostloss_report: Optional[dict] = None) -> None:
    """Flush the `-run-dir` artifact (utils/artifact.py layout).  The
    trajectory prefers the device-recorded history (fast path), falls
    back to the windowed loop's host-collected rows, and degrades to a
    single final-Stats row only when neither existed (a silent rank or a
    telemetry-off oracle-free fast path) -- the basis is named so
    compare_runs can refuse apples-to-oranges fingerprints."""
    from gossip_simulator_tpu.utils import artifact

    rdir = artifact.RunDir(cfg.run_dir)
    hist_o = telem.overlay_snapshot() if telem is not None else None
    hist_g = telem.gossip_snapshot() if telem is not None else None
    traj = artifact.trajectory_from_history(hist_g)
    basis = "telemetry"
    if traj is None:
        traj = artifact.trajectory_from_rows(window_rows)
        basis = "windows"
    if traj is None:
        traj = artifact.trajectory_from_rows(
            [(stats.round, stats.total_received, stats.total_message,
              stats.total_crashed, stats.total_removed)])
        basis = "final"
    rdir.write_config(cfg)
    rdir.write_env()
    rdir.write_telemetry(hist_o, hist_g, traj)
    if cfg.telemetry_spatial_enabled:
        # Shard-health watchdog over the fetched panels: verdict to
        # health.json, findings to the flight recorder as instants.
        from gossip_simulator_tpu.utils import health as _health

        n_shards = getattr(telem, "n_shards", 1) if telem is not None else 1
        rdir.write_health(_health.report_health(_health.evaluate_health(
            hist_g, cap=_health.ring_slot_cap(cfg, n_shards))))
    if serve_report is not None:
        rdir.write_serve(serve_report)
    if hostloss_report is not None:
        rdir.write_hostloss(hostloss_report)
    rdir.write_result({
        **payload,
        "fingerprint": artifact.fingerprint_rows(traj),
        "fingerprint_windows": int(traj.shape[0]),
        "fingerprint_basis": basis,
    })


def latency_summary(lat) -> dict:
    """Interpolated per-rumor latency summary (the SLO block).  np.percentile
    linear interpolation between order statistics -- NOT histogram-bucket
    upper edges, which overstated p50 by up to a full bucket width at small
    R -- with the exact min/max/mean alongside."""
    import numpy as np

    a = np.asarray(lat, np.int64)
    p50, p90, p99 = np.percentile(a, [50, 90, 99])
    return {
        "min": int(a.min()), "max": int(a.max()),
        "p50": round(float(p50), 2),
        "p90": round(float(p90), 2),
        "p99": round(float(p99), 2),
        "mean": round(float(a.mean()), 2),
    }


def _multi_rumor_report(cfg: Config, stepper: Stepper, stats: Stats,
                        coverage_ms: float) -> dict:
    """Steady-state serving metrics for the terminal `result` record
    (simulated-time domain; wall-clock throughput lives in the telemetry
    report).  Per-rumor latency = rumor_done stamp minus the ANALYTIC
    inject tick (arrivals.arrival_ticks under -traffic stream, tick 0
    under oneshot) -- the schedule is deterministic, so no per-rumor
    start stamp is carried on device."""
    import jax
    import numpy as np

    from gossip_simulator_tpu import arrivals as _arrivals

    R = cfg.rumors
    done = np.asarray(jax.device_get(stepper.state.rumor_done))[:R]
    inject = (np.asarray(_arrivals.arrival_ticks(cfg), np.int64)
              if cfg.traffic == "stream" else np.zeros(R, np.int64))
    out: dict = {"traffic": cfg.traffic}
    secs = coverage_ms / 1000.0
    if secs > 0:
        out["rumors_per_sec"] = round(stats.rumors_done / secs, 4)
        out["deliveries_per_sec"] = round(stats.total_message / secs, 1)
    lat = (done.astype(np.int64) - inject)[done >= 0]
    if lat.size:
        out["rumor_latency_ms"] = latency_summary(lat)
        counts, edges = np.histogram(lat, bins=min(10, max(1, lat.size)))
        out["rumor_latency_hist"] = {
            "edges_ms": [round(float(e), 1) for e in edges],
            "counts": [int(c) for c in counts],
        }
    return out


class _Checkpointer:
    def __init__(self, cfg: Config, stepper: Stepper, run_id: str = ""):
        self.cfg, self.stepper = cfg, stepper
        # Provenance sidecar keys (empty run_id = none, keeping plain
        # runs' sidecars byte-identical to pre-provenance builds).
        self.extra_meta = {"run_id": run_id} if run_id else None

    def _due(self, window: int) -> bool:
        cfg = self.cfg
        return (cfg.checkpointing_enabled
                and window % cfg.checkpoint_every == 0)

    def maybe_save(self, window: int, stats: Stats) -> None:
        if not self._due(window):
            return
        from gossip_simulator_tpu.utils import checkpoint

        # Collective on every rank (the sharded backend host-gathers);
        # only the primary host writes the file.
        tree = self.stepper.state_pytree()
        if tree is not None and self.stepper.primary_host:
            checkpoint.save(self.cfg.checkpoint_dir, window, tree, stats,
                            extra_meta=self.extra_meta)
            checkpoint.prune(self.cfg.checkpoint_dir, self.cfg.ckpt_keep)

    def maybe_save_overlay(self, window: int) -> None:
        """Phase-1 snapshot on the same cadence (VERDICT r3 weak #6: a
        minutes-long 100M overlay build was all-or-nothing).  Written
        under the `overlay_` prefix with phase=1 metadata; the load path
        continues construction mid-overlay."""
        if not self._due(window):
            return
        from gossip_simulator_tpu.utils import checkpoint

        # None from backends without phase-1 snapshots (the native/cpp
        # oracles: base.overlay_state_pytree's default -- phase 1 is
        # seconds at their feasible n).
        tree = self.stepper.overlay_state_pytree()
        if tree is not None and self.stepper.primary_host:
            checkpoint.save(
                self.cfg.checkpoint_dir, window, tree,
                Stats(n=self.cfg.n), prefix="overlay",
                extra_meta={"phase": 1,
                            "sim_ms": self.stepper.sim_time_ms()})
            checkpoint.prune(self.cfg.checkpoint_dir, self.cfg.ckpt_keep,
                             prefix="overlay")

