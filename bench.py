#!/usr/bin/env python
"""Benchmark harness.  Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline metric: node-updates/sec/chip for SI push gossip (BASELINE.json).
One "node update" = one node-tick of simulation work (N nodes advanced one
simulated ms).  vs_baseline = this backend's rate / the event-driven
native-oracle rate measured on this host (the stand-in for the reference's
Go loop -- Go toolchain absent here, same actor-per-node semantics).

Usage:
    python bench.py                  # headline + BASELINE config suite +
                                     # 100M row + Pallas validation (the
                                     # driver-captured full record)
    python bench.py --n 10000       # smoke run: headline at N only (skips
                                     # the suite, the 100M row and the
                                     # PALLAS_VALIDATION.json refresh)
    python bench.py --n 10000 --full # force the full record at an
                                     # overridden headline size
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from gossip_simulator_tpu.utils import jaxsetup

jaxsetup.setup()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from gossip_simulator_tpu import tuning as _tuning  # noqa: E402
from gossip_simulator_tpu.backends.jax_backend import JaxStepper  # noqa: E402
from gossip_simulator_tpu.backends.native import NativeStepper  # noqa: E402
from gossip_simulator_tpu.config import Config  # noqa: E402
from gossip_simulator_tpu.utils import trace as _trace  # noqa: E402
from gossip_simulator_tpu.utils.telemetry import GCOL  # noqa: E402

# --- flight recorder (PR 10) -------------------------------------------------
# With `--run-dir DIR`, every measured row writes a self-describing artifact
# (utils/artifact.py layout) under DIR/<row-name>/, and the whole bench run
# records one span per row into DIR/bench_trace.json.  The row name flows
# through pool_retry's `name=` (every hardware capture goes through it) or
# the suite loops' explicit set -- `_row_name` is the single channel so
# `_bench_backend` needs no signature change at any call site.
_RUN_DIR_ROOT: str | None = None
_ROW_NAME: str = ""


class _named_row:
    """Scoped bench-row name for artifact/trace attribution."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        global _ROW_NAME
        self._prev = _ROW_NAME
        _ROW_NAME = self.name
        return self

    def __exit__(self, *exc):
        global _ROW_NAME
        _ROW_NAME = self._prev
        return False

# Error signatures of an unreachable/flaky accelerator (grpc/PJRT
# faults): retried with backoff instead of killing the whole bench
# record mid-suite.  This retry machinery is slated for the bench.py
# rebuild (ROADMAP Queue 1 item 1).
POOL_ERROR_MARKERS = ("UNAVAILABLE", "unreachable", "DEADLINE_EXCEEDED",
                      "failed to connect", "Connection refused",
                      "Socket closed", "RESOURCE_EXHAUSTED: Failed to "
                      "allocate device",
                      # Bounded jax.distributed join failure
                      # (parallel/mesh.bounded_initialize, ISSUE 20): a
                      # coordinator that never comes up is pool weather,
                      # not a bench bug.
                      "DistributedInitError")


def is_pool_error(exc: BaseException) -> bool:
    text = repr(exc)
    return any(m in text for m in POOL_ERROR_MARKERS)


def pool_retry(fn, *args, name: str = "", retries: int = 3,
               base_delay_s: float = 10.0, _sleep=time.sleep, **kw):
    """Run `fn`, retrying pool-shaped failures (is_pool_error) up to
    `retries` times with exponential backoff.  A still-failing call -- or
    a non-pool error -- returns a dated ``skipped`` record (skip_record,
    THE one emitter of them) instead of raising, so one dead pool stops
    ONE row, not the whole suite (the PR-2/PR-3 sessions each lost their
    TPU evidence window to an unreachable pool killing bench.py
    mid-record).  `_sleep` is injectable for the unit test."""
    last = None
    with _named_row(name or getattr(fn, "__name__", "call")):
        for attempt in range(retries + 1):
            try:
                return fn(*args, **kw)
            except Exception as e:  # noqa: BLE001 -- recorded, not silent
                last = e
                if not is_pool_error(e) or attempt == retries:
                    break
                delay = base_delay_s * (2 ** attempt)
                print(f"[bench] {name or getattr(fn, '__name__', 'call')}: "
                      f"pool error (attempt {attempt + 1}/{retries + 1}), "
                      f"retrying in {delay:.0f}s: {e!r}", file=sys.stderr)
                _sleep(delay)
    return skip_record(last, attempts=attempt + 1)


# The bench session this tree runs as (one per PR round): stamped into
# every dated skip record so a BENCH_SELF_rNN.json names WHICH session
# failed to reach hardware, and diffed against queued_since below to
# render how many consecutive sessions each queued row has waited.
SESSION = "r20"


def session_number(tag: str) -> int:
    """Numeric part of an rNN session tag ("r13" -> 13)."""
    return int(tag.lstrip("r"))


def skip_record(error: BaseException, attempts: int = 1) -> dict:
    """THE dated skip record (satellite: one helper instead of per-round
    hand-written JSON notes).  Every queued hardware row carries exactly
    this shape; QUEUED_HARDWARE_ROWS + queued_section() aggregate them
    into one generated list.  `session` records which bench session the
    failure happened in, and the QUEUED table renders the
    consecutive-miss count)."""
    import datetime

    return {"skipped": True,
            "date": datetime.date.today().isoformat(),
            "session": SESSION,
            "error": repr(error),
            "pool_error": is_pool_error(error),
            "attempts": attempts}


# Every bench row that NEEDS a TPU and is still unmeasured.  One place, one shape: the generated
# QUEUED section in the README renders from this table, and the next
# hardware window works it top to bottom.
QUEUED_HARDWARE_ROWS = (
    {"row": "sharded_50m_twins", "queued_since": "r6",
     "capture": "capture_sharded_1chip + capture_scale50",
     "what": "50M sharded-vs-jax same-seed twins on a v5e (PR-2 routing "
             "claims rest on CPU stand-ins)"},
    {"row": "exchange_profile", "queued_since": "r6",
     "capture": "capture_exchange_profile",
     "what": "all_to_all exchange cost split at S=8"},
    {"row": "two_phase_100m", "queued_since": "r7",
     "capture": "capture_100m_two_phase",
     "what": "100M reference-default two-phase wall clock (PR-3 overlay "
             "floors measured on CPU only)"},
    {"row": "overlay_profile", "queued_since": "r7",
     "capture": "capture_overlay_profile",
     "what": "phase-1 chunk-ladder / dead-skip gate timings at scale"},
    {"row": "multirumor_50m", "queued_since": "r8",
     "capture": "capture_multirumor_50m",
     "what": "50M single- vs multi-rumor twins (marginal cost of the "
             "rumor axis at scale)"},
    {"row": "deliver_kernel_twins", "queued_since": "r9",
     "capture": "capture_deliver_kernel_twins",
     "what": "50M/100M xla-vs-pallas same-seed wall-clock twins "
             "(kernel is parity-pinned but unmeasured)"},
    {"row": "pallas_validation", "queued_since": "r6",
     "capture": "_pallas_validation",
     "what": "on-device distributional checks + fused_kernel profile "
             "rows (interpret-mode CPU rows are correctness-only)"},
    {"row": "autotune_sweep", "queued_since": "r12",
     "capture": "capture_autotune",
     "what": "chunk-ladder autotune sweep at 50M/100M on a v5e-8, "
             "neutrality-gated winners persisted to TUNING_TABLE.json "
             "per platform/scale band"},
    {"row": "exchange_pipeline_50m_twins", "queued_since": "r13",
     "capture": "capture_exchange_pipeline_twins",
     "what": "50M S=8 -exchange-pipeline double-vs-off same-seed "
             "wall-clock twins on a v5e-8 (the schedule is parity-pinned "
             "bit-identical on CPU; the overlap win needs real ICI)"},
    {"row": "pushsum_50m_twins", "queued_since": "r14",
     "capture": "capture_pushsum_50m",
     "what": "50M PushSum sharded-vs-jax same-seed twins (exchange cost "
             "of the 12-column mass payload + shard-invariance at scale; "
             "CPU pins cover semantics only)"},
    {"row": "spatial_overhead_50m", "queued_since": "r16",
     "capture": "capture_spatial_overhead_50m",
     "what": "50M sharded S=8 spatial-panels on-vs-off same-seed twins "
             "(the traffic matrix + shard/group panels' recording cost "
             "over real ICI; the CPU spatial_overhead_1m twin bounds "
             "only the single-chip scatter cost)"},
    {"row": "hostloss_50m_twins", "queued_since": "r20",
     "capture": "capture_hostloss_50m",
     "what": "50M supervised kill-drill vs undisturbed same-seed twin "
             "(recovery_pause_ms against a real-scale snapshot + "
             "Stats-exactness at scale; the CPU hostloss_recovery row "
             "bounds only the /100 stand-in restore)"},
)


def queued_section() -> str:
    """The generated QUEUED markdown block (README carries it between
    QUEUED:BEGIN/END markers; regenerate with `python bench.py
    --write-queued`)."""
    lines = [
        "All rows below need TPU hardware and carry dated `skipped` "
        "records (emitted by `bench.py skip_record`, each stamped with "
        "its bench session) in the most recent `BENCH_SELF_rNN.json`.  "
        "`missed` counts the "
        f"consecutive sessions a row has waited as of {SESSION}. "
        "They run automatically from `python bench.py` in the next "
        "hardware window.",
        "",
        "| queued row | since | missed | capture | what it measures |",
        "|---|---|---|---|---|",
    ]
    now = session_number(SESSION)
    for q in QUEUED_HARDWARE_ROWS:
        missed = now - session_number(q["queued_since"]) + 1
        lines.append(f"| `{q['row']}` | {q['queued_since']} | "
                     f"{missed} | `{q['capture']}` | {q['what']} |")
    return "\n".join(lines)


QUEUED_BEGIN = "<!-- QUEUED:BEGIN (generated by `python bench.py --write-queued`) -->"
QUEUED_END = "<!-- QUEUED:END -->"


def write_queued_section(readme_path: str) -> bool:
    """Replace the README's generated QUEUED block in place; returns
    whether the file changed (CI uses this as an up-to-date check)."""
    with open(readme_path) as fh:
        text = fh.read()
    begin = text.index(QUEUED_BEGIN) + len(QUEUED_BEGIN)
    end = text.index(QUEUED_END)
    new = text[:begin] + "\n" + queued_section() + "\n" + text[end:]
    if new != text:
        with open(readme_path, "w") as fh:
            fh.write(new)
        return True
    return False


def _bench_backend(cfg: Config, time_graph_gen: bool = False) -> dict:
    """Time the device-side run-to-target while_loop for any Stepper
    backend (excludes compile).  THE one warmup/reset/timed protocol --
    the sharded-vs-jax 1-chip twins the README projection rests on must
    stay like-for-like, so both go through here.

    The body runs under tuning.ambient(cfg), like driver.run_simulation:
    cfg-less tunable lookups deeper in the stack (exchange pad/rank
    path, pallas block rows) resolve THIS row's tuning table instead of
    registry defaults, so bench evidence measures the same constant
    resolution a production run of the same config would.

    With `time_graph_gen`, steady-state graph generation is timed
    separately (first-call init is tracing + compile + generate; the
    regeneration shows the cached-executable cost) -- skipped at
    100M-scale where it would hold a SECOND friends table (2.4 GB at
    1e8 x 6) alongside the live state; transient peaks like that are
    what crashed the r2 fanout-6 attempts on the 16 GB v5e."""
    with _tuning.ambient(cfg):
        return _bench_backend_body(cfg, time_graph_gen)


def _bench_backend_body(cfg: Config, time_graph_gen: bool) -> dict:
    from gossip_simulator_tpu.backends import make_stepper
    from gossip_simulator_tpu.models import graphs

    s = make_stepper(cfg)
    t0 = time.perf_counter()
    s.init()
    jax.block_until_ready(s.state.friends)
    graph_s = time.perf_counter() - t0
    if time_graph_gen and cfg.n < 50_000_000:
        t0 = time.perf_counter()
        f, c = graphs.generate(cfg, graphs.graph_key(cfg))
        jax.block_until_ready(f)
        graph_gen_s = time.perf_counter() - t0
        del f, c
    else:
        graph_gen_s = None
    s.seed()
    # Warm-up: compile + one full run, then rebuild state (the run donated
    # the old buffers) and time a clean run with the executable cached.
    with _trace.span(f"bench.{_ROW_NAME or 'row'}.warmup", cat="bench"):
        s.run_to_target()
    s.reset_state()
    s.seed()
    t0 = time.perf_counter()
    with _trace.span(f"bench.{_ROW_NAME or 'row'}", cat="bench") as sp:
        stats = s.run_to_target()
        if sp is not None:
            sp.update(n=cfg.n, messages=int(stats.total_message),
                      ticks=int(stats.round))
    run_s = time.perf_counter() - t0
    ticks = stats.round
    out = {
        "n": cfg.n, "backend": cfg.backend, "devices": jax.device_count(),
        "ticks": ticks, "run_s": run_s,
        "graph_s": graph_s, "graph_gen_s": graph_gen_s,
        "coverage": stats.coverage, "total_message": stats.total_message,
        "ns_per_message": (run_s * 1e9 / stats.total_message
                           if stats.total_message else None),
        "node_updates_per_sec": cfg.n * ticks / run_s if run_s > 0 else 0.0,
        "messages_per_sec": (stats.total_message / run_s
                             if run_s > 0 else 0.0),
        "converged": stats.coverage >= cfg.coverage_target,
    }
    # Device-resident telemetry rides the timed run for free (the history
    # writes are scalar ops inside the jitted loop): the phase ledger --
    # init / compile (first bounded call, warm run) / execute / fetch --
    # and the per-window count make the perf trajectory self-documenting
    # in the BENCH record.
    telem = getattr(s, "_telem", None)
    if telem is not None:
        out["phases_s"] = {k: round(v, 4)
                           for k, v in sorted(telem.phases.items())}
        hist = telem.gossip_snapshot()
        if hist:
            out["windows"] = hist["count"]
            out["mail_high_water"] = int(
                hist["cols"][:hist["count"], GCOL["mail_high"]]
                .max(initial=0))
            if cfg.scenario_resolved.active:
                # Per-window churn telemetry rides the same device-
                # resident history (cumulative counters per window).
                c = hist["cols"][:hist["count"]]
                out["per_window_scenario"] = {
                    "tick": c[:, GCOL["tick"]].tolist(),
                    "scen_crashed": c[:, GCOL["scen_crashed"]].tolist(),
                    "scen_recovered": c[:, GCOL["recovered"]].tolist(),
                    "heal_repaired": c[:, GCOL["repaired"]].tolist(),
                    "part_dropped": c[:, GCOL["part_dropped"]].tolist(),
                }
    if cfg.scenario_resolved.active:
        out.update(scen_crashed=stats.scen_crashed,
                   scen_recovered=stats.scen_recovered,
                   part_dropped=stats.part_dropped,
                   heal_repaired=stats.heal_repaired,
                   overlay_heal=cfg.overlay_heal)
    if cfg.multi_rumor:
        # Serving-workload metrics (ISSUE 8): coverage above is already the
        # min-across-rumors; the throughput pair is the steady-state rate in
        # the SIMULATED-time domain (wall-clock rates are the generic
        # messages_per_sec above).
        sim_s = s.sim_time_ms() / 1000.0
        out.update(rumors=cfg.rumors, traffic=cfg.traffic,
                   rumors_done=stats.rumors_done,
                   rumor_min_recv=stats.rumor_min_recv,
                   rumors_per_sim_sec=(round(stats.rumors_done / sim_s, 4)
                                       if sim_s > 0 else None),
                   deliveries_per_sim_sec=(round(
                       stats.total_message / sim_s, 1)
                       if sim_s > 0 else None))
    if _RUN_DIR_ROOT and _ROW_NAME:
        _write_bench_run_dir(cfg, s, out)
    return out


def _write_bench_run_dir(cfg: Config, stepper, row: dict) -> None:
    """One run artifact per bench row (`--run-dir`): same layout the
    driver writes, so compare_runs.py diffs bench rows and CLI runs
    interchangeably.  The trajectory comes from the timed run's device
    history (warm run -- reset_state dropped the warmup's)."""
    from gossip_simulator_tpu.utils import artifact

    rdir = artifact.RunDir(os.path.join(_RUN_DIR_ROOT, _ROW_NAME))
    telem = getattr(stepper, "_telem", None)
    hist_g = telem.gossip_snapshot() if telem is not None else None
    hist_o = telem.overlay_snapshot() if telem is not None else None
    traj = artifact.trajectory_from_history(hist_g)
    result = dict(row)
    if traj is None:
        st = stepper.stats()
        traj = artifact.trajectory_from_rows(
            [(st.round, st.total_received, st.total_message,
              st.total_crashed, st.total_removed)])
        result["fingerprint_basis"] = "final"
    else:
        result["fingerprint_basis"] = "telemetry"
    result["fingerprint"] = artifact.fingerprint_rows(traj)
    result["fingerprint_windows"] = int(traj.shape[0])
    rdir.write_config(cfg)
    rdir.write_env({"bench_row": _ROW_NAME})
    rdir.write_telemetry(hist_o, hist_g, traj)
    rdir.write_result(result)


def _bench_jax(cfg: Config) -> dict:
    return _bench_backend(cfg, time_graph_gen=True)


def _bench_oracle(cfg: Config, budget_s: float = 20.0, stepper=None) -> dict:
    """Event-driven oracle rate in node-updates/sec on the same semantics
    (backend 'native' = Python actor loop, 'cpp' = C++ discrete-event).
    Run at a feasible N, rate extrapolates roughly linearly (O(messages))."""
    if stepper is not None:
        s = stepper
    elif cfg.backend == "cpp":
        from gossip_simulator_tpu.backends.cpp import CppStepper

        s = CppStepper(cfg)
    else:
        s = NativeStepper(cfg)
    s.init()
    while not s.overlay_window()[2]:
        pass
    s.seed()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        st = s.gossip_window()
        if st.coverage >= cfg.coverage_target or s.exhausted:
            break
    run_s = time.perf_counter() - t0
    ticks = int(s.sim_time_ms())
    return {
        "n": cfg.n, "ticks": ticks, "run_s": run_s,
        "coverage": st.coverage,
        "node_updates_per_sec": cfg.n * ticks / run_s if run_s > 0 else 0.0,
        "converged": st.coverage >= cfg.coverage_target,
    }


def headline(n: int | None, seed: int) -> dict:
    on_tpu = jax.default_backend() == "tpu"
    if n is None:
        n = 10_000_000 if on_tpu else 200_000
    # BASELINE config 2 shape: SI push, fanout 3, static kout graph (the
    # overlay build is phase 1 and benchmarked separately in --full).
    # coverage_target=0.90: at fanout 3 / drop 0.1 the infection asymptotes at
    # 1 - e^{-2.7} ~ 93% (the reference would livelock waiting for 99%,
    # SURVEY §5.3a), so 90% is the honest "done" line for this config.
    cfg = Config(n=n, fanout=3, graph="kout", backend="jax", seed=seed,
                 crashrate=0.001, coverage_target=0.90, max_rounds=3000,
                 pallas=on_tpu, progress=False).validate()
    with _named_row("headline_jax"):
        jx = _bench_jax(cfg)
    # Two baselines, both part of this repo:
    # * python actor loop ("native"): per-node actors + delayed deliveries,
    #   the architecture-faithful stand-in for the reference's
    #   goroutine-per-node design (Go toolchain absent here).
    # * C++ discrete-event loop ("cpp"): the strongest single-core native
    #   implementation of the same semantics -- the honest perf bar.
    nat = _bench_oracle(cfg.replace(n=min(n, 100_000), backend="native"))
    import shutil

    from gossip_simulator_tpu.backends import cpp as cpp_mod

    cpp_cfg = cfg.replace(n=min(n, 10_000_000), backend="cpp")
    if shutil.which("g++") or os.path.exists(cpp_mod._LIB):
        # A prebuilt libgossip_sim.so works without the toolchain; real
        # backend failures still raise rather than masquerading as a
        # missing-compiler environment limit.  Same n as the JAX run (up to
        # 10M) so vs_cpp compares like for like -- measured 12.7s / 228M
        # node-updates/s at 10M, linear in messages as expected.
        cpp = _bench_oracle(cpp_cfg, budget_s=120.0)
    else:
        cpp = {"error": "g++ not available and no prebuilt library",
               "node_updates_per_sec": 0.0}
    # Multithreaded C++ baseline (VERDICT r3 stretch #8): the whole-host
    # native bar.  On this image's 1-core container it degenerates to the
    # serial rate (threads recorded so the record is self-describing);
    # on a real multi-core host it is the honest ">= 50x" denominator.
    nthreads = os.cpu_count() or 1
    try:
        from gossip_simulator_tpu.backends.cpp import CppMtStepper

        cpp_mt = _bench_oracle(
            cpp_cfg, budget_s=120.0,
            stepper=CppMtStepper(cpp_cfg, nthreads=nthreads))
        cpp_mt["threads"] = nthreads
    except Exception as e:
        cpp_mt = {"error": repr(e), "node_updates_per_sec": 0.0,
                  "threads": nthreads}
    vs_actor = (jx["node_updates_per_sec"] / nat["node_updates_per_sec"]
                if nat["node_updates_per_sec"] else 0.0)
    vs_cpp = (jx["node_updates_per_sec"] / cpp["node_updates_per_sec"]
              if cpp["node_updates_per_sec"] else 0.0)
    vs_cpp_mt = (jx["node_updates_per_sec"] / cpp_mt["node_updates_per_sec"]
                 if cpp_mt.get("node_updates_per_sec") else 0.0)
    detail = {
        "device": jax.devices()[0].device_kind,
        "jax": jx,
        "python_actor_baseline": nat,
        "cpp_event_baseline": cpp,
        "cpp_mt_baseline": cpp_mt,
    }
    return {
        "metric": "node_updates_per_sec_per_chip",
        "value": round(jx["node_updates_per_sec"], 1),
        "unit": "node_ticks/s",
        # vs the architecture-faithful actor loop (reference design).
        "vs_baseline": round(vs_actor, 2),
        # vs our optimized C++ discrete-event loop (strongest native tier).
        "vs_cpp_event_loop": round(vs_cpp, 2),
        # vs the multithreaded C++ loop over all host cores.
        "vs_cpp_mt": round(vs_cpp_mt, 2),
        "detail": detail,
    }


def capture_sharded_1chip(detail: dict, seed: int) -> None:
    """VERDICT r3 #1 / r5 #1: the sharded engine's real-TPU cost at equal
    n vs the jax backend.  Through round 5 the S=1 twin measured the full
    routing constant (route_multi sort+scatter, post-exchange filtering;
    61.6 vs 48.7 ns/msg at 50M fanout 6 -- the 27% gap VERDICT r5 named).
    Round 6 ELIMINATED the identity work on a 1-device mesh (sort-free
    bucketing, pre-exchange suppression, DIRECT_SELF_APPEND -- see
    parallel/event_sharded.py; bit-identical totals by construction and
    by tests/test_sharded.py's parity pins), so the S=1 twin now measures
    the per-shard constant the v5e-8 projection's term 1 cites, while the
    S>1-only routing machinery is measured separately by
    scripts/profile_exchange.py (the projection's term 2).  Round-4
    history: 10M fanout 3 sharded 2.394s vs jax 2.259s (+6%); 50M fanout
    6 @99% 21.44s vs 19.40s.  100M on ONE device exceeds the sharded
    wire packing bound (n_local*dw*B < 2^31 -- a per-SHARD bound: v5e-8's
    n_local=12.5M is 30x inside it), so 50M is the largest 1-chip twin.
    The rows record `devices`: on a multi-chip host the sharded rows are
    a real S-way run (ICI included), not the S=1 twin -- read them
    accordingly."""
    base = Config(n=10_000_000, fanout=3, graph="kout", backend="sharded",
                  seed=seed, crashrate=0.001, coverage_target=0.90,
                  max_rounds=3000, progress=False).validate()
    # The 99% twins run crashrate 0.0 from round 5 on (same rationale as
    # the 100M north-star row: the reference's own crash default truncates
    # to 0, and it is the duplicate-suppression gate); sharded_10m keeps
    # 0.001 for cross-round comparability.
    for name, cfg in (
        ("sharded_10m", base),
        ("sharded_50m_99pct", base.replace(
            n=50_000_000, fanout=6, coverage_target=0.99,
            crashrate=0.0).validate()),
        ("jax_50m_99pct", base.replace(
            n=50_000_000, fanout=6, coverage_target=0.99,
            crashrate=0.0, backend="jax").validate()),
    ):
        # pool_retry: an unreachable-pool fault retries with backoff and
        # then lands a dated `skipped` record (the PR-2/PR-3 failure
        # mode) instead of a bare error row.
        detail[name] = pool_retry(_bench_backend, cfg, name=name)


def capture_exchange_profile(detail: dict) -> None:
    """Routing-constant micro-profile (scripts/profile_exchange.py run
    in-process -- a subprocess would open a second TPU client while this
    one is live): the per-component append/route constants the README
    v5e-8 projection's term 2 cites."""
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        spec = importlib.util.spec_from_file_location(
            "profile_exchange",
            os.path.join(here, "scripts", "profile_exchange.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        m = 786_432 if jax.default_backend() == "tpu" else 98_304
        prof = mod.profile_append_s1(m, 5)
        prof["ns_per_lane"] = {k[:-2]: v * 1e9 / m for k, v in prof.items()}
        detail["exchange_profile"] = {"m": m, "append_s1": prof}
    except Exception as e:  # record, don't kill the record
        detail["exchange_profile"] = {"error": repr(e)}


def capture_100m_two_phase(detail: dict, seed: int,
                           phase1_twins: bool = True) -> None:
    """VERDICT r3 #3: the full reference-default two-phase pipeline at
    flagship scale -- 100M-node dynamic-overlay construction (rounds
    mode, the auto split-round memory path) chained into the epidemic
    phase on one chip.  fanout 5 is the reference default; coverage 0.90
    is its honest done-line (5 x 0.9 drop asymptotes ~98.9% < 99%,
    SURVEY 5.3a).  Each row runs ONCE (no warm/timed double pass); wall
    time includes compile -- the `wall_warm_s` field subtracts the
    telemetry-recorded compile share when available.

    Round 7 (`phase1_twins`): A/B twin rows isolate each phase-1 gate's
    contribution (ISSUE 4 acceptance) -- `two_phase_100m` runs the
    round-7 defaults, `_pre` forces every gate off (the bit-exact
    pre-round-7 pipeline), and the three single-gate-off rows subtract
    one lever each.  The membership multiset of `_pre` at the pinned
    seed is the round-6 result by construction (gates off = the old
    code paths; pinned at CPU scale by tests/test_overlay_phase1.py)."""
    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    base = Config(n=100_000_000, graph="overlay", fanout=5, seed=seed,
                  coverage_target=0.90, backend="jax",
                  progress=False).validate()
    rows = [("two_phase_100m", base)]
    if phase1_twins:
        rows += [
            ("two_phase_100m_pre", base.replace(
                overlay_static_boot="off", overlay_adaptive_chunks="off",
                overlay_dead_skip="off")),
            ("two_phase_100m_dynboot", base.replace(
                overlay_static_boot="off")),
            ("two_phase_100m_noadaptive", base.replace(
                overlay_adaptive_chunks="off")),
            ("two_phase_100m_nodeadskip", base.replace(
                overlay_dead_skip="off")),
        ]
    for name, cfg in rows:
        t0 = time.perf_counter()
        try:
            # Context-managed printer: closed even if the near-ceiling run
            # faults (metrics.ProgressPrinter.__exit__).
            with ProgressPrinter(False) as printer:
                res = run_simulation(cfg, printer=printer)
            detail[name] = {
                "n": cfg.n, "overlay_mode": cfg.overlay_mode_resolved,
                "overlay_windows": res.overlay_windows,
                "stabilize_sim_ms": res.stabilize_ms,
                "quiesced": True,  # run_simulation raises otherwise
                "coverage": res.stats.coverage,
                "total_message": res.stats.total_message,
                "mailbox_dropped": res.stats.mailbox_dropped,
                "converged": res.converged,
                "gates": {
                    "static_boot": cfg.overlay_static_boot,
                    "adaptive_chunks": cfg.overlay_adaptive_chunks,
                    "dead_skip": cfg.overlay_dead_skip,
                },
                "wall_s": round(time.perf_counter() - t0, 1),
            }
        except Exception as e:  # record, don't kill the record
            detail[name] = {"error": repr(e)}


def capture_overlay_profile(detail: dict) -> None:
    """Phase-1 cost-floor micro-profile (scripts/profile_overlay.py run
    in-process -- a subprocess would open a second TPU client while this
    one is live): the per-chunk scatter/scan and per-row popcount
    constants the README phase-1 cost-model table cites."""
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        spec = importlib.util.spec_from_file_location(
            "profile_overlay",
            os.path.join(here, "scripts", "profile_overlay.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        n = 16_777_216 if jax.default_backend() == "tpu" else 1_048_576
        from gossip_simulator_tpu.models import overlay as _ov
        cap = Config(n=n).mailbox_cap_for(n)
        widths = _ov.hosted_chunk_widths(Config(n=n), n)
        detail["overlay_profile"] = {
            "n": n, "cap": cap, "widths": list(widths),
            "chunk_floor": mod.profile_chunk_floor(n, cap, widths, 3),
            "row_floor": mod.profile_row_floor(n, cap, 5),
        }
    except Exception as e:  # record, don't kill the record
        detail["overlay_profile"] = {"error": repr(e)}


def capture_scale50(detail: dict, seed: int) -> None:
    """Flagship-adjacent rows for the beyond-parity protocols (VERDICT r4
    #7): event-engine SIR and push-pull at 50M on one chip.  SIR runs the
    kout graph here -- the BASELINE config-4 Erdos table at lambda=8 is
    29 columns wide (5.8 GB at 5e7) and does not fit a 16 GB chip next to
    the SIR-sized mail ring (measured RESOURCE_EXHAUSTED 2026-08-01; the
    10M suite row keeps the faithful Erdos shape, and the sharded mesh is
    the path past it).  Push-pull rides the lane-aware call budget
    (epidemic.run_call_budget) and the protocol's placeholder friends
    table (graphs.generate)."""
    for name, cfg in (
        ("sir_50m_kout", Config(
            n=50_000_000, fanout=8, graph="kout", protocol="sir",
            removal_rate=0.2, backend="jax", seed=seed, pallas=True,
            coverage_target=0.8, progress=False)),
        ("pushpull_50m_logn", Config(
            n=50_000_000, fanout=26, protocol="pushpull", graph="kout",
            backend="jax", seed=seed, progress=False)),
    ):
        try:
            detail[name] = _bench_backend(cfg.validate())
        except Exception as e:  # record, don't kill the record
            detail[name] = {"error": repr(e)}


# ISSUE-5 acceptance scenario: >= 20% steady churn with 60 ms reboots
# plus a mid-run 2-way partition -- the coverage-under-churn twins' fault
# timeline (tests/test_scenario.py pins the same shape at CPU scale).
CHURN_SCENARIO = ('{"groups": 2, "downtime": 60, "events": ['
                  '{"type": "churn", "start": 0, "end": 150, "rate": 2.0},'
                  '{"type": "crash", "at": 30, "frac": 0.3, "group": 1},'
                  '{"type": "partition", "start": 20, "end": 60}]}')


def capture_churn_healing(detail: dict, seed: int,
                          n: int | None = None) -> None:
    """Coverage-under-churn heal-on/off twins (ISSUE 5 acceptance): a
    1M-node SI run under CHURN_SCENARIO reaches the 99% target with
    -overlay-heal on and demonstrably strands coverage with it off; both
    rows carry the per-window churn telemetry.  CPU hosts run the /100
    twin (same scenario shape; tests pin the small-n behavior)."""
    if n is None:
        n = 1_000_000 if jax.default_backend() == "tpu" else 10_000
    base = Config(n=n, fanout=6, graph="kout", backend="jax", seed=seed,
                  crashrate=0.0, coverage_target=0.99, max_rounds=2000,
                  scenario=CHURN_SCENARIO, progress=False).validate()
    for name, cfg in (("churn_1m_heal_on",
                       base.replace(overlay_heal="on")),
                      ("churn_1m_heal_off", base)):
        row = pool_retry(_bench_backend, cfg, name=name)
        row["n"] = cfg.n
        detail[name] = row
    on, off = detail["churn_1m_heal_on"], detail["churn_1m_heal_off"]
    if "error" not in on and "skipped" not in on:
        on["acceptance"] = bool(
            on.get("converged") and not off.get("converged", True)
            and on.get("scen_crashed", 0) >= 0.2 * n)


def capture_multirumor(detail: dict, seed: int,
                       n: int | None = None) -> None:
    """Concurrent multi-rumor serving rows (ISSUE 8): a 1M-node R=16
    oneshot broadcast (16 pipelined waves through ONE shared mailbox --
    the marginal cost over the single-rumor row is the serving-workload
    headline) and a 1M-node streaming run (R=64 injected at 100
    rumors/simulated-second -- steady-state rumors/s and deliveries/s).
    CPU hosts run the /100 twins (tests/test_multirumor.py pins the
    small-n semantics)."""
    if n is None:
        n = 1_000_000 if jax.default_backend() == "tpu" else 10_000
    base = Config(n=n, fanout=6, graph="kout", backend="jax", seed=seed,
                  crashrate=0.0, coverage_target=0.95, max_rounds=3000,
                  progress=False).validate()
    for name, cfg in (
        ("multirumor_1m_r16", base.replace(rumors=16)),
        ("stream_1m", base.replace(rumors=64, traffic="stream",
                                   stream_rate=100)),
    ):
        row = pool_retry(_bench_backend, cfg, name=name)
        row["n"] = cfg.n
        detail[name] = row


def capture_pushsum(detail: dict, seed: int, n: int | None = None) -> None:
    """Numeric-gossip row (ISSUE 14): a 1M-node PushSum averaging run to
    the 95% eps-band target -- the wall-clock cost of the sum-combine
    drain and the (dim+1)x4-limb mail payload against the same kout
    overlay the SI headline rides.  CPU hosts run the /100 twin
    (tests/test_pushsum.py pins the small-n semantics and the
    check_bench CPU row pins the exact trajectory)."""
    if n is None:
        n = 1_000_000 if jax.default_backend() == "tpu" else 10_000
    cfg = Config(n=n, fanout=6, graph="kout", backend="jax", seed=seed,
                 model="pushsum", droprate=0.0, crashrate=0.0,
                 coverage_target=0.95, max_rounds=3000,
                 progress=False).validate()
    row = pool_retry(_bench_backend, cfg, name="pushsum_1m")
    row["n"] = cfg.n
    detail["pushsum_1m"] = row


def capture_pushsum_50m(detail: dict, seed: int) -> None:
    """TPU-only 50M numeric-gossip twin pair (ISSUE 14): the sharded
    S=8 PushSum run against its single-chip jax twin at the SAME
    n/graph/seed.  The pair bounds two claims at scale that CPU shims
    cannot: the routed exchange's cost carrying the 12-column int32 mass
    payload (vs SI's 1 id/lane), and the shard-count invariance of the
    trajectory (the two rows must report identical ticks/coverage --
    conservation makes any divergence a bug, not noise)."""
    base = Config(n=50_000_000, fanout=6, graph="kout", seed=seed,
                  model="pushsum", droprate=0.0, crashrate=0.0,
                  coverage_target=0.95, max_rounds=3000,
                  progress=False)
    for name, cfg in (
        ("pushsum_50m_jax", base.replace(backend="jax").validate()),
        ("pushsum_50m_sharded", base.replace(backend="sharded").validate()),
    ):
        detail[name] = pool_retry(_bench_backend, cfg, name=name)
    a, b = detail["pushsum_50m_jax"], detail["pushsum_50m_sharded"]
    if all("skipped" not in r and "error" not in r for r in (a, b)):
        a["acceptance"] = bool(a.get("ticks") == b.get("ticks")
                               and a.get("coverage") == b.get("coverage"))


def capture_spatial_overhead(detail: dict, seed: int,
                             n: int = 1_000_000) -> None:
    """Spatial-telemetry overhead twins (ISSUE 16): the same seeded SI
    run with `-telemetry-spatial` off vs on.  The panels ride the
    existing per-window record as extra row scatters, so the on-run must
    stay within 5% of the off-run's wall clock (the acceptance bound)
    AND trajectory-identical (recording-invisible by construction --
    tests/test_spatial.py pins the byte parity; this row pins the
    cost)."""
    cfg = Config(n=n, fanout=3, graph="kout", backend="jax", seed=seed,
                 crashrate=0.001, coverage_target=0.90, max_rounds=3000,
                 progress=False)
    off = pool_retry(_bench_backend, cfg.validate(), name="spatial_off_1m")
    on = pool_retry(_bench_backend,
                    cfg.replace(telemetry_spatial="on").validate(),
                    name="spatial_on_1m")
    row = {"n": n, "off": off, "on": on}
    if all("skipped" not in r and "error" not in r for r in (off, on)):
        ratio = ((on.get("run_s") or 0.0)
                 / max(off.get("run_s") or 0.0, 1e-9))
        row["overhead_ratio"] = round(ratio, 4)
        row["acceptance"] = bool(
            ratio <= 1.05
            and off.get("ticks") == on.get("ticks")
            and off.get("coverage") == on.get("coverage"))
    detail["spatial_overhead_1m"] = row


def capture_spatial_overhead_50m(detail: dict, seed: int) -> None:
    """TPU-only 50M sharded spatial twins (queued row): same on/off pair
    as spatial_overhead_1m but S=8 over real ICI, where the panels also
    count the traffic matrix inside the routed all_to_all -- the cost
    the 1M single-chip twin cannot see."""
    base = Config(n=50_000_000, fanout=6, graph="kout", backend="sharded",
                  seed=seed, crashrate=0.0, coverage_target=0.95,
                  max_rounds=3000, progress=False)
    for name, cfg in (
        ("spatial_50m_off", base.validate()),
        ("spatial_50m_on",
         base.replace(telemetry_spatial="on").validate()),
    ):
        detail[name] = pool_retry(_bench_backend, cfg, name=name)
    a, b = detail["spatial_50m_off"], detail["spatial_50m_on"]
    if all("skipped" not in r and "error" not in r for r in (a, b)):
        ratio = (b.get("run_s") or 0.0) / max(a.get("run_s") or 0.0, 1e-9)
        detail["spatial_overhead_50m"] = {
            "overhead_ratio": round(ratio, 4),
            "acceptance": bool(ratio <= 1.05
                               and a.get("ticks") == b.get("ticks"))}


def capture_serve_elasticity(detail: dict, seed: int) -> None:
    """Elastic serving row (ISSUE 11): the CI twin shape forced through
    one widen and one narrow, measuring reshard_pause_ms -- the wall-clock
    the service stood still across checkpoint -> rebuild -> restore, the
    SLO cost a future perf round drives down -- with the zero-loss
    invariant (shed == 0, every rumor delivered) asserted in the row
    itself.  Needs >= 2 devices to widen onto; single-device hosts record
    a named skip (CI runs the full twin on the 8-fake-device shim)."""
    devs = len(jax.devices())
    if devs < 2:
        detail["serve_elasticity"] = {
            "skipped": f"needs >= 2 devices to reshard, host has {devs} "
                       "(tier-1 runs the twin on the 8-fake-device shim)"}
        return
    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    import tempfile

    wide = 8 if devs >= 8 else 2
    n = 1_048_576 if jax.default_backend() == "tpu" else 2048
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory() as rd:
            cfg = Config(n=n, graph="kout", fanout=6, seed=seed,
                         crashrate=0.0, droprate=0.0, delaylow=10,
                         delayhigh=11, protocol="si", engine="event",
                         backend="jax", rumors=8, traffic="stream",
                         stream_rate=40, coverage_target=0.99,
                         max_rounds=3000, progress=False, serve=True,
                         serve_force=f"{wide}@4,1@10",
                         run_dir=rd).validate()
            res = run_simulation(cfg, printer=ProgressPrinter(enabled=False))
            with open(os.path.join(rd, "result.json")) as fh:
                payload = json.load(fh)
        row = {"n": n, "wide_shards": wide,
               "converged": res.converged,
               "rumors_done": res.stats.rumors_done,
               "shed": res.stats.shed,
               "resizes": payload["serve"]["resizes"],
               "reshard_pause_ms": payload["reshard_pause_ms"],
               "wall_s": round(time.perf_counter() - t0, 3)}
        if res.stats.shed or res.stats.rumors_done != cfg.rumors:
            row["error"] = "zero-loss reshard invariant violated"
    except Exception as e:  # record, don't kill the bench line
        row = {"error": repr(e)}
    detail["serve_elasticity"] = row


def capture_hostloss_recovery(detail: dict, seed: int) -> None:
    """Host-loss recovery row (ISSUE 20): a supervised run loses a
    worker to the -chaos kill-worker drill mid-stream, restores the last
    sha256-verified snapshot, and replays to convergence -- measuring
    recovery_pause_ms (the wall-clock the service stood still across
    detect -> restore -> reshard, the SLO a future perf round drives
    down) next to the snapshot size that bounds it, with the exactness
    invariant (shed == 0, every rumor delivered) asserted in the row
    itself.  Scale-banded like the suite: 1M nodes on TPU, /100 on CPU
    stand-in hosts."""
    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils import checkpoint
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    import tempfile

    n = 1_048_576 if jax.default_backend() == "tpu" else 10_485
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory() as rd:
            ck = os.path.join(rd, "ckpt")
            cfg = Config(n=n, graph="kout", fanout=6, seed=seed,
                         crashrate=0.0, droprate=0.0, delaylow=10,
                         delayhigh=11, protocol="si", engine="event",
                         backend="jax", rumors=8, traffic="stream",
                         stream_rate=40, coverage_target=0.99,
                         max_rounds=3000, progress=False,
                         supervise=True, workers=2,
                         chaos="kill-worker@1:3", checkpoint_every=2,
                         checkpoint_dir=ck, run_dir=rd).validate()
            res = run_simulation(cfg, printer=ProgressPrinter(enabled=False))
            snap = checkpoint.latest(ck)
            ckpt_bytes = os.path.getsize(snap) if snap else 0
        row = {"n": n, "converged": res.converged,
               "rumors_done": res.stats.rumors_done,
               "shed": res.stats.shed,
               "recovered_windows": res.recovered_windows,
               "recovery_pause_ms": res.recovery_pause_ms,
               "ckpt_bytes": ckpt_bytes,
               "wall_s": round(time.perf_counter() - t0, 3)}
        if res.stats.shed or res.stats.rumors_done != cfg.rumors:
            row["error"] = "exact-recovery invariant violated"
    except Exception as e:  # record, don't kill the bench line
        row = {"error": repr(e)}
    detail["hostloss_recovery"] = row


def capture_hostloss_50m(detail: dict, seed: int) -> None:
    """TPU-only 50M host-loss twin pair: the supervised kill-drill run
    and its undisturbed twin at the SAME n/graph/seed, so the record
    carries recovery_pause_ms against a real-scale snapshot (the CPU
    hostloss_recovery row bounds only the /100 stand-in restore) plus
    the Stats-exactness check at scale."""
    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    import tempfile

    def _pair() -> dict:
        base = Config(n=50_000_000, graph="kout", fanout=6, seed=seed,
                      crashrate=0.0, droprate=0.0, delaylow=10,
                      delayhigh=11, protocol="si", engine="event",
                      backend="jax", rumors=8, traffic="stream",
                      stream_rate=40, coverage_target=0.99,
                      max_rounds=3000, progress=False)
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory() as rd:
            drilled = base.replace(
                supervise=True, workers=2, chaos="kill-worker@1:3",
                checkpoint_every=2,
                checkpoint_dir=os.path.join(rd, "ckpt")).validate()
            res = run_simulation(drilled,
                                 printer=ProgressPrinter(enabled=False))
        drill_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        twin = run_simulation(base.validate(),
                              printer=ProgressPrinter(enabled=False))
        return {"n": base.n,
                "stats_exact": res.stats.to_dict() == twin.stats.to_dict(),
                "recovered_windows": res.recovered_windows,
                "recovery_pause_ms": res.recovery_pause_ms,
                "shed": res.stats.shed,
                "drill_wall_s": round(drill_s, 3),
                "twin_wall_s": round(time.perf_counter() - t1, 3)}

    detail["hostloss_50m_twins"] = pool_retry(_pair,
                                              name="hostloss_50m_twins")


def capture_multirumor_50m(detail: dict, seed: int) -> None:
    """TPU-only 50M twin pair: the single-rumor baseline and the R=16
    concurrent broadcast at the SAME n/graph/seed, so the record carries
    the measured marginal cost of the rumor axis at scale (the bitmask
    word ladder is 1 uint32/node at R<=32; the mail ring widens by W
    payload words).  100M is intentionally NOT attempted: the R=16 mail
    ring's extra word column sits too close to the 16 GB ceiling next to
    the 1e8-node state (the 50M pair plus the 1-chip sharded twins bound
    the projection)."""
    base = Config(n=50_000_000, fanout=6, graph="kout", backend="jax",
                  seed=seed, crashrate=0.0, coverage_target=0.95,
                  max_rounds=3000, progress=False).validate()
    for name, cfg in (("multirumor_50m_r1", base),
                      ("multirumor_50m_r16", base.replace(rumors=16))):
        detail[name] = pool_retry(_bench_backend, cfg, name=name)


def capture_deliver_kernel_twins(detail: dict, seed: int) -> None:
    """-deliver-kernel A/B twins at scale (ISSUE 9): the 50M suite shape,
    its R=16 multi-rumor sibling, and the 100M north-star shape, each run
    with the fused pallas delivery vs the XLA sort/rank/scatter chain it
    replaces at the SAME n/graph/seed.  Interpret-mode CI already pins
    bit-identical trajectories (tests/test_pallas_deliver.py), so these
    rows exist to record the measured wall-clock delta on real hardware;
    an unreachable device leaves dated skip records that re-queue the
    measurement for the next TPU pass."""
    base = Config(n=50_000_000, fanout=6, graph="kout", backend="jax",
                  seed=seed, crashrate=0.0, coverage_target=0.95,
                  max_rounds=3000, progress=False).validate()
    star = Config(n=100_000_000, fanout=6, graph="kout", backend="jax",
                  seed=seed, crashrate=0.0, coverage_target=0.99,
                  max_rounds=3000, pallas=True, progress=False).validate()
    for name, cfg in (("deliver_50m", base),
                      ("deliver_50m_r16", base.replace(rumors=16)),
                      ("deliver_100m_99pct", star)):
        for kern in ("xla", "pallas"):
            row = pool_retry(
                _bench_backend,
                cfg.replace(deliver_kernel=kern).validate(),
                name=f"{name}_{kern}")
            detail[f"{name}_{kern}"] = row


def capture_exchange_pipeline_twins(detail: dict, seed: int) -> None:
    """-exchange-pipeline A/B twins at scale (ISSUE 13): the 50M suite
    shape on the sharded backend (S = all attached chips), run with the
    double-buffered exchange schedule vs the serial route->drain it
    overlaps, at the SAME n/graph/seed.  CI already pins the two gates
    bit-identical in trajectory (tests/test_sharded.py PRE_PIPELINE_FP),
    so these rows exist to record the measured overlap win on real ICI
    -- the CPU fake-device mesh routes over host loopback, where the
    collective has nothing to hide behind; an unreachable device
    leaves dated skip records that re-queue the pair."""
    base = Config(n=50_000_000, fanout=6, graph="kout", backend="sharded",
                  seed=seed, crashrate=0.0, coverage_target=0.99,
                  max_rounds=3000, progress=False).validate()
    for gate in ("off", "double"):
        row = pool_retry(
            _bench_backend,
            base.replace(exchange_pipeline=gate).validate(),
            name=f"exchange_pipeline_50m_{gate}")
        detail[f"exchange_pipeline_50m_{gate}"] = row


def capture_autotune(detail: dict, seed: int) -> None:
    """TPU chunk-ladder autotune sweep at the 50M and 100M bands
    (ISSUE 12): scripts/autotune.py's coordinate sweep through THIS
    module's warm+timed protocol, neutrality-gated against the
    default-constants twin, winners persisted per (platform, device_kind,
    scale band) into the committed TUNING_TABLE.json.  Each candidate
    already rides pool_retry inside sweep_space, so a mid-sweep pool
    fault costs candidates, not the record; a fault before the baseline
    lands here as the usual dated skip."""
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "autotune", os.path.join(here, "scripts", "autotune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from gossip_simulator_tpu import tuning

    for name, n in (("autotune_sweep_50m", 50_000_000),
                    ("autotune_sweep_100m", 100_000_000)):
        def _sweep(n=n):
            return mod.sweep_space("chunk_ladder", n, seed=seed,
                                   table_file=tuning.COMMITTED_TABLE)
        detail[name] = pool_retry(_sweep, name=name)


def capture_100m(detail: dict, seed: int, headline_n: int) -> None:
    """The 100M single-chip rows (BASELINE.md north-star scale), captured in
    the driver-recorded bench output rather than only in the README.
    fanout 3 / coverage 0.90 is the throughput row; fanout 6 / coverage 0.99
    is the NORTH-STAR measurement (time-to-99% at 100M -- BASELINE.md's
    target metric).  Called LAST in the record: these runs sit closest to
    the 16 GB HBM ceiling, and a TPU worker fault here (observed r2 before
    the transient-peak fixes) must not take the already-measured headline,
    suite and Pallas validation down with it."""
    base = Config(n=100_000_000, fanout=3, graph="kout", backend="jax",
                  seed=seed, crashrate=0.001, coverage_target=0.90,
                  max_rounds=3000, pallas=True, progress=False).validate()
    if headline_n == base.n:
        # `--n 100000000 --full`: the headline already measured exactly
        # this config -- don't run the near-ceiling scale a third time.
        detail["jax_100m"] = detail["jax"]
    else:
        detail["jax_100m"] = pool_retry(_bench_jax, base, name="jax_100m")
    # NORTH-STAR row: crashrate 0.0 from round 5 on -- the reference's own
    # default crashrate 0.001 IS 0 under its 1%-resolution Bernoulli
    # (simulator.go:180), and crash_p == 0 is the soundness gate for
    # duplicate suppression (config.dup_suppress).  Round <= 4 rows ran
    # the exact-float 0.001 (35.4s at r4; the crashrate change itself is
    # ~0.1s -- the off-twin below isolates the suppression effect).
    star = base.replace(fanout=6, coverage_target=0.99,
                        crashrate=0.0).validate()
    detail["jax_100m_99pct"] = pool_retry(_bench_jax, star,
                                          name="jax_100m_99pct")
    # A/B twin: identical physics with suppression forced off (same
    # per-window observables by construction; see the dup-suppress
    # tests) -- records the suppression speedup in the driver record.
    detail["jax_100m_99pct_nosuppress"] = pool_retry(
        _bench_backend, star.replace(dup_suppress="off").validate(),
        name="jax_100m_99pct_nosuppress")


def _pallas_validation() -> dict:
    """Run scripts/validate_pallas_tpu.py's checks in-process (a subprocess
    would open a second TPU client while this one is live -- concurrent
    clients can crash the worker) and write the artifact."""
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        spec = importlib.util.spec_from_file_location(
            "validate_pallas_tpu",
            os.path.join(here, "scripts", "validate_pallas_tpu.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        result = mod.run_checks()
        result["deliver_tpu"] = mod.run_deliver_checks()
        # Merge, don't overwrite: the artifact also carries the CPU
        # --interpret deliver verdict from CI hosts.
        mod._merge_out(os.path.join(here, "PALLAS_VALIDATION.json"), result)
        return result
    except Exception as e:  # record, don't kill the bench line
        return {"error": repr(e)}


def _bench_overlay(cfg: Config) -> dict:
    """Phase-1 (overlay construction) timing: windows to quiescence, wall
    clock, and the stabilization clock in simulated ms.  Runs twice -- the
    first pass eats compile (the nested dynamic loops are minutes cold;
    the persistent cache makes reruns cheap) and the second is the
    reported number."""
    out: dict = {"n": cfg.n, "overlay_mode": cfg.overlay_mode}
    for attempt in ("warm", "timed"):
        s = JaxStepper(cfg)
        t0 = time.perf_counter()
        s.init()
        # The quiet-run fast path (bounded device-side while_loop; what a
        # quiet CLI run and the driver's bench invocation actually pay).
        windows, q = s.overlay_run_to_quiescence(20_000)
        out.update(windows=windows, quiesced=bool(q),
                   stabilize_sim_ms=s.sim_time_ms())
        out[f"wall_s_{attempt}"] = round(time.perf_counter() - t0, 3)
    return out


def full_suite(seed: int) -> list[dict]:
    """BASELINE.json configs 1-4 plus two overlay phase-1 timing rows
    (default rounds mode and the tick-faithful engine), on
    this host's devices.  Config 5 (100M sharded on v5e-8) needs an 8-chip
    slice; run it via `-backend sharded` on such a host -- see
    tests/test_sharded.py for the 8-fake-device CPU rehearsal."""
    on_tpu = jax.default_backend() == "tpu"
    scale = 1 if on_tpu else 100  # shrink on CPU hosts
    runs = [
        # fanout=1 per BASELINE config 1: the wave follows single-successor
        # chains, so the default 10% drop kills it after ~10 hops --
        # converged=False with ~0.2% coverage IS the correct outcome (the
        # reference would spin forever here, SURVEY §5.3a).
        ("si_1k_fanout1", Config(n=1000, fanout=1, graph="kout",
                                 backend="native", seed=seed, progress=False,
                                 max_rounds=20000)),
        # coverage 0.90: fanout 3 / drop 0.1 asymptotes at ~93% (headline
        # rationale above).
        ("si_1m_fanout3", Config(n=1_000_000 // scale, fanout=3, graph="kout",
                                 backend="jax", seed=seed, pallas=on_tpu,
                                 coverage_target=0.90, max_rounds=3000,
                                 progress=False)),
        # Anti-entropy gossips with fresh random peers each round; the
        # static graph is irrelevant, so skip the overlay build phase.
        ("pushpull_10m_logn", Config(n=10_000_000 // scale,
                                     fanout=23, protocol="pushpull",
                                     graph="kout", backend="jax", seed=seed,
                                     progress=False)),
        # Auto resolves SIR to the event engine since round 5 (the ring
        # engine paid O(n) per tick: 41.9s vs ~5s here).
        ("sir_10m_erdos", Config(n=10_000_000 // scale, fanout=8,
                                 graph="erdos", protocol="sir",
                                 removal_rate=0.2, backend="jax", seed=seed,
                                 pallas=on_tpu, coverage_target=0.8,
                                 progress=False)),
    ]
    out = []
    for name, cfg in runs:
        t0 = time.perf_counter()
        try:
            cfg = cfg.validate()
            with _named_row(name):
                if cfg.backend == "jax":
                    r = _bench_jax(cfg)
                else:
                    r = _bench_oracle(cfg, budget_s=60.0)
        except Exception as e:  # record, don't kill the suite
            r = {"error": repr(e)}
        r["config"] = name
        if name == "si_1k_fanout1":
            # Self-describing record (VERDICT r5 #7b): the die-out is the
            # measurement, not a failure -- nobody should re-read it as a
            # broken row.
            r["note"] = ("expected die-out: fanout-1 chains + 10% drop "
                         "kill the wave after ~10 hops; converged=False "
                         "with ~0.2% coverage IS the correct physics "
                         "(the reference would poll forever here, "
                         "SURVEY 5.3a)")
        r["wall_s"] = round(time.perf_counter() - t0, 3)
        out.append(r)
    # Overlay phase-1 timing rows (the reference's "Constructing Overlay"
    # phase, simulator.go:219-235): 1M nodes single-chip, default rounds
    # mode AND the tick-faithful engine (per-message delays, the
    # reference's true stabilization clock -- `-overlay-mode ticks`).
    for name, on, mode in (("overlay_1m_phase1", 1_000_000, "rounds"),
                           ("overlay_1m_ticks", 1_000_000, "ticks"),
                           # Round 7 (VERDICT r5 #3): the raised
                           # OVERLAY_TICKS_AUTO_MAX band's anchor row --
                           # 10M true-per-message-clock construction,
                           # justified against overlay_10m_phase1's
                           # rounds-mode cost (<= 2x budget; README
                           # "Overlay mode at scale").
                           ("overlay_10m_phase1", 10_000_000, "rounds"),
                           ("overlay_10m_ticks", 10_000_000, "ticks")):
        try:
            ocfg = Config(n=on // scale, graph="overlay",
                          overlay_mode=mode, backend="jax",
                          seed=seed, progress=False).validate()
            r = _bench_overlay(ocfg)
        except Exception as e:
            r = {"error": repr(e)}
        r["config"] = name
        out.append(r)
    return out


def cpu_scale_rows(seed: int) -> list[tuple[str, Config]]:
    """The deterministic CPU-scale capture set behind
    scripts/check_bench.py: small shapes whose trajectory-derived fields
    (ticks, coverage, total_message, windows, mail high-water, rumors
    done) are exact functions of (code, seed) on any host -- a changed
    value IS a changed trajectory, caught without TPU hardware.  Spans
    the engine surface: event SI, ring SIR via erdos, multi-rumor
    oneshot, streaming injection, and PushSum numeric gossip."""
    return [
        ("cpu_si_event_10k", Config(
            n=10_000, graph="kout", fanout=6, seed=seed, crashrate=0.01,
            coverage_target=0.95, backend="jax", progress=False,
            max_rounds=3000)),
        ("cpu_sir_erdos_10k", Config(
            n=10_000, graph="erdos", fanout=8, protocol="sir",
            removal_rate=0.2, seed=seed, backend="jax",
            coverage_target=0.8, progress=False, max_rounds=3000)),
        ("cpu_multirumor_10k_r16", Config(
            n=10_000, graph="kout", fanout=6, rumors=16, seed=seed,
            crashrate=0.0, coverage_target=0.95, backend="jax",
            progress=False, max_rounds=3000)),
        ("cpu_stream_10k", Config(
            n=10_000, graph="kout", fanout=6, rumors=8, traffic="stream",
            stream_rate=50, seed=seed, crashrate=0.0,
            coverage_target=0.95, backend="jax", progress=False,
            max_rounds=3000)),
        ("cpu_pushsum_10k", Config(
            n=10_000, graph="kout", fanout=6, model="pushsum", seed=seed,
            droprate=0.0, crashrate=0.0, coverage_target=0.95,
            backend="jax", progress=False, max_rounds=3000)),
    ]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="force the full record (suite + 100M + Pallas "
                         "validation) even with an explicit --n")
    ap.add_argument("--run-dir", default="",
                    help="write one run artifact per measured row under "
                         "this directory (utils/artifact.py layout) plus "
                         "a bench_trace.json span timeline")
    ap.add_argument("--queued", action="store_true",
                    help="print the generated QUEUED hardware-rows "
                         "section and exit")
    ap.add_argument("--write-queued", action="store_true",
                    help="regenerate the README's QUEUED section in "
                         "place and exit (0 = already current)")
    args = ap.parse_args()
    here_ = os.path.dirname(os.path.abspath(__file__))
    if args.queued:
        print(queued_section())
        return 0
    if args.write_queued:
        changed = write_queued_section(os.path.join(here_, "README.md"))
        print("README QUEUED section "
              + ("updated" if changed else "already current"))
        return 1 if changed else 0
    global _RUN_DIR_ROOT
    tracer = None
    if args.run_dir:
        _RUN_DIR_ROOT = os.path.abspath(args.run_dir)
        os.makedirs(_RUN_DIR_ROOT, exist_ok=True)
        tracer = _trace.activate(_trace.Tracer(
            path=os.path.join(_RUN_DIR_ROOT, "bench_trace.json")))
    # The driver invokes plain `python bench.py`: the default invocation IS
    # the full record (BASELINE suite + Pallas validation + 100M rows).
    # An explicit --n is a smoke run and skips all of it unless --full.
    # Record order = risk order: headline, suite, Pallas validation, and
    # the near-HBM-ceiling 100M rows last (see capture_100m).
    full = args.full or args.n is None
    result = headline(args.n, args.seed)
    if full:
        result["detail"]["suite"] = full_suite(args.seed)
        # Coverage-under-churn heal twins (ISSUE 5 acceptance rows):
        # scale-banded like the suite (1M on TPU, /100 on CPU hosts).
        capture_churn_healing(result["detail"], args.seed)
        # Multi-rumor serving rows (ISSUE 8): 1M R=16 oneshot + streaming
        # injection, scale-banded the same way.
        capture_multirumor(result["detail"], args.seed)
        # Numeric-gossip row (ISSUE 14): 1M PushSum averaging to the
        # eps-band target, scale-banded the same way.
        capture_pushsum(result["detail"], args.seed)
        # Elastic serving row (ISSUE 11): forced widen+narrow reshard
        # pause + zero-loss invariant (skipped on single-device hosts).
        capture_serve_elasticity(result["detail"], args.seed)
        # Host-loss recovery row (ISSUE 20): supervised kill drill,
        # recovery pause vs snapshot size, exactness invariant.
        capture_hostloss_recovery(result["detail"], args.seed)
        # Spatial-telemetry on/off twins (ISSUE 16): panels must cost
        # <= 5% wall clock and leave the trajectory untouched.
        capture_spatial_overhead(result["detail"], args.seed)
        if jax.default_backend() == "tpu":
            # Distributional validation of the Pallas generators on real
            # hardware (interpret-mode CI can only check structure); also
            # refreshes the PALLAS_VALIDATION.json artifact.
            result["detail"]["pallas_validation"] = _pallas_validation()
            # Salvage artifact: a hard TPU worker fault in the 100M rows
            # kills the process before the stdout JSON line prints; the
            # already-measured headline + suite + validation survive here.
            here = os.path.dirname(os.path.abspath(__file__))
            partial = os.path.join(here, "BENCH_PARTIAL.json")
            with open(partial, "w") as fh:
                json.dump(result, fh)
            capture_sharded_1chip(result["detail"], args.seed)
            capture_exchange_profile(result["detail"])
            capture_overlay_profile(result["detail"])
            capture_scale50(result["detail"], args.seed)
            # 50M single- vs multi-rumor twins: the measured marginal
            # cost of the rumor axis at scale (ISSUE 8).
            capture_multirumor_50m(result["detail"], args.seed)
            # 50M supervised kill-drill vs undisturbed twin (ISSUE 20):
            # recovery pause against a real-scale snapshot.
            capture_hostloss_50m(result["detail"], args.seed)
            # 50M PushSum sharded-vs-jax twins (ISSUE 14): mass-payload
            # exchange cost + shard-invariance at scale.
            capture_pushsum_50m(result["detail"], args.seed)
            # 50M sharded spatial on/off twins (ISSUE 16): the traffic
            # matrix's recording cost over real ICI.
            capture_spatial_overhead_50m(result["detail"], args.seed)
            # -deliver-kernel fused-vs-XLA wall-clock twins at 50M/100M
            # (ISSUE 9; dated skips re-queue when the pool is down).
            capture_deliver_kernel_twins(result["detail"], args.seed)
            # 50M sharded exchange-pipeline double-vs-off twins
            # (ISSUE 13): the overlap win needs real ICI to show.
            capture_exchange_pipeline_twins(result["detail"], args.seed)
            # Chunk-ladder autotune sweep at the 50M/100M bands
            # (ISSUE 12): winners land in TUNING_TABLE.json.
            capture_autotune(result["detail"], args.seed)
            # Refresh the salvage so a worker fault in the near-ceiling
            # 100M rows can't discard the just-measured sharded twins.
            with open(partial, "w") as fh:
                json.dump(result, fh)
            capture_100m(result["detail"], args.seed,
                         result["detail"]["jax"]["n"])
            with open(partial, "w") as fh:
                json.dump(result, fh)
            # The ~10+ minute two-phase build runs LAST: everything else
            # is already salvaged if it faults.
            capture_100m_two_phase(result["detail"], args.seed)
            # The run completed: drop the salvage file so a stale partial
            # can't masquerade as a later run's salvage.
            os.unlink(partial)
    # The FULL record goes to bench_out.json; stdout ends with exactly ONE
    # compact JSON line so the driver's tail capture always parses
    # (VERDICT r4 #8: the old full-record line overflowed the captured
    # tail and recorded "parsed": null).  The compact line carries the
    # headline metric plus the north-star scalars.
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_out.json"), "w") as fh:
        json.dump(result, fh, indent=1)
    if tracer is not None:
        tracer.write(metadata={"kind": "bench", "seed": args.seed})
        _trace.deactivate()
    line = {k: v for k, v in result.items() if k != "detail"}
    d = result["detail"]
    for row in ("jax_100m_99pct", "jax_100m_99pct_nosuppress", "jax_100m",
                "two_phase_100m", "two_phase_100m_pre"):
        if row in d and "error" not in d[row]:
            line[row + "_s"] = round(
                d[row].get("run_s", d[row].get("wall_s", 0.0)) or 0.0, 2)
    line["detail_file"] = "bench_out.json"
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
