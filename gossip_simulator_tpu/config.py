"""Typed configuration for the TPU-native gossip simulator.

Mirrors the reference CLI flag-for-flag (reference: simulator.go:186-205) and adds
the knobs the TPU framework needs (`backend`, `protocol`, `graph`, `seed`,
`max_rounds`, ...).  Documented divergences from the reference:

* ``fanin`` defaults to *resolved* ``fanout + 1``.  The reference evaluates
  ``Fanout+1`` at flag-registration time, so its fanin default is the constant 6
  regardless of ``-fanout`` (simulator.go:189).  ``compat_reference=True``
  restores the constant-6 behaviour.
* Drop/crash probabilities are exact float Bernoulli draws.  The reference
  truncates to 1% resolution via ``rand.Intn(100) < int(rate*100)``
  (simulator.go:172,180), so its default ``crashrate=0.001`` can never crash.
  ``compat_reference=True`` restores the truncation.
* ``DelayHigh <= DelayLow`` is a validation error here; the reference panics in
  ``rand.Intn`` (simulator.go:167).
* ``max_rounds`` bounds the epidemic phase; the reference spins forever if 99%
  is unreachable (simulator.go:243-251).
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import os
from typing import Optional


def er_cap(lam: float) -> int:
    """Erdős–Rényi friends-column capacity: covers the Poisson(lam) upper
    tail to ~6 sigma (overflow is clipped in degree, probability ~1e-9 per
    node at lam <= 32).  The single source of truth for every ER generator
    (models/graphs.erdos, ops/pallas_graph.erdos_pallas)."""
    return max(1, int(math.ceil(lam + 6.0 * math.sqrt(max(lam, 1.0)) + 4)))

BACKENDS = ("native", "cpp", "jax", "sharded")
PROTOCOLS = ("si", "pushpull", "sir")
GRAPHS = ("overlay", "kout", "erdos", "ring")
TIME_MODES = ("ticks", "rounds")
ENGINES = ("auto", "ring", "event")
# overlay_mode="auto" picks the tick-faithful phase-1 engine up to this n.
# Round 7 raised the band 1M -> 10M: the prefix-dense drain delivery
# (ops.mailbox deliver_pair prefix path -- the drained ring's live
# entries are a sorted PREFIX, so the per-chunk compaction scans that
# dominated the 10M chunk sweep are pure waste) brought the 10M ticks
# build inside the <=2x-rounds-mode budget the fidelity default demands
# (README "Overlay mode at scale"; scripts/profile_overlay.py measures
# the per-chunk scan/sort/scatter constants the raise cites).  Above
# 10M the estimated clock (within ~1 window of true at 1M/10M, r3)
# remains the default and the driver prints the notice.
OVERLAY_TICKS_AUTO_MAX = 10_000_000
# overlay_static_boot="auto" band: at and above this many rows the
# single-device ROUNDS overlay draws the whole initial friends table and
# emits the n*fanout makeup burst at round 0 (the way overlay_ticks.
# init_state always has -- the reference's needNewFriend loop re-arms
# with no delay, simulator.go:103-105, so a node fills all fanout slots
# at t~0 and, once at fanout, can never drop below it again).  A
# deterministic re-choice of the bootstrap schedule, same as the column
# band's arrival-order re-choice: every n below the band is bit-identical
# to round 6; the band sits at the split-round boundary
# (overlay.SPLIT_ROUND_MIN_ROWS) where the staggered per-round burst was
# the measured dominant phase-1 cost.  Module-level so CPU tests can
# lower it and pin both trajectories.
OVERLAY_STATIC_BOOT_MIN_ROWS = 32_000_000
# The auto mailbox cap drops 16 -> 8 at this many local rows (see
# Config.mailbox_cap_for: emission-buffer memory, not overflow risk,
# is what the cap costs at scale).
MAILBOX_CAP_MEMORY_BAND = 32_000_000


@dataclasses.dataclass(frozen=True)
class Config:
    """Full simulation configuration.

    The first seven fields correspond 1:1 to the reference flags
    (simulator.go:187-193, defaults preserved).
    """

    n: int = 50_000
    fanout: int = 5
    fanin: int = -1  # -1 -> resolved fanout + 1 (see module docstring)
    delaylow: int = 10  # ms (one simulated tick == 1 ms)
    delayhigh: int = 20  # ms, exclusive upper bound like rand.Intn
    droprate: float = 0.1
    crashrate: float = 0.001

    # --- framework extensions -------------------------------------------------
    backend: str = "jax"
    protocol: str = "si"
    graph: str = "overlay"
    seed: int = 0
    max_rounds: int = 100_000
    coverage_target: float = 0.99  # reference stops at >=99% (simulator.go:248)
    # "ticks": 1 round == 1 simulated ms; messages carry uniform[delaylow,
    # delayhigh) delivery delays through a ring buffer (faithful to the
    # reference's time-to-99% semantics).  "rounds": synchronous rounds, one
    # hop per round (classic epidemic-rounds accounting; faster).
    time_mode: str = "ticks"
    # SIR removal probability (config 4 in BASELINE.json); ignored otherwise.
    removal_rate: float = 0.1
    # Erdos-Renyi edge probability; -1 -> fanout/n (expected degree == fanout).
    er_p: float = -1.0
    # Reproduce reference quirks (1%-resolution Bernoulli, constant fanin
    # default, seed node never counted as received: simulator.go:240-241).
    compat_reference: bool = False
    # Mailbox / exchange capacities (see ops/mailbox.py).  -1 -> auto.
    mailbox_cap: int = -1
    # Use the Pallas TPU-PRNG graph generator (ops/pallas_graph.py) for the
    # kout graph: same distribution, different stream, much faster at 100M.
    pallas: bool = False
    # Wavefront compaction: gather only actual senders' edges before the
    # scatter/route (identical results while no exchange overflow occurs --
    # overflow is counted, never silent; big win in ticks mode where the
    # per-tick wave is a small fraction of n).  "auto" = on for ticks mode.
    compact: str = "auto"
    # Compaction chunk size override (-1 = auto: n_local//128, min 4096; see
    # epidemic.compact_chunk_cap).  Exposed mainly so tests can force the
    # multi-chunk path at small n.
    compact_chunk: int = -1
    # Epidemic engine (jax + sharded backends): "ring" keeps per-(slot,
    # node) arrival counts (O(n) per tick); "event" keeps per-slot message
    # id-lists (O(arrivals) per tick -- models/event.py and
    # parallel/event_sharded.py).  "auto" = event for SI and (round 5)
    # SIR in ticks mode on the jax/sharded backends (unless compact is
    # explicitly set, a ring-engine request), ring otherwise.
    engine: str = "auto"
    # Event engine per-WINDOW-slot message capacity (-1 = auto: see
    # event.slot_cap -- 1.5*n*mean_degree*B/delay_span, bounded by the SI
    # message total and int32 flat addressing; overflow is counted in
    # Stats.mailbox_dropped, never silent).
    event_slot_cap: int = -1
    # Event engine drain chunk size (-1 = auto: 524288; see
    # event.drain_chunk).
    event_chunk: int = -1
    # Guaranteed-duplicate suppression at append (event engine): an edge
    # whose destination already has the received bit -- monotone, so it is
    # STILL set at delivery -- can only increment total_message there
    # (simulator.go:111,117-119); with an effective crash rate of 0 there
    # is not even a crash draw.  Suppression never writes such edges into
    # the mail ring (~4.8x of endgame traffic at fanout 6); their counts
    # are BANKED per arrival window in EventState.sup_cnt at append time
    # and credited into total_message when that window drains -- the
    # exact step their deliveries would have counted -- so every
    # per-window observable (stdout, JSONL, death tick), not just the
    # final totals, is bit-identical to the unsuppressed path
    # (A/B-tested).  On the sharded backend the filter runs pre-exchange
    # for locally-owned destinations and on the receiving shard for
    # routed ones (parallel/event_sharded._route_and_append), with the
    # same deferred crediting.  "auto" = on iff the EFFECTIVE crash rate
    # is 0: that is
    # crashrate 0, or any crashrate < 0.01 under -compat-reference
    # (whose 1%-resolution Bernoulli truncates the reference's own
    # 0.001 default to 0, simulator.go:180).  Plain crashrate 0.001
    # WITHOUT compat is an exact-float 0.1% crash rate here and keeps
    # suppression off -- pass -crashrate 0 (or -compat-reference) to
    # engage it.  "on" errors when crash_p > 0: per-reception crash
    # draws are keyed by mailbox position, so removing entries would
    # shift every later draw.
    dup_suppress: str = "auto"
    # Phase-1 overlay timing (graph=overlay): "rounds" batches membership
    # into synchronous rounds, delivering every emission exactly one round
    # later and ESTIMATING stabilization time as rounds x mean_delay;
    # "ticks" keeps the reference's per-message uniform delays through a
    # packed window-slot ring (models/overlay_ticks.py, sharded variant
    # parallel/overlay_ticks_sharded.py) so the stabilization clock is
    # true simulated ms (simulator.go:151-168).  "auto" (default)
    # size-bands: ticks at n <= 1e6 -- the reference's default n=50000
    # lands there and the faithful engine costs little at that scale --
    # rounds above, where ticks costs 3-4x more and the estimated clock
    # measured within ~1 window of true (r3: 380 true vs 390 estimated ms
    # at 1M, 400 vs 405 at 10M); a one-line notice marks the estimate.
    # native/cpp are inherently faithful (discrete-event) and ignore the
    # flag.
    overlay_mode: str = "auto"
    # --- phase-1 speed-round gates (round 7; rounds overlay engine) ----------
    # Occupancy-adaptive hosted-delivery chunk schedule (split-round band
    # only): the hosted column delivery picks its per-row chunk width from
    # a ladder sized to the row's live emission count -- one narrow chunk
    # for settled rows, few fat chunks for the dense burst rows whose
    # per-chunk flat-scatter floors dominated the 100M build (chunking is
    # trajectory-neutral: ascending ranges + rank continuation are
    # bit-identical at ANY chunk, ops/mailbox.deliver).  "auto" = on.
    overlay_adaptive_chunks: str = "auto"
    # Dead-emission-row skip (split-round band only): the round pieces
    # count each emission slot's entries AT EMISSION TIME (a scalar per
    # processed slot) and the hosted delivery skips zero rows without the
    # n-wide popcount each row otherwise costs -- once membership settles,
    # ~16 of 17 rows are dead every round at 100M.  The same counts feed a
    # scalar quiescence check, replacing the per-round (cap, n) emission-
    # mask reductions.  Trajectory-neutral (the counts equal the masks'
    # sums exactly; A/B-pinned).  "auto" = on.
    overlay_dead_skip: str = "auto"
    # One-shot static bootstrap (see OVERLAY_STATIC_BOOT_MIN_ROWS): "auto"
    # size-bands (on at >= the band, off below -- bit-identical to round 6
    # below it); "on"/"off" force either schedule at any n.  Changes the
    # membership trajectory above the band (a deterministic re-choice of
    # the bootstrap schedule, strictly CLOSER to the reference's burst);
    # "off" reproduces the pre-round-7 staggered schedule exactly.
    overlay_static_boot: str = "auto"
    # Delivery kernel for the mailbox sort/rank/scatter chain (ROADMAP
    # item 5): "pallas" runs the fused single-pass kernels
    # (ops/pallas_deliver -- natively on TPU, interpret mode elsewhere;
    # bit-identical mailboxes/counts/drops, A/B-pinned by trajectory
    # fingerprints); "xla" is the recorded sort + segment-rank + scatter
    # chain and reproduces every prior trajectory bit-for-bit; "auto" is
    # xla on every platform (Config._kernel_gate).
    deliver_kernel: str = "auto"
    # Exchange pipelining for the sharded backend (ROADMAP item 1):
    # "double" software-pipelines the per-chunk all_to_all at chunk
    # granularity -- the ring_append drain of batch j is deferred one
    # batch behind the route, so XLA's async collective scheduler can
    # hoist batch j+1's all_to_all dispatch above batch j's drain.
    # Trajectory-preserving by construction (the dup verdict is still
    # computed at the serial program point; only the append is staged,
    # and in-window appends always target later windows), so "double"
    # is bit-identical to "off".  "off" runs the serial route->drain
    # chunk loop and reproduces pre-pipeline trajectories bit-for-bit;
    # "auto" picks double on multi-device meshes and off elsewhere
    # (S=1 skips the collective entirely, nothing to overlap).
    exchange_pipeline: str = "auto"
    # --- flight recorder (utils/trace.py, utils/artifact.py) -----------------
    # Host-side span trace (phase-1 rounds, phase-2 windows and bounded
    # calls, checkpoint save/load, sharded exchange) as Chrome trace-event
    # JSON to this path, on the profiler's clock.  Pure host-side
    # observability: the traced jitted programs are unchanged, so
    # trajectories stay bit-identical.
    trace: str = ""
    # jax.profiler device profile (xplane) wrapping the whole run: every
    # span is a TraceAnnotation whenever a profiler runs, and the ring
    # engine's ops carry named scopes, so device and host timelines line
    # up with the -trace spans.
    xprof_dir: str = ""
    # Write a self-describing run artifact here: config snapshot +
    # resolved gates, platform/env fingerprint, JSONL metrics, telemetry
    # histories (npz), trace file, final Stats and the trajectory
    # fingerprint.  scripts/compare_runs.py diffs two of these.
    run_dir: str = ""
    # Append structured JSONL records (params, per-window progress, totals,
    # wall-clock) to this path, alongside the reference-format stdout.
    log_jsonl: str = ""
    # Checkpoint every k rounds to this directory (0 = off).
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
    # Resume the epidemic phase from the latest snapshot in checkpoint_dir
    # (jax backend; skips overlay construction and seeding).
    resume: bool = False
    progress: bool = True  # print reference-format progress lines
    # Multi-host SPMD (backend=sharded): every participating process runs
    # the same CLI with its own -process-id; jax.distributed wires them
    # into one global device mesh (ICI within a slice, DCN across), the
    # node axis shards over ALL processes' devices, and only process 0
    # prints.  Empty coordinator/counts fall back to jax's automatic
    # detection (TPU pod environments set them via the runtime).
    distributed: bool = False
    coordinator: str = ""  # e.g. "host0:1234"
    num_processes: int = -1  # -1 = auto-detect
    process_id: int = -1  # -1 = auto-detect
    # --- host-loss supervision (ISSUE 20; gossip_simulator_tpu/distributed/) --
    # -supervise turns host death into a recoverable event.  Without
    # -coordinator it is the single-process drillable mode: the live mesh's
    # devices are partitioned into -workers logical workers, heartbeat
    # beacons are stamped per window, and a detected loss tears the state
    # down and restores the last provenance-checked snapshot onto the
    # survivors (distributed/supervisor.py run_supervised).  With
    # -coordinator it is the real flavor: the supervisor process spawns
    # -workers CLI worker processes joined via jax.distributed, monitors
    # exits + wall-clock beacon staleness, and relaunches survivors with
    # -resume.  Requires checkpointing (recovery restores the last atomic
    # snapshot).
    supervise: bool = False
    workers: int = 2
    # Liveness beacon directory ("" = <checkpoint_dir>/heartbeats).  Each
    # worker stamps worker_<rank>.json atomically once per poll window.
    heartbeat_dir: str = ""
    # Loss verdict threshold: a worker whose beacon lags this far behind
    # (wall-clock in the real supervisor; the WINDOW_MS-scaled window lag
    # in the drillable mode) is declared lost.
    heartbeat_timeout_ms: int = 30000
    # Injected host-loss drill: "kill-worker@W[:K]" / "stall-worker@W[:K]"
    # kills (or silences the beacon of) worker W at gossip window K
    # (default 6).  Requires -supervise; parse_chaos() below.
    chaos: str = ""
    # Recovery staleness gate: refuse to restore a snapshot more than this
    # many windows behind the loss point (0 = no limit).  See
    # utils/checkpoint.py verify_provenance.
    recover_max_stale: int = 0
    # Provenance token stamped into snapshot sidecars; recovery refuses a
    # snapshot from a different run.  "" = generate one per run (the
    # supervisor passes its own to every worker so relaunched survivors
    # adopt the original run's snapshots).
    run_id: str = ""
    # Bounded jax.distributed.initialize: per-attempt timeout in seconds
    # (3 exponential-backoff attempts; parallel/mesh.py bounded_initialize).
    init_timeout_s: int = 60
    # Device-resident per-window telemetry (utils/telemetry.py): the fast-
    # path while_loops record the full per-window trajectory on device and
    # the driver replays it through the printer afterward -- so a progress-
    # printing or JSONL-logging run takes the fast path whenever
    # checkpointing is off.  "off" restores the old gating (observing runs
    # pay the windowed host loop).  jax/sharded backends only; the
    # discrete-event oracles have no device loop to instrument.
    telemetry: str = "on"
    # Spatial telemetry panels (utils/telemetry.py spatial_*): per-group /
    # per-shard per-window panels plus the exchange traffic matrix,
    # recorded on device next to the scalar history and fetched in the
    # same single transfer.  npz-only (run dirs / utils/health.py): the
    # stdout/JSONL surface is byte-identical on and off, and "off" traces
    # the pre-spatial program (trajectory pins in tests/test_spatial.py).
    # Requires -telemetry on; jax/sharded backends only.
    telemetry_spatial: str = "off"
    # --- fault-injection scenario (gossip_simulator_tpu/scenario.py) --------
    # "off" (default: traced programs identical to a scenario-less build),
    # a path to a JSON timeline, or the JSON inline.  Schedules crash
    # waves, steady churn, node recovery after `downtime` ms, and
    # partition masks over simulated time; jax/sharded backends, SI/SIR
    # ticks-and-rounds epidemics.  Draws are (window, GLOBAL-id)-keyed so
    # trajectories are shard-count invariant and survive reshard-resume.
    scenario: str = "off"
    # Overlay self-healing during phase 2: every poll window, live nodes
    # replace friends that have been dead >= heal_detect_ms (the windowed
    # failed-delivery detection -- a dead friend black-holes every send,
    # so detect_ms models how long the sender's delivery accounting takes
    # to condemn it) with a fresh uniform peer, re-entering the phase-1
    # makeup draw (overlay.heal_dead_friends); infected healers re-send
    # the rumor over the repaired edge (the rejoin anti-entropy that lets
    # recovered nodes catch up).  Works on any friends-table graph.
    overlay_heal: str = "off"
    heal_detect_ms: int = 30
    # Print the end-of-run telemetry block (phase breakdown, throughput).
    telemetry_summary: bool = False
    # --- multi-rumor traffic (ISSUE 8) ---------------------------------------
    # Number of concurrent rumors sharing the dissemination substrate.  R=1
    # (default) keeps every legacy single-rumor code path byte-for-byte: the
    # rumor axis only materializes when multi_rumor resolves True.  R>1 adds
    # a packed uint32 word ladder (W = ceil(R/32) words per node / per mail
    # entry) and first-touch-wins becomes a per-rumor bitwise OR fold over
    # the SAME mailbox/sort/rank/flat-scatter machinery -- no per-rumor loop.
    rumors: int = 1
    # "oneshot": all R rumors injected at tick 0 at R random sources, the run
    # ends when every rumor reaches coverage_target (the classic wall-time
    # measurement, now R-wide).  "stream": rumors are injected continuously
    # from a jitted schedule at `stream_rate` rumors per 1000 simulated ms
    # until all `rumors` are in flight; Stats/telemetry report steady-state
    # throughput (rumors/s reaching the target, deliveries/s) instead of a
    # single one-shot wall time.
    traffic: str = "oneshot"
    # Streaming injection rate: rumors per 1000 simulated ms (>= 1).  Rumor
    # r is injected at tick r * 1000 // stream_rate at a derived-key uniform
    # source, shard-count invariantly.
    stream_rate: int = 100
    # --- elastic serving mode (ISSUE 11; gossip_simulator_tpu/serve.py) ------
    # Long-lived serving loop: watches mail-ring occupancy against the
    # watermarks below and reshards (checkpoint -> wider/narrower mesh ->
    # resume) without dropping in-flight rumors.  Requires -traffic stream
    # on the event engine (jax/sharded backends).
    serve: bool = False
    # Arrival process shaping the per-rumor injection schedule (stream
    # traffic): "fixed" keeps the analytic r * 1000 // stream_rate ladder
    # (bit-identical to the pre-serve build); "poisson" draws exponential
    # inter-arrivals with mean 1000/stream_rate; "burst" releases groups
    # of 8 rumors at group boundaries; "diurnal" modulates the rate with a
    # sinusoidal load curve.  All schedules are precomputed host-side from
    # (arrivals, stream_rate, rumors, seed) alone -- keyed by rumor index,
    # so they are shard-count invariant and reshard-resume safe.
    arrivals: str = "fixed"
    # Autoscaler watermarks: mail-ring occupancy fractions (high-water
    # entries / slot capacity).  serve_window consecutive windows above
    # serve_high trip a widen; the same below serve_low trip a narrow.
    serve_high: float = 0.85
    serve_low: float = 0.10
    serve_window: int = 3
    # Shard-count band for the autoscaler.  -1 = all visible devices.
    serve_min_shards: int = 1
    serve_max_shards: int = -1
    # Deterministic transition override for CI: "S@W[,S@W...]" reshards to
    # S shards at serve window W regardless of occupancy (e.g. "8@4,1@10"
    # forces one widen and one narrow).  Empty = telemetry-driven.
    serve_force: str = ""
    # Admission-control backoff cap (simulated ms): when the widest mesh
    # is still saturated, pending injections are deferred by a doubling
    # backoff capped here, counted in Stats.shed, and never dropped.
    serve_max_defer: int = 2000
    # Checkpoint retention: after each successful save keep only the
    # newest K snapshots (sha256 sidecars pruned with them).  0 = keep all.
    ckpt_keep: int = 0
    # Internal: explicit per-rumor injection-tick override (sorted tuple,
    # len == rumors).  Set by serve's admission control when it defers
    # pending injections; not a CLI flag.
    inject_ticks: Optional[tuple] = None
    # --- tuning table (ISSUE 12; gossip_simulator_tpu/tuning.py) -------------
    # Per-platform tuned-constant table produced by scripts/autotune.py:
    # "auto" consults the committed TUNING_TABLE.json when present, "off"
    # forces registered defaults, a path loads that table.  Resolution
    # order per tunable: explicit CLI flag (-compact-chunk, -event-chunk,
    # -event-slot-cap) > table entry > registered default; the active
    # entry id (or "defaults") is stamped into resolved_gates().
    tuning_table: str = "auto"
    # --- numeric gossip (ISSUE 14; models/pushsum.py) -------------------------
    # Model family: "si" is the reference's 1-bit infection; "pushsum" runs
    # Kempe-style PushSum averaging -- every node carries a (value, weight)
    # mass vector in 64-bit fixed point (exact integer limbs, so shard-count
    # invariance and conservation hold bit-exactly), keeps ceil(half) each
    # window and pushes the rest split over its friends through the same
    # mail ring / all_to_all; delivery is a commutative scatter-ADD instead
    # of the SI first-touch-wins OR.  Convergence: max over live nodes of
    # the relative error of value/weight vs the true network mean.
    model: str = "si"
    # PushSum payload dimensionality (value vector length, 1..8).
    pushsum_dim: int = 2
    # Convergence threshold: the run completes when every live node's
    # estimate is within this relative error of the true mean.
    pushsum_eps: float = 1e-3

    # --- derived --------------------------------------------------------------
    @property
    def fanin_resolved(self) -> int:
        if self.fanin >= 0:
            return self.fanin
        return 6 if self.compat_reference else self.fanout + 1

    @property
    def max_degree(self) -> int:
        """Friend-list capacity.

        A node's list grows by its own bootstrap (up to fanout,
        simulator.go:95-106) and by accepted makeups (up to fanin,
        simulator.go:66-75); eviction keeps it at fanin once saturated.
        """
        return max(self.fanout, self.fanin_resolved)

    @property
    def delay_span(self) -> int:
        return self.delayhigh - self.delaylow

    @property
    def er_p_resolved(self) -> float:
        return self.er_p if self.er_p > 0 else self.fanout / max(self.n, 1)

    @property
    def graph_width(self) -> int:
        """Actual friends-table column count for this config's graph: the
        Erdős–Rényi generators pad to the Poisson tail cap (er_cap), which
        can be ~3x max_degree.  This bounds a single sender's reservation;
        aggregate in-flight sizing uses mean_degree (reservations are
        exact-size, so padding never reaches the mail ring)."""
        if self.graph == "erdos":
            return er_cap(self.er_p_resolved * self.n)
        return self.max_degree

    @property
    def mean_degree(self) -> float:
        """Expected out-degree -- the right per-node in-flight budget for
        the event engine's exact-size mail reservations (event.slot_cap)."""
        if self.graph == "erdos":
            return self.er_p_resolved * self.n
        return float(self.max_degree)

    @property
    def crashrate_eff(self) -> float:
        """Effective per-reception crash probability (the compat gate's
        1%-resolution truncation applied -- simulator.go:180; mirrors
        epidemic.p_eff, which models/ keep for jit-time constants)."""
        if self.compat_reference:
            return int(self.crashrate * 100) / 100.0
        return self.crashrate

    @property
    def scenario_resolved(self):
        """Parsed fault-injection Scenario (scenario.OFF when "off") --
        module-cached, so the jitted closures all see one object."""
        from gossip_simulator_tpu import scenario as _scen

        return _scen.parse(self.scenario)

    @property
    def overlay_heal_resolved(self) -> bool:
        return self.overlay_heal == "on"

    @property
    def faults_enabled(self) -> bool:
        """Whether the phase-2 steps carry the fault machinery (the
        per-node down_since crash clock and the scenario tick): scenario
        crash/churn/recovery events, or healing -- whose dead-friend
        detection reads the same clock."""
        return (self.scenario_resolved.has_faults
                or self.overlay_heal_resolved)

    @property
    def dup_suppress_resolved(self) -> bool:
        """Whether the event engine suppresses guaranteed-duplicate edges
        at append (see the `dup_suppress` field comment).  Only sound at
        crash_p == 0; validate() rejects an explicit "on" otherwise.
        Scenario faults also force it off: a suppressed edge's count is
        credited assuming delivery to a live node, but a scenario crash
        can black-hole the destination between append and delivery --
        the unsuppressed path would then NOT count it."""
        if self.dup_suppress == "off":
            return False
        if self.scenario_resolved.has_faults:
            return False
        if self.multi_rumor:
            # The "guaranteed duplicate" predicate (destination's received
            # bit already set -- monotone) no longer implies zero-information
            # delivery: an infected node can still gain NEW rumor bits from
            # the entry's payload word.  validate() rejects an explicit "on".
            return False
        return self.crashrate_eff == 0.0

    @property
    def multi_rumor(self) -> bool:
        """Whether the rumor axis materializes (R > 1, or stream traffic --
        a stream of 1 still needs per-rumor accounting).  Python-static: the
        default single-rumor configuration never traces a rumor-axis op."""
        return self.rumors > 1 or self.traffic == "stream"

    @property
    def rumor_word_count(self) -> int:
        """uint32 words in the packed rumor ladder (W = ceil(R/32))."""
        return (self.rumors + 31) // 32 if self.multi_rumor else 1

    @property
    def last_inject_tick(self) -> int:
        """Tick of the final rumor's injection under stream traffic
        (rumor r enters at r * 1000 // stream_rate on the fixed schedule;
        non-fixed arrivals and serve deferrals consult the precomputed
        arrival table); 0 for oneshot."""
        if self.traffic != "stream":
            return 0
        from gossip_simulator_tpu import arrivals as _arrivals

        table = _arrivals.table_or_none(self)
        if table is not None:
            return int(table[-1])
        return (self.rumors - 1) * 1000 // max(self.stream_rate, 1)

    @property
    def effective_time_mode(self) -> str:
        """Push-pull anti-entropy is a synchronous per-round protocol; it always
        runs (and is budgeted) in rounds mode regardless of `time_mode`."""
        return "rounds" if self.protocol == "pushpull" else self.time_mode

    @property
    def checkpointing_enabled(self) -> bool:
        """Snapshots can actually be written: BOTH -checkpoint-every and
        -checkpoint-dir are set.  THE predicate for every gate that trades
        the fast paths for per-window observability (driver phase-1/2
        gates, _Checkpointer._due) -- they drifted when each spelled it
        out (advisor r4)."""
        return bool(self.checkpoint_every and self.checkpoint_dir)

    @property
    def heartbeat_dir_resolved(self) -> str:
        """Where liveness beacons live: the explicit -heartbeat-dir, else a
        heartbeats/ subdir of the checkpoint dir (supervision requires
        checkpointing, so the fallback always resolves under -supervise)."""
        if self.heartbeat_dir:
            return self.heartbeat_dir
        if self.checkpoint_dir:
            return os.path.join(self.checkpoint_dir, "heartbeats")
        return ""

    @property
    def telemetry_enabled(self) -> bool:
        """Whether the device-side loops record per-window history (see the
        `telemetry` field): jax/sharded only -- the oracles' windowed loop
        IS their only loop."""
        return self.telemetry != "off" and self.backend in ("jax", "sharded")

    @property
    def telemetry_spatial_enabled(self) -> bool:
        """Whether the device-side loops also record the spatial panels
        (per-group / per-shard / traffic-matrix histories).  Rides the
        telemetry fast path, so it inherits telemetry_enabled's gating."""
        return self.telemetry_spatial == "on" and self.telemetry_enabled

    @property
    def overlay_mode_resolved(self) -> str:
        """Size-banded 'auto' resolution (see the field comment): ticks at
        n <= OVERLAY_TICKS_AUTO_MAX on tick-semantics runs, rounds
        otherwise (the ticks overlay engine needs -time-mode ticks)."""
        if self.overlay_mode != "auto":
            return self.overlay_mode
        if (self.backend in ("jax", "sharded")
                and self.effective_time_mode != "ticks"):
            return "rounds"
        from gossip_simulator_tpu import tuning as _tuning

        band = _tuning.value("config.overlay_ticks_auto_max", self,
                             default=OVERLAY_TICKS_AUTO_MAX)
        return "ticks" if self.n <= band else "rounds"

    @property
    def overlay_adaptive_chunks_resolved(self) -> bool:
        return self.overlay_adaptive_chunks != "off"

    @property
    def overlay_dead_skip_resolved(self) -> bool:
        return self.overlay_dead_skip != "off"

    @staticmethod
    def _kernel_gate(value: str) -> str:
        """"xla" or "pallas" for a -deliver-kernel value.  "auto" is "xla"
        on every platform, with no probe: whether the Pallas family goes
        back on the default path is for a measured PR to decide.
        Explicit "pallas" runs the kernels (natively on TPU, in interpret
        mode elsewhere) and lets whatever the compiler or the interpreter
        raises propagate."""
        return "pallas" if value == "pallas" else "xla"

    @property
    def deliver_kernel_resolved(self) -> str:
        return self._kernel_gate(self.deliver_kernel)

    @property
    def exchange_pipeline_resolved(self) -> str:
        """"off" or "double" -- resolved LAZILY (first model-build time,
        after jaxsetup.setup(); validate() must not import jax).
        Explicit off/double pass through; "auto" picks double only on a
        multi-device mesh -- at S=1 the exchange is an identity (no
        collective in the program), so there is nothing to overlap and
        the serial loop is already optimal.  The engines additionally
        run serial at S=1 even under a forced "double" (trivially
        identical: the pipelined loop with no collective is the serial
        loop plus a no-op staging buffer)."""
        if self.exchange_pipeline in ("off", "double"):
            return self.exchange_pipeline
        import jax

        return "double" if len(jax.devices()) > 1 else "off"

    @property
    def tuning_entry_resolved(self) -> str:
        """Active tuning-table entry id(s, "+"-joined when several
        spaces match), or "defaults" -- resolved LAZILY
        (table matching keys on the jax platform fingerprint, so the
        lookup happens post-setup like deliver_kernel_resolved; validate()
        must not import jax).  Never raises: any table-resolution error
        degrades to "defaults", the values the run would use anyway."""
        from gossip_simulator_tpu import tuning

        return tuning.entry_id(self)

    def resolved_gates(self) -> dict:
        """The resolved gate set, stamped into run artifacts and the
        terminal `result` record so a trajectory is attributable without
        re-deriving auto resolutions from argv.  The kernel gates only
        exist on the jax/sharded backends; the oracles report None.  Only
        TRAJECTORY-affecting gates belong here: observability toggles
        (telemetry, checkpointing) are excluded on purpose so a
        telemetry-on/off twin pair's `result` records stay
        field-identical (the fast-path replay parity tests compare
        them)."""
        gates = {
            "engine": self.engine_resolved,
            "overlay_mode": self.overlay_mode_resolved,
            "compact": self.compact_resolved,
            "overlay_adaptive_chunks": self.overlay_adaptive_chunks_resolved,
            "overlay_dead_skip": self.overlay_dead_skip_resolved,
            "overlay_heal": self.overlay_heal_resolved,
            "dup_suppress": self.dup_suppress_resolved,
            "multi_rumor": self.multi_rumor,
            "time_mode": self.effective_time_mode,
            "model": self.model,
        }
        jaxy = self.backend in ("jax", "sharded")
        gates["deliver_kernel"] = self.deliver_kernel_resolved if jaxy else None
        # Exchange pipelining only exists on the sharded backend's
        # routed path; everywhere else there is no exchange to overlap.
        gates["exchange_pipeline"] = (
            self.exchange_pipeline_resolved
            if self.backend == "sharded" else "off")
        # The active tuning-table entry ids ("defaults" when no table
        # matches): a table CAN carry trajectory-affecting values (it is
        # reviewed, committed data -- autotune itself persists only
        # contract-neutral tunables band-wide, and gate-validated ones
        # behind a matching workload-shape key), so compare_runs names a
        # mismatch here as the first divergence suspect.
        gates["tuning_table"] = self.tuning_entry_resolved
        # The active compile-budget pin ("none" when absent): the budget
        # cannot move a trajectory, but a RETRACE regression it would
        # have caught can hide behind one -- so compare_runs names a
        # stale budget id right next to the tuning-table id when
        # fingerprints diverge.  Never raises (budget_id degrades to
        # "none"); pure-stdlib, so validate()'s no-jax rule holds.
        from gossip_simulator_tpu.analysis import runtime as _rt

        gates["compile_budget"] = _rt.budget_id()
        return gates

    @property
    def log_jsonl_resolved(self) -> str:
        """JSONL destination: an explicit -log-jsonl wins; otherwise a
        -run-dir run logs into its own artifact (metrics.jsonl) so the
        dir is complete without extra flags."""
        if self.log_jsonl:
            return self.log_jsonl
        if self.run_dir:
            import os

            return os.path.join(self.run_dir, "metrics.jsonl")
        return ""

    @property
    def trace_resolved(self) -> str:
        """Trace destination: explicit -trace wins; a -run-dir run traces
        into its artifact by default (host-side only -- the traced jitted
        programs are unchanged either way)."""
        if self.trace:
            return self.trace
        if self.run_dir:
            import os

            return os.path.join(self.run_dir, "trace.json")
        return ""

    def static_boot_for(self, n_rows: int) -> bool:
        """One-shot static bootstrap for a ROUNDS-overlay surface of
        `n_rows` rows (single-device engine only; the sharded hook path
        keeps the per-round schedule -- its routed init has no burst
        delivery and its per-shard slices sit below the band anyway)."""
        if self.overlay_static_boot != "auto":
            return self.overlay_static_boot == "on"
        return n_rows >= OVERLAY_STATIC_BOOT_MIN_ROWS

    @property
    def compact_resolved(self) -> bool:
        if self.compact == "auto":
            return (self.effective_time_mode == "ticks"
                    and self.protocol != "pushpull")
        return self.compact == "on"

    @property
    def engine_resolved(self) -> str:
        """Event engine requires SI/SIR + ticks semantics on the jax or
        sharded backend; everything else uses the ring engine.  Auto picks
        event for BOTH SI and SIR (round 5: event SIR runs the BASELINE
        config-4 shape ~8x faster than ring -- 5.1 vs 42 s at 10M ER --
        with crash-path-only divergences enumerated in models/event.py and
        pinned by the vs-ring/determinism/dieout/removal-1==SI tests plus
        the sir_event golden).  An explicit `-compact on/off` is a
        ring-engine request (the event engine has no dense path to
        compact), so auto honors it."""
        if self.engine == "event":
            return "event"
        if (self.engine == "auto" and self.backend in ("jax", "sharded")
                and self.protocol in ("si", "sir")
                and self.effective_time_mode == "ticks"
                and self.compact == "auto"):
            return "event"
        return "ring"

    def mailbox_cap_for(self, n_rows: int, *, stacked: bool = False) -> int:
        """Mailbox capacity for a delivery surface of `n_rows` local rows
        (the full node axis single-device; one shard's slice sharded --
        flat int32 addressing is per-LOCAL-array, so a sharded run keeps
        cap 16 well past the single-device boundary).

        `stacked=True` is for consumers that deliver through
        ops.mailbox.deliver_pair's stacked [2n, cap] flat addressing (the
        phase-1 ticks engines); only they shrink at the half boundary.
        Plain deliver() surfaces -- the rounds overlay and the phase-2
        ring delivery, in any overlay mode -- keep the full-boundary cap
        (advisor r3: a mode-keyed shrink halved phase-2 overflow headroom
        in ticks runs for n_local in (~6.7e7, 1.34e8] for no reason)."""
        if self.mailbox_cap > 0:
            return self.mailbox_cap
        # Balls-in-bins: with <=N uniform messages into N bins the max load
        # is ~ln N/ln ln N w.h.p. (~6.3 at N=1e8), so BOTH 16 and 8 put
        # overflow in the negligible band (and overflow is counted, never
        # silent).  Two size-banded shrinks to 8:
        # * MEMORY (round 4): the rounds overlay holds (n, cap+2) makeup
        #   + (n, cap) breakup emission buffers -- at cap 16 that is
        #   13.6 GB for n=1e8, over the 16 GB v5e HBM by itself.  Cap 8
        #   halves it and makes the reference-default 100M two-phase
        #   pipeline fit a single chip.  The band sits above every
        #   measured/golden-pinned config (<= 10M rows keep cap 16).
        # * ADDRESSING: past n_rows ~ 1.34e8, (n_rows+1)*16 overflows the
        #   flat int32 mailbox addressing and delivery would silently
        #   take the ~15x dense 2-D-scatter path (ops/mailbox.deliver);
        #   cap 8 keeps flat addressing to n_rows ~ 2.7e8.  Beyond THAT
        #   the dense fallback engages and deliver's one-time warning
        #   names it.  deliver_pair's STACKED [2n, cap] addressing
        #   (stacked=True consumers) stops fitting at ~1.34e8 even at
        #   cap 8; its fallback is two deliver() passes, not the dense
        #   path.  The memory band (3.2e7) sits below both addressing
        #   boundaries, so the fits() checks are a backstop kept EXACTLY
        #   as the delivery paths consult them (deliver_pair checks
        #   fits(2n+1, cap); deliver checks fits(n, cap)).
        from gossip_simulator_tpu.ops.mailbox import flat_addressing_fits

        if n_rows >= MAILBOX_CAP_MEMORY_BAND:
            return 8
        rows = 2 * n_rows + 1 if stacked else n_rows
        if not flat_addressing_fits(rows, 16):
            return 8
        return 16

    @property
    def mailbox_cap_resolved(self) -> int:
        return self.mailbox_cap_for(self.n)

    def validate(self) -> "Config":
        if self.n < 2:
            raise ValueError(f"n must be >= 2, got {self.n}")
        if self.graph == "overlay" and self.n < 3:
            # Breakup replacement excludes two ids (self + leaver,
            # simulator.go:87-89); with n=2 the reference's retry loop would
            # spin forever -- reject the config instead.
            raise ValueError("overlay graph requires n >= 3")
        if self.fanout < 1:
            raise ValueError(f"fanout must be >= 1, got {self.fanout}")
        if self.fanin != -1 and self.fanin < 1:
            raise ValueError(f"fanin must be >= 1 (or -1=auto), got {self.fanin}")
        if self.delayhigh <= self.delaylow:
            # The reference panics inside rand.Intn here (simulator.go:167).
            raise ValueError(
                f"delayhigh ({self.delayhigh}) must be > delaylow ({self.delaylow})"
            )
        if self.delaylow < 0:
            raise ValueError(f"delaylow must be >= 0, got {self.delaylow}")
        if (self.delaylow < 1 and self.backend in ("jax", "sharded")
                and self.effective_time_mode == "ticks"):
            # The delay-ring engines batch B = min(10, delaylow) ticks per
            # step and clamp drawn delays to >= 1 (a zero-delay message
            # would land in the ring slot already drained this step); with
            # delaylow=0 the clamp silently reshapes the delay distribution
            # instead.  Reject it -- zero-delay networks run faithfully on
            # the discrete-event backends (native/cpp) or in rounds mode.
            raise ValueError(
                "delaylow must be >= 1 in ticks mode on the jax/sharded "
                "backends (drawn delays are clamped to >= 1 tick); use "
                "-time-mode rounds or -backend native/cpp for delaylow=0")
        for name in ("droprate", "crashrate", "removal_rate"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be in [0,1], got {v}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {self.backend!r}")
        if self.protocol not in PROTOCOLS:
            raise ValueError(
                f"protocol must be one of {PROTOCOLS}, got {self.protocol!r}"
            )
        if self.graph not in GRAPHS:
            raise ValueError(f"graph must be one of {GRAPHS}, got {self.graph!r}")
        if self.compact not in ("auto", "on", "off"):
            raise ValueError(
                f"compact must be auto|on|off, got {self.compact!r}")
        if self.engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {self.engine!r}")
        if self.dup_suppress not in ("auto", "on", "off"):
            raise ValueError(
                f"dup_suppress must be auto|on|off, got {self.dup_suppress!r}")
        if self.telemetry not in ("on", "off"):
            raise ValueError(
                f"telemetry must be on|off, got {self.telemetry!r}")
        if self.telemetry_spatial not in ("on", "off"):
            raise ValueError(f"telemetry_spatial must be on|off, got "
                             f"{self.telemetry_spatial!r}")
        if self.telemetry_spatial == "on" and self.telemetry == "off":
            raise ValueError(
                "-telemetry-spatial on records panels on the telemetry "
                "fast path; it cannot run with -telemetry off")
        if (self.telemetry_spatial == "on"
                and self.backend not in ("jax", "sharded")):
            raise ValueError(
                "-telemetry-spatial needs a device-side loop to record "
                f"panels; backend {self.backend!r} has none")
        for name in ("overlay_adaptive_chunks", "overlay_dead_skip",
                     "overlay_static_boot"):
            v = getattr(self, name)
            if v not in ("auto", "on", "off"):
                raise ValueError(f"{name} must be auto|on|off, got {v!r}")
        if self.deliver_kernel not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"deliver_kernel must be auto|xla|pallas, "
                f"got {self.deliver_kernel!r}")
        if self.exchange_pipeline not in ("auto", "off", "double"):
            raise ValueError(
                f"exchange_pipeline must be auto|off|double, "
                f"got {self.exchange_pipeline!r}")
        if self.dup_suppress == "on" and self.crashrate_eff > 0.0:
            raise ValueError(
                "-dup-suppress on requires an effective crash rate of 0 "
                "(crash draws are keyed by mailbox position; suppressing "
                "entries would shift every later draw).  Note the "
                "reference's own default crashrate 0.001 IS 0 under "
                "-compat-reference (1%-resolution truncation).")
        # --- multi-rumor traffic -----------------------------------------
        if not 1 <= self.rumors <= 1024:
            raise ValueError(
                f"rumors must be in [1, 1024], got {self.rumors}")
        if self.traffic not in ("oneshot", "stream"):
            raise ValueError(
                f"traffic must be oneshot|stream, got {self.traffic!r}")
        if self.multi_rumor:
            if self.backend not in ("jax", "sharded"):
                raise ValueError(
                    "-rumors > 1 / -traffic stream require backend=jax or "
                    "sharded (the discrete-event oracles are single-rumor)")
            if self.protocol != "si":
                raise ValueError(
                    "-rumors > 1 / -traffic stream support protocol=si only "
                    "(SIR removal and push-pull digests are single-rumor)")
            if self.effective_time_mode != "ticks":
                raise ValueError(
                    "-rumors > 1 / -traffic stream require -time-mode ticks")
            if self.compat_reference:
                raise ValueError(
                    "-compat-reference is strictly single-rumor (the "
                    "reference broadcasts exactly one rumor per run)")
            if self.dup_suppress == "on":
                raise ValueError(
                    "-dup-suppress on is unsound with a rumor axis: an "
                    "already-infected destination can still gain new rumor "
                    "bits, so 'guaranteed duplicate' edges carry information")
            if self.engine_resolved == "ring":
                if self.backend == "sharded":
                    raise ValueError(
                        "-rumors > 1 on the ring engine is single-device "
                        "only (use -engine event for -backend sharded)")
                if self.overlay_heal_resolved:
                    raise ValueError(
                        "-overlay-heal with -rumors > 1 requires the event "
                        "engine (ring-engine heal re-sends are single-rumor)")
        if self.traffic == "stream":
            if not 1 <= self.stream_rate <= 1_000_000:
                # The upper bound keeps the injection schedule's clamped
                # tick * rate product in int32 (event.injection_batch).
                raise ValueError(
                    f"stream_rate must be in [1, 1000000], got "
                    f"{self.stream_rate}")
            if self.engine_resolved != "event":
                raise ValueError(
                    "-traffic stream requires the event engine (the jitted "
                    "injection schedule rides the event window step)")
        # --- numeric gossip (-model pushsum) ------------------------------
        if self.model not in ("si", "pushsum"):
            raise ValueError(
                f"model must be si|pushsum, got {self.model!r}")
        if self.model == "pushsum":
            if self.backend not in ("jax", "sharded"):
                raise ValueError(
                    "-model pushsum requires backend=jax or sharded (the "
                    "discrete-event oracles are 1-bit SI only)")
            if self.graph not in ("kout", "erdos"):
                raise ValueError(
                    "-model pushsum supports -graph kout|erdos (the rounds "
                    "overlay build has no numeric state to average)")
            if self.protocol != "si":
                raise ValueError(
                    "-model pushsum replaces the infection protocol; use "
                    "the default -protocol si")
            if self.effective_time_mode != "ticks":
                raise ValueError("-model pushsum requires -time-mode ticks")
            if self.engine_resolved != "event":
                raise ValueError(
                    "-model pushsum rides the event-engine mail ring; "
                    "leave -engine auto/event")
            if self.multi_rumor or self.traffic != "oneshot":
                raise ValueError(
                    "-model pushsum is incompatible with -rumors > 1 / "
                    "-traffic stream (mass columns replace the rumor words)")
            if self.compat_reference:
                raise ValueError(
                    "-compat-reference is strictly 1-bit SI; it has no "
                    "PushSum surface")
            if self.dup_suppress == "on":
                raise ValueError(
                    "-dup-suppress on is meaningless under -model pushsum: "
                    "every delivery carries fresh mass, nothing is a "
                    "guaranteed duplicate")
            if self.droprate != 0.0:
                raise ValueError(
                    "-model pushsum requires droprate 0 (a dropped message "
                    "destroys mass and breaks the conservation invariant; "
                    "model lossy links with -scenario partitions instead, "
                    "which block at send time)")
            if self.crashrate != 0.0:
                raise ValueError(
                    "-model pushsum requires crashrate 0 (per-reception "
                    "crashes black-hole in-flight mass; use -scenario "
                    "crash/churn events -- crashed nodes park mass and "
                    "rejoin with it)")
            if self.serve:
                raise ValueError("-serve streams rumors; it has no "
                                 "pushsum surface")
            if not 1 <= self.pushsum_dim <= 8:
                raise ValueError(
                    f"pushsum_dim must be in [1, 8], got {self.pushsum_dim}")
            if not self.pushsum_eps > 0.0:
                raise ValueError(
                    f"pushsum_eps must be > 0, got {self.pushsum_eps}")
        # --- elastic serving / arrival processes --------------------------
        if self.arrivals not in ("fixed", "poisson", "burst", "diurnal"):
            raise ValueError(
                f"arrivals must be fixed|poisson|burst|diurnal, "
                f"got {self.arrivals!r}")
        if self.arrivals != "fixed" and self.traffic != "stream":
            raise ValueError(
                "-arrivals shapes the streaming injection schedule; it "
                "requires -traffic stream")
        if self.inject_ticks is not None:
            if self.traffic != "stream":
                raise ValueError("inject_ticks requires -traffic stream")
            ticks = self.inject_ticks
            if len(ticks) != self.rumors:
                raise ValueError(
                    f"inject_ticks length ({len(ticks)}) must equal rumors "
                    f"({self.rumors})")
            if any(t < 0 or t >= 2**31 - 1 for t in ticks):
                raise ValueError("inject_ticks entries must be int32 ticks")
            if any(b < a for a, b in zip(ticks, ticks[1:])):
                raise ValueError("inject_ticks must be nondecreasing")
        if self.serve:
            if self.traffic != "stream":
                raise ValueError(
                    "-serve is the streaming service loop; it requires "
                    "-traffic stream")
            if self.backend not in ("jax", "sharded"):
                raise ValueError("-serve requires backend=jax or sharded")
            if self.resume:
                raise ValueError(
                    "-serve manages its own reshard-resume cycle; -resume "
                    "is not supported with it")
            if not 0.0 <= self.serve_low < self.serve_high <= 1.0:
                raise ValueError(
                    f"need 0 <= serve_low < serve_high <= 1, got "
                    f"low={self.serve_low} high={self.serve_high}")
            if self.serve_window < 1:
                raise ValueError(
                    f"serve_window must be >= 1, got {self.serve_window}")
            if self.serve_min_shards < 1:
                raise ValueError(
                    f"serve_min_shards must be >= 1, "
                    f"got {self.serve_min_shards}")
            if (self.serve_max_shards != -1
                    and self.serve_max_shards < self.serve_min_shards):
                raise ValueError(
                    "serve_max_shards must be -1 (all devices) or >= "
                    "serve_min_shards")
            if self.serve_max_defer < 0:
                raise ValueError(
                    f"serve_max_defer must be >= 0, got {self.serve_max_defer}")
            parse_serve_force(self.serve_force)  # raises on a bad spec
        if self.ckpt_keep < 0:
            raise ValueError(f"ckpt_keep must be >= 0, got {self.ckpt_keep}")
        if self.tuning_table not in ("auto", "off"):
            import os

            if not os.path.exists(self.tuning_table):
                raise ValueError(
                    f"-tuning-table: no such file {self.tuning_table!r} "
                    "(use 'auto', 'off', or a tuning-table JSON path)")
        # --- fault-injection scenario ------------------------------------
        scen = self.scenario_resolved  # raises ValueError on a bad spec
        if scen.active:
            if self.backend not in ("jax", "sharded"):
                raise ValueError(
                    "-scenario requires backend=jax or sharded (the "
                    "discrete-event oracles have no fault timeline)")
            if self.protocol == "pushpull":
                raise ValueError(
                    "-scenario supports protocol=si|sir (push-pull has "
                    "no send-time wave for the partition mask to filter)")
            if scen.groups > self.n:
                raise ValueError(
                    f"scenario groups ({scen.groups}) cannot exceed n "
                    f"({self.n})")
            if self.dup_suppress == "on" and scen.has_faults:
                raise ValueError(
                    "-dup-suppress on is unsound under scenario faults: "
                    "a banked duplicate credit assumes delivery to a "
                    "live node, but a scenario crash can black-hole the "
                    "destination before its window drains")
        if self.overlay_heal not in ("on", "off"):
            raise ValueError(
                f"overlay_heal must be on|off, got {self.overlay_heal!r}")
        if self.overlay_heal_resolved:
            if self.backend not in ("jax", "sharded"):
                raise ValueError(
                    "-overlay-heal requires backend=jax or sharded")
            if self.protocol == "pushpull":
                raise ValueError(
                    "-overlay-heal is meaningless for push-pull (fresh "
                    "random peers every round; there is no friends table "
                    "to repair)")
        if self.heal_detect_ms < 0:
            raise ValueError(
                f"heal_detect_ms must be >= 0, got {self.heal_detect_ms}")
        if self.engine == "event":
            if (self.protocol not in ("si", "sir")
                    or self.effective_time_mode != "ticks"):
                raise ValueError(
                    "engine=event supports protocol=si|sir in ticks mode only")
            if self.backend not in ("jax", "sharded"):
                raise ValueError(
                    "engine=event requires backend=jax or sharded")
        if self.time_mode not in TIME_MODES:
            raise ValueError(
                f"time_mode must be one of {TIME_MODES}, got {self.time_mode!r}"
            )
        if self.overlay_mode not in ("auto", "rounds", "ticks"):
            raise ValueError(
                f"overlay_mode must be 'auto', 'rounds' or 'ticks', "
                f"got {self.overlay_mode!r}")
        if self.overlay_mode == "ticks" and self.graph == "overlay":
            # native/cpp are discrete-event and inherently faithful, so the
            # flag is a no-op there; only the vectorized backends gate.
            # (auto resolves to rounds on rounds-semantics runs instead of
            # erroring -- the gate is for the EXPLICIT request.)
            if (self.backend in ("jax", "sharded")
                    and self.effective_time_mode != "ticks"):
                raise ValueError(
                    "-overlay-mode ticks requires -time-mode ticks")
        if self.distributed:
            if self.backend != "sharded":
                raise ValueError("-distributed requires -backend sharded")
            manual = (bool(self.coordinator), self.num_processes != -1,
                      self.process_id != -1)
            if any(manual) and not all(manual):
                raise ValueError(
                    "-coordinator, -num-processes and -process-id must be "
                    "given together (or all omitted for jax's automatic "
                    "cluster detection, e.g. on TPU pods)")
            if all(manual):
                if self.num_processes < 1:
                    raise ValueError(
                        f"-num-processes must be >= 1, got {self.num_processes}")
                if not 0 <= self.process_id < self.num_processes:
                    raise ValueError(
                        f"-process-id must be in [0, {self.num_processes}), "
                        f"got {self.process_id}")
        if self.chaos and not self.supervise:
            raise ValueError(
                "-chaos is a supervision drill; it requires -supervise")
        if self.workers < 1:
            raise ValueError(f"-workers must be >= 1, got {self.workers}")
        if self.heartbeat_timeout_ms < 1:
            raise ValueError(
                f"-heartbeat-timeout-ms must be >= 1, "
                f"got {self.heartbeat_timeout_ms}")
        if self.recover_max_stale < 0:
            raise ValueError(
                f"-recover-max-stale must be >= 0, "
                f"got {self.recover_max_stale}")
        if self.init_timeout_s < 1:
            raise ValueError(
                f"-init-timeout must be >= 1, got {self.init_timeout_s}")
        if self.supervise:
            if self.backend not in ("jax", "sharded"):
                raise ValueError(
                    "-supervise requires backend=jax or sharded (recovery "
                    "rides the checkpoint/restore surface)")
            if self.serve:
                raise ValueError(
                    "-supervise and -serve are exclusive phase-2 loops")
            if self.distributed:
                raise ValueError(
                    "-supervise launches the -distributed workers itself; "
                    "run the supervisor WITHOUT -distributed")
            if self.resume:
                raise ValueError(
                    "-supervise manages resume itself (survivors relaunch "
                    "with -resume); start the supervisor without it")
            if not self.checkpointing_enabled:
                raise ValueError(
                    "-supervise requires -checkpoint-every and "
                    "-checkpoint-dir: recovery restores the last atomic "
                    "snapshot, so there must be one to restore")
            if self.workers < 2:
                raise ValueError(
                    "-supervise needs -workers >= 2 (losing the only "
                    "worker leaves no survivors to recover onto)")
            if self.coordinator and self.backend != "sharded":
                raise ValueError(
                    "-supervise with -coordinator spawns -distributed "
                    "workers, which require -backend sharded")
            if self.coordinator and (self.num_processes != -1
                                     or self.process_id != -1):
                raise ValueError(
                    "-supervise assigns -num-processes/-process-id to the "
                    "workers it spawns; do not set them on the supervisor")
            drill = parse_chaos(self.chaos)
            if drill is not None and drill.worker >= self.workers:
                raise ValueError(
                    f"-chaos targets worker {drill.worker} but only "
                    f"{self.workers} workers exist")
        if not 0.0 < self.coverage_target <= 1.0:
            raise ValueError(
                f"coverage_target must be in (0,1], got {self.coverage_target}"
            )
        if self.max_rounds < 1:
            raise ValueError(f"max_rounds must be >= 1, got {self.max_rounds}")
        if self.resume:
            if not self.checkpoint_dir:
                raise ValueError("-resume requires -checkpoint-dir")
            if self.backend not in ("jax", "sharded"):
                raise ValueError(
                    "-resume requires backend=jax or sharded")
        if self.fanout >= self.n:
            raise ValueError(f"fanout ({self.fanout}) must be < n ({self.n})")
        if self.backend == "jax":
            self.check_ring_rows(self.n)
        return self

    def check_ring_rows(self, rows: int, shards: int = 1) -> None:
        """Refuse a ring-engine run whose delay ring cannot be indexed in
        int32.  The ring is one flat int32[d * rows] array per device
        (`rows` = n on one device, n / shards per shard) whose scatter
        index uses d * rows as its drop sentinel (epidemic.deposit_local);
        d is epidemic.ring_depth.  The sharded wire word, rows * d + slot
        (exchange.pack_dst_slot), has the same bound.  validate() checks
        `-backend jax`; the sharded backend checks its shard once the mesh
        is known (ShardedStepper)."""
        if self.engine_resolved != "ring":
            return
        d = self.delayhigh if self.effective_time_mode == "ticks" else 2
        if d * rows < 2**31 - 1:
            return
        where = (f"-n {self.n}" if shards == 1 else
                 f"-n {self.n} over {shards} shards, {rows} per shard")
        raise ValueError(
            f"the ring engine's delay ring ({d} slots from -delayhigh, "
            f"times {where}) holds {d * rows} cells per device, over the "
            f"int32 index bound {2**31 - 1}; lower -delayhigh or -n"
            + (", or run -backend sharded" if shards == 1 else
               ", or run on more devices"))

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw).validate()

    # --- reference-format parameter dump (simulator.go:197-204) ---------------
    def parameter_dump(self) -> str:
        """Reference prints flags alphabetically via flag.VisitAll with an `ms`
        suffix on the delay flags (simulator.go:197-204)."""
        ref = {
            "crashrate": self.crashrate,
            "delayhigh": f"{self.delayhigh}ms",
            "delaylow": f"{self.delaylow}ms",
            "droprate": self.droprate,
            "fanin": self.fanin_resolved,
            "fanout": self.fanout,
            "n": self.n,
        }
        lines = ["=== Parameters ==="]
        lines += [f"{k}={v}" for k, v in sorted(ref.items())]
        return "\n".join(lines)


def parse_serve_force(spec: str) -> dict:
    """Parse a `-serve-force` spec "S@W[,S@W...]" into {window: shards}.
    Raises ValueError on malformed entries."""
    out: dict = {}
    if not spec:
        return out
    for part in spec.split(","):
        try:
            s_str, w_str = part.strip().split("@")
            s, w = int(s_str), int(w_str)
        except ValueError:
            raise ValueError(
                f"bad -serve-force entry {part!r} (expected S@W, e.g. 8@4)")
        if s < 1 or w < 0:
            raise ValueError(
                f"-serve-force entry {part!r}: need shards >= 1, window >= 0")
        if w in out:
            raise ValueError(
                f"-serve-force window {w} given twice")
        out[w] = s
    return out


@dataclasses.dataclass(frozen=True)
class ChaosDrill:
    """A parsed -chaos spec: inject `kind` against `worker` once the run
    reaches gossip window `window`."""

    kind: str  # "kill-worker" | "stall-worker"
    worker: int
    window: int


def parse_chaos(spec: str) -> Optional[ChaosDrill]:
    """Parse a `-chaos` drill spec "kill-worker@W[:K]" /
    "stall-worker@W[:K]" (W = target worker rank, K = gossip window to
    inject at, default 6).  Returns None for the empty spec; raises
    ValueError on malformed ones."""
    if not spec:
        return None
    try:
        kind, rest = spec.strip().split("@")
        if ":" in rest:
            w_str, k_str = rest.split(":")
        else:
            w_str, k_str = rest, "6"
        worker, window = int(w_str), int(k_str)
    except ValueError:
        raise ValueError(
            f"bad -chaos spec {spec!r} (expected kill-worker@W[:K] or "
            "stall-worker@W[:K], e.g. kill-worker@1:6)")
    if kind not in ("kill-worker", "stall-worker"):
        raise ValueError(
            f"-chaos kind must be kill-worker or stall-worker, got {kind!r}")
    if worker < 0:
        raise ValueError(f"-chaos worker must be >= 0, got {worker}")
    if window < 1:
        raise ValueError(
            f"-chaos window must be >= 1 (the drill fires after a full "
            f"gossip window), got {window}")
    return ChaosDrill(kind=kind, worker=worker, window=window)


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gossip-sim-tpu",
        description="TPU-native gossip/epidemic simulator "
        "(capability parity with go-distributed/gossip_simulator).",
    )
    d = Config()
    # Reference flags (single-dash accepted for drop-in parity with Go's flag).
    p.add_argument("-n", "--n", type=int, default=d.n, help="total number of nodes")
    p.add_argument("-fanout", "--fanout", type=int, default=d.fanout, help="fanout")
    p.add_argument(
        "-fanin", "--fanin", type=int, default=-1,
        help="fanin (default: fanout+1; reference defaults to the constant 6)",
    )
    p.add_argument("-delaylow", "--delaylow", type=int, default=d.delaylow,
                   help="delay low (ms)")
    p.add_argument("-delayhigh", "--delayhigh", type=int, default=d.delayhigh,
                   help="delay high (ms)")
    p.add_argument("-droprate", "--droprate", type=float, default=d.droprate,
                   help="message drop rate")
    p.add_argument("-crashrate", "--crashrate", type=float, default=d.crashrate,
                   help="machine crash rate")
    # Framework extensions.
    p.add_argument("-backend", "--backend", choices=BACKENDS, default=d.backend)
    p.add_argument("-protocol", "--protocol", choices=PROTOCOLS, default=d.protocol)
    p.add_argument("-graph", "--graph", choices=GRAPHS, default=d.graph)
    p.add_argument("-seed", "--seed", type=int, default=d.seed)
    p.add_argument("-max-rounds", "--max-rounds", dest="max_rounds", type=int,
                   default=d.max_rounds)
    p.add_argument("-coverage-target", "--coverage-target", dest="coverage_target",
                   type=float, default=d.coverage_target)
    p.add_argument("-time-mode", "--time-mode", dest="time_mode",
                   choices=TIME_MODES, default=d.time_mode)
    p.add_argument("-removal-rate", "--removal-rate", dest="removal_rate",
                   type=float, default=d.removal_rate)
    p.add_argument("-er-p", "--er-p", dest="er_p", type=float, default=d.er_p)
    p.add_argument("-compat-reference", "--compat-reference",
                   dest="compat_reference", action="store_true")
    p.add_argument("-pallas", "--pallas", action="store_true")
    p.add_argument("-compact", "--compact", choices=("auto", "on", "off"),
                   default="auto")
    p.add_argument("-engine", "--engine", choices=ENGINES, default=d.engine)
    p.add_argument("-event-slot-cap", "--event-slot-cap",
                   dest="event_slot_cap", type=int, default=d.event_slot_cap)
    p.add_argument("-event-chunk", "--event-chunk", dest="event_chunk",
                   type=int, default=d.event_chunk)
    p.add_argument("-dup-suppress", "--dup-suppress", dest="dup_suppress",
                   choices=("auto", "on", "off"), default=d.dup_suppress,
                   help="suppress guaranteed-duplicate sends at append "
                        "(event engine, crash rate 0 only; auto = on "
                        "whenever sound)")
    p.add_argument("-overlay-mode", "--overlay-mode", dest="overlay_mode",
                   choices=("auto", "rounds", "ticks"),
                   default=d.overlay_mode)
    p.add_argument("-overlay-adaptive-chunks", "--overlay-adaptive-chunks",
                   dest="overlay_adaptive_chunks",
                   choices=("auto", "on", "off"),
                   default=d.overlay_adaptive_chunks,
                   help="occupancy-adaptive hosted-delivery chunk ladder "
                        "for the split-round overlay (trajectory-neutral; "
                        "auto = on)")
    p.add_argument("-overlay-dead-skip", "--overlay-dead-skip",
                   dest="overlay_dead_skip", choices=("auto", "on", "off"),
                   default=d.overlay_dead_skip,
                   help="skip dead emission rows via counts carried across "
                        "rounds (split-round overlay; trajectory-neutral; "
                        "auto = on)")
    p.add_argument("-overlay-static-boot", "--overlay-static-boot",
                   dest="overlay_static_boot", choices=("auto", "on", "off"),
                   default=d.overlay_static_boot,
                   help="one-shot bootstrap burst for the rounds overlay "
                        "(auto = on at >= 32M rows; off reproduces the "
                        "staggered per-round schedule)")
    p.add_argument("-deliver-kernel", "--deliver-kernel",
                   dest="deliver_kernel", choices=("auto", "xla", "pallas"),
                   default=d.deliver_kernel,
                   help="mailbox delivery kernel: pallas fuses the "
                        "sort/rank/scatter chain into one pass "
                        "(bit-identical, A/B-pinned); xla reproduces "
                        "prior trajectories bit-for-bit; auto = xla")
    p.add_argument("-exchange-pipeline", "--exchange-pipeline",
                   dest="exchange_pipeline",
                   choices=("auto", "off", "double"),
                   default=d.exchange_pipeline,
                   help="sharded exchange pipelining: double defers each "
                        "chunk's drain one batch behind its all_to_all "
                        "so the next dispatch overlaps the drain "
                        "(bit-identical, A/B-pinned); off reproduces the "
                        "serial route->drain loop bit-for-bit; auto = "
                        "double on multi-device meshes, off at S=1")
    p.add_argument("-telemetry", "--telemetry", choices=("on", "off"),
                   default=d.telemetry,
                   help="device-resident per-window telemetry on fast-path "
                        "runs (jax/sharded); off restores the windowed "
                        "host loop for observing runs")
    p.add_argument("-telemetry-spatial", "--telemetry-spatial",
                   dest="telemetry_spatial", choices=("on", "off"),
                   default=d.telemetry_spatial,
                   help="per-group/per-shard panels + exchange traffic "
                        "matrix recorded next to the scalar history "
                        "(npz-only; stdout/JSONL unchanged)")
    p.add_argument("-telemetry-summary", "--telemetry-summary",
                   dest="telemetry_summary", action="store_true",
                   help="print the end-of-run telemetry block (phase "
                        "breakdown, throughput)")
    p.add_argument("-scenario", "--scenario", default=d.scenario,
                   help="fault-injection timeline: 'off', a JSON file "
                        "path, or inline JSON (crash waves, churn, "
                        "recovery downtime, partition masks -- see "
                        "scenario.py)")
    p.add_argument("-overlay-heal", "--overlay-heal", dest="overlay_heal",
                   choices=("on", "off"), default=d.overlay_heal,
                   help="phase-2 overlay self-healing: replace detected-"
                        "dead friends via the phase-1 makeup draw and "
                        "re-send the rumor over repaired edges")
    p.add_argument("-heal-detect-ms", "--heal-detect-ms",
                   dest="heal_detect_ms", type=int, default=d.heal_detect_ms,
                   help="ms of failed deliveries before a dead friend is "
                        "condemned and replaced")
    p.add_argument("-rumors", "--rumors", type=int, default=d.rumors,
                   help="concurrent rumors sharing one dissemination "
                        "substrate (packed uint32 word ladder; 1 = the "
                        "reference's single-rumor broadcast)")
    p.add_argument("-traffic", "--traffic", choices=("oneshot", "stream"),
                   default=d.traffic,
                   help="oneshot: all rumors injected at tick 0; stream: "
                        "continuous injection at -stream-rate with steady-"
                        "state throughput reporting")
    p.add_argument("-stream-rate", "--stream-rate", dest="stream_rate",
                   type=int, default=d.stream_rate,
                   help="stream traffic injection rate, rumors per 1000 "
                        "simulated ms")
    p.add_argument("-serve", "--serve", action="store_true",
                   help="elastic serving loop: autoscale the shard count "
                        "under streaming traffic via checkpoint -> reshard "
                        "-> resume, with admission control when saturated")
    p.add_argument("-arrivals", "--arrivals",
                   choices=("fixed", "poisson", "burst", "diurnal"),
                   default=d.arrivals,
                   help="stream arrival process: fixed analytic ladder, "
                        "poisson inter-arrivals, 8-rumor bursts, or a "
                        "sinusoidal diurnal curve (all deterministic per "
                        "rumor index, shard-count invariant)")
    p.add_argument("-serve-high", "--serve-high", dest="serve_high",
                   type=float, default=d.serve_high,
                   help="widen watermark: mail-ring occupancy fraction")
    p.add_argument("-serve-low", "--serve-low", dest="serve_low",
                   type=float, default=d.serve_low,
                   help="narrow watermark: mail-ring occupancy fraction")
    p.add_argument("-serve-window", "--serve-window", dest="serve_window",
                   type=int, default=d.serve_window,
                   help="consecutive windows beyond a watermark before the "
                        "autoscaler acts")
    p.add_argument("-serve-min-shards", "--serve-min-shards",
                   dest="serve_min_shards", type=int,
                   default=d.serve_min_shards)
    p.add_argument("-serve-max-shards", "--serve-max-shards",
                   dest="serve_max_shards", type=int,
                   default=d.serve_max_shards,
                   help="autoscaler shard-count ceiling (-1 = all devices)")
    p.add_argument("-serve-force", "--serve-force", dest="serve_force",
                   default=d.serve_force,
                   help="deterministic reshard override 'S@W[,S@W...]': "
                        "reshard to S shards at serve window W (CI twins)")
    p.add_argument("-serve-max-defer", "--serve-max-defer",
                   dest="serve_max_defer", type=int, default=d.serve_max_defer,
                   help="admission-control backoff cap in simulated ms")
    p.add_argument("-ckpt-keep", "--ckpt-keep", dest="ckpt_keep", type=int,
                   default=d.ckpt_keep,
                   help="keep only the newest K checkpoint snapshots after "
                        "each successful save (0 = keep all)")
    p.add_argument("-model", "--model", choices=("si", "pushsum"),
                   default=d.model,
                   help="model family: si = the reference's 1-bit "
                        "infection; pushsum = numeric PushSum averaging "
                        "(nodes push half their (value, weight) mass to "
                        "friends each window; delivery is a scatter-add; "
                        "the run converges when every live node's estimate "
                        "is within -pushsum-eps of the true mean)")
    p.add_argument("-pushsum-dim", "--pushsum-dim", dest="pushsum_dim",
                   type=int, default=d.pushsum_dim,
                   help="pushsum value-vector length (1..8)")
    p.add_argument("-pushsum-eps", "--pushsum-eps", dest="pushsum_eps",
                   type=float, default=d.pushsum_eps,
                   help="pushsum convergence threshold: max relative "
                        "error of any live node's estimate vs the true "
                        "network mean")
    p.add_argument("-tuning-table", "--tuning-table", dest="tuning_table",
                   default=d.tuning_table,
                   help="tuned-constant table (scripts/autotune.py): auto "
                        "= the committed TUNING_TABLE.json when present, "
                        "off = registered defaults, or a table path; "
                        "explicit flags like -event-chunk still outrank "
                        "table entries")
    p.add_argument("-trace", "--trace", default=d.trace,
                   help="write host-side phase/window spans as Chrome "
                        "trace-event JSON to this path")
    p.add_argument("-xprof", "--xprof", dest="xprof_dir",
                   default=d.xprof_dir,
                   help="wrap the run in a jax.profiler device trace "
                        "(TensorBoard/XProf dir): every host span is a "
                        "TraceAnnotation and the ring engine's ops carry "
                        "named scopes, so device and host timelines align")
    p.add_argument("-run-dir", "--run-dir", dest="run_dir",
                   default=d.run_dir,
                   help="write a self-describing run artifact (config, "
                        "env fingerprint, JSONL metrics, telemetry npz, "
                        "trace, result + trajectory fingerprint) into "
                        "this directory; see scripts/compare_runs.py")
    p.add_argument("-log-jsonl", "--log-jsonl", dest="log_jsonl",
                   default=d.log_jsonl,
                   help="append structured JSONL progress records here")
    p.add_argument("-checkpoint-every", "--checkpoint-every",
                   dest="checkpoint_every", type=int, default=0)
    p.add_argument("-checkpoint-dir", "--checkpoint-dir", dest="checkpoint_dir",
                   default="")
    p.add_argument("-resume", "--resume", action="store_true",
                   help="resume from the latest snapshot in -checkpoint-dir")
    p.add_argument("-quiet", "--quiet", action="store_true",
                   help="suppress per-window progress lines")
    p.add_argument("-distributed", "--distributed", action="store_true",
                   help="multi-host SPMD: initialize jax.distributed and "
                        "shard the node axis over every process's devices")
    p.add_argument("-coordinator", "--coordinator", default=d.coordinator,
                   help="jax.distributed coordinator address host:port "
                        "(empty = auto-detect)")
    p.add_argument("-num-processes", "--num-processes", dest="num_processes",
                   type=int, default=d.num_processes)
    p.add_argument("-process-id", "--process-id", dest="process_id",
                   type=int, default=d.process_id)
    p.add_argument("-supervise", "--supervise", action="store_true",
                   help="host-loss supervision (distributed/supervisor.py): "
                        "single-process drillable mode, or with "
                        "-coordinator the real process-spawning supervisor")
    p.add_argument("-workers", "--workers", type=int, default=d.workers,
                   help="worker count under -supervise (logical device "
                        "slices, or spawned processes with -coordinator)")
    p.add_argument("-heartbeat-dir", "--heartbeat-dir", dest="heartbeat_dir",
                   default=d.heartbeat_dir,
                   help="liveness beacon directory "
                        "(default: <checkpoint-dir>/heartbeats)")
    p.add_argument("-heartbeat-timeout-ms", "--heartbeat-timeout-ms",
                   dest="heartbeat_timeout_ms", type=int,
                   default=d.heartbeat_timeout_ms,
                   help="beacon lag before a worker is declared lost")
    p.add_argument("-chaos", "--chaos", default=d.chaos,
                   help="host-loss drill: kill-worker@W[:K] or "
                        "stall-worker@W[:K] (worker W at gossip window K)")
    p.add_argument("-recover-max-stale", "--recover-max-stale",
                   dest="recover_max_stale", type=int,
                   default=d.recover_max_stale,
                   help="refuse recovery from a snapshot more than this "
                        "many windows behind the loss point (0 = no limit)")
    p.add_argument("-run-id", "--run-id", dest="run_id", default=d.run_id,
                   help="checkpoint provenance token (default: generated "
                        "per run; recovery refuses foreign snapshots)")
    p.add_argument("-init-timeout", "--init-timeout", dest="init_timeout_s",
                   type=int, default=d.init_timeout_s,
                   help="jax.distributed.initialize per-attempt timeout "
                        "in seconds (3 retried attempts)")
    return p


def parse_args(argv: Optional[list[str]] = None) -> Config:
    ns = _build_parser().parse_args(argv)
    kw = vars(ns)
    kw["progress"] = not kw.pop("quiet")
    return Config(**kw).validate()


def expected_rounds(cfg: Config) -> int:
    """Analytic upper-ish bound on rounds to 99% for SI push (SURVEY §6):
    log_{1+f(1-d)} N + slack.  Used for buffer sizing and test tolerances."""
    growth = 1.0 + cfg.fanout * (1.0 - cfg.droprate)
    if growth <= 1.0:
        return cfg.max_rounds
    return int(math.log(max(cfg.n, 2)) / math.log(growth)) + 12
