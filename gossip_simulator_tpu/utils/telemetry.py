"""Device-resident telemetry: per-window history without leaving the fast path.

The reference's only observability is six polled atomics printed every 10 ms
(simulator.go:26-31); our windowed driver loop reproduces that surface but
pays one jit dispatch + one device->host stats round-trip per 10 simulated ms
(~2x wall-clock at n=1e7).  This module removes the
observability-vs-speed tradeoff: the bounded device-side while_loops
(epidemic/event `make_run_to_coverage_fn`, overlay `make_bounded_run`) write
one row of counters per poll window into a preallocated device `History`
buffer -- a handful of scalar ops against a window of O(n) work -- and the
host fetches the whole trajectory in ONE transfer at loop exit.

`replay_overlay` / `replay_gossip` then drive the fetched history through the
ordinary ProgressPrinter, producing stdout/JSONL per-window output
byte-identical to the windowed loop's (the golden CLI transcripts enforce
this), so a progress-printing or JSONL-logging run takes the fast path
whenever checkpointing is off.  `TelemetrySession` is the host-side holder a
backend carries: device histories for both phases plus the wall-clock phase
ledger (init / compile / execute / fetch); `TelemetryReport` turns it into
throughput numbers, per-window deltas and the `-telemetry-summary` block.

History rows are int32; the 64-bit total_message pair travels as two
bitcast int32 columns and is reassembled host-side (msg64 convention from
models/state.py).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import numpy as np

# Column layouts (one int32 matrix per phase keeps the per-window write a
# single row scatter instead of one per counter).  The four scenario
# columns (scenario.py) record the per-window fault trajectory -- crash
# waves, reboots, repaired edges, partition-suppressed sends -- on the
# same device-resident ride as the epidemic counters; they are constant 0
# on scenario-less runs and the replay functions never read them, so the
# replayed stdout/JSONL surface is unchanged.
GOSSIP_COLS = ("tick", "received", "msg_hi", "msg_lo", "crashed", "removed",
               "mail_high", "dropped", "overflow", "scen_crashed",
               "recovered", "repaired", "part_dropped", "rumors_done",
               "exchange_inflight_hwm", "relerr_ppb")
OVERLAY_COLS = ("clock", "makeups", "breakups", "dropped")

# Optional trailing per-window blocks of the JSONL `telemetry` record: a
# group is emitted only when the build carries its source columns AND any
# of them is nonzero (all-zero columns would bloat every record), and it
# is emitted whole -- the scenario quartet travels together.  Each entry
# maps the emitted per_window key to its GOSSIP_COLS source column; this
# registry IS the contract scripts/check_telemetry.py validates the
# stream against (hardcoded per-column name checks drifted once per
# added column).
OPTIONAL_BLOCK_GROUPS = (
    (("scen_crashed", "scen_crashed"), ("scen_recovered", "recovered"),
     ("heal_repaired", "repaired"), ("part_dropped", "part_dropped")),
    (("rumors_done", "rumors_done"),),
    (("exchange_inflight_hwm", "exchange_inflight_hwm"),),
    (("relerr_ppb", "relerr_ppb"),),
)

# --- spatial panels (ISSUE 16) ----------------------------------------------
# Per-window spatial panels recorded next to the scalar history and
# fetched in the SAME single transfer: a (windows, groups, KG) group
# panel over the PR-4 scenario contiguous-id ranges (falling back to the
# sharded backend's shard slices when no scenario declares groups), a
# (windows, shards, KS) shard panel, and a (windows, S, S) exchange
# traffic matrix counted inside the routed all_to_all (parallel/
# exchange.py).  npz-only: the replayed stdout/JSONL surface never reads
# them, so a spatial-on/off twin pair stays byte-identical.
#
# Group columns are probe-time gauges over per-node state, chosen so the
# reconciliation invariant is exact (tests/test_spatial.py): summed over
# groups, `received` equals the global received column every window and
# `removed` equals the removed column; `down` is the currently-crashed
# count (== scen_crashed when faults are scenario waves without
# recovery; a cumulative-crash panel would need per-group accumulators
# in every fault site).
SPATIAL_GROUP_COLS = ("received", "down", "removed")
# Shard columns: probe-time mail-ring occupancy high-water (max over
# shards == the global mail_high column), resident informed count (sums
# to the received column), and the exchange counters accumulated inside
# the routed collective (exch_counts layout below) -- send-side overflow
# and valid lanes received off the wire.  relerr_ppb is the pushsum
# eps-check error, pmax-replicated by the sharded step (every shard
# records the same value; per-shard attribution would need the step to
# defer its pmax).
SPATIAL_SHARD_COLS = ("mail_high", "received", "overflow", "relerr_ppb",
                      "exch_rcvd")

# Layout of the per-shard exchange accumulator state leaf (`exch_counts`,
# int32[1, S + 2] on spatial sharded runs, int32[1, 1] placeholder
# otherwise -- the down_since convention): [0, :S] is this shard's
# traffic-matrix row (routed lanes by destination, counted at dispatch
# inside exchange.route_*), [0, S] the valid lanes received off the
# wire, [0, S + 1] the send-side overflow (lanes ranked past the slot
# cap, which never reach a receiver).
def exch_counts_width(spec) -> int:
    return spec.n_shards + 2 if (spec is not None and spec.n_shards > 1) \
        else 1

# Named column indices -- THE way to address a history column (schema v3
# names these in the JSONL header).  Positional literals ("the 14th
# column") drifted once per added column; every reader below and every
# external consumer (bench.py, utils/artifact.py, scripts) goes through
# these maps instead.
GCOL = {name: i for i, name in enumerate(GOSSIP_COLS)}
OCOL = {name: i for i, name in enumerate(OVERLAY_COLS)}


class History(NamedTuple):
    """Device-resident per-window ring: `idx` rows written (keeps counting
    past the capacity so truncation is detectable; writes saturate at the
    last row), `cols` the int32[cap, F] matrix."""

    idx: object  # int32[]
    cols: object  # int32[cap, F]


def empty_history(cap: int, ncols: int) -> History:
    import jax.numpy as jnp

    return History(idx=jnp.zeros((), jnp.int32),
                   cols=jnp.zeros((max(int(cap), 1), ncols), jnp.int32))


def record(hist: History, row) -> History:
    """Append one window's row (list of int32 scalars) device-side."""
    import jax.numpy as jnp

    cap = hist.cols.shape[0]
    vals = jnp.stack([jnp.asarray(v).astype(jnp.int32) for v in row])
    i = jnp.minimum(hist.idx, cap - 1)
    return History(idx=hist.idx + 1, cols=hist.cols.at[i].set(vals))


class Panels(NamedTuple):
    """Device-resident spatial panels, same ring discipline as History
    (the bundle shares History.idx -- panels and scalars are always
    row-aligned)."""

    group: object  # int32[cap, G, KG]
    shard: object  # int32[cap, S, KS]
    traffic: object  # int32[cap, S, S]  cumulative routed-lane counts


class SpatialBundle(NamedTuple):
    """The telemetry carry on spatial runs: the scalar History plus the
    panels.  Threaded through the same `hist` argument of the six
    run-to-coverage fns (backends/base.py treats it opaquely); a
    spatial-off run carries a plain History, so the off path traces the
    pre-spatial program."""

    hist: History
    panels: Panels


class SpatialSpec(NamedTuple):
    """Static panel geometry, hashable (closed over at trace time).
    groups = scenario groups when a scenario declares > 1, else the
    shard count (shard slices ARE contiguous-id groups -- scenario.py's
    group ranges coincide with the sharded backend's slices when groups
    == device count); group_size is the ceil-division id-range width."""

    groups: int
    group_size: int
    n: int
    n_shards: int


def spatial_spec(cfg, n_shards: int = 1):
    """The engine- and session-side gate: None when spatial panels are
    off (the run fns then trace the exact pre-spatial program)."""
    if not cfg.telemetry_spatial_enabled:
        return None
    scen = cfg.scenario_resolved
    g = scen.groups if (scen.active and scen.groups > 1) \
        else max(1, int(n_shards))
    return SpatialSpec(groups=g, group_size=-(-cfg.n // g), n=cfg.n,
                       n_shards=max(1, int(n_shards)))


def empty_panels(cap: int, spec: SpatialSpec) -> Panels:
    import jax.numpy as jnp

    cap = max(int(cap), 1)
    g, s = spec.groups, spec.n_shards
    return Panels(
        group=jnp.zeros((cap, g, len(SPATIAL_GROUP_COLS)), jnp.int32),
        shard=jnp.zeros((cap, s, len(SPATIAL_SHARD_COLS)), jnp.int32),
        traffic=jnp.zeros((cap, s, s), jnp.int32))


def bundle_specs(spec, P):
    """shard_map in/out specs for the telemetry carry: replicated
    History when spatial is off, replicated bundle when on (every panel
    row is psum/all_gather-replicated before the scatter)."""
    hspecs = History(idx=P(), cols=P(None, None))
    if spec is None:
        return hspecs
    return SpatialBundle(hist=hspecs,
                         panels=Panels(group=P(None, None, None),
                                       shard=P(None, None, None),
                                       traffic=P(None, None, None)))


def spatial_probe(st, spec: SpatialSpec, shard_index=0, gather=None,
                  psum=None, relerr=None):
    """One panel row triple (group (G, KG), shard (S, KS), traffic
    (S, S)) from an engine's local state view.  Duck-typed like
    gossip_probe: event/pushsum states carry `flags` + `mail_cnt`, the
    ring engine boolean node arrays + `pending`.  On sharded engines
    `shard_index` is lax.axis_index, `gather` all-gathers over the mesh
    axis and `psum` sums the per-shard group partials; single-device
    callers leave them None (S == 1)."""
    import jax
    import jax.numpy as jnp

    I32 = jnp.int32
    z = jnp.zeros((), I32)
    if hasattr(st, "flags"):
        from gossip_simulator_tpu.models.event import (CRASHED, RECEIVED,
                                                       REMOVED)

        received = (st.flags & RECEIVED) > 0
        down = (st.flags & CRASHED) > 0
        removed = (st.flags & REMOVED) > 0
        high = st.mail_cnt.max().astype(I32)
    else:
        received, down, removed = st.received, st.crashed, st.removed
        high = st.pending.max().astype(I32)
    n_local = received.shape[0]
    vals = jnp.stack([received, down, removed], axis=1).astype(I32)
    # Per-group sums WITHOUT a length-n scatter (segment_sum lowers to a
    # serial scatter-add on CPU -- measured ~200ms/window at 1M, blowing
    # the <=5% overhead budget).  Groups are contiguous equal-width id
    # ranges, so shift the local block to its within-group offset inside
    # a chunk-aligned buffer and reduce with a reshape -- the only
    # scatters left are two O(groups) dynamic_update_slices.
    gsz = spec.group_size
    kg = vals.shape[1]
    n_chunks = -(-n_local // gsz) + 1
    first = jnp.asarray(shard_index, I32) * n_local
    buf = jax.lax.dynamic_update_slice(
        jnp.zeros((n_chunks * gsz, kg), I32), vals, (first % gsz, 0))
    chunk = buf.reshape(n_chunks, gsz, kg).sum(axis=1, dtype=I32)
    group_rows = jax.lax.dynamic_update_slice(
        jnp.zeros((spec.groups + n_chunks, kg), I32), chunk,
        (first // gsz, 0))[:spec.groups]
    if psum is not None:
        group_rows = psum(group_rows)
    received_loc = vals[:, 0].sum(dtype=I32)
    rel = jnp.asarray(relerr, I32) if relerr is not None else z
    s = spec.n_shards
    if s > 1:
        ex = st.exch_counts[0]
        srow = jnp.stack([high, received_loc, ex[s + 1], rel, ex[s]])
        return group_rows, gather(srow), gather(ex[:s])
    srow = jnp.stack([high, received_loc, z, rel, z])
    return group_rows, srow[None, :], jnp.zeros((1, 1), I32)


def record_spatial(b: SpatialBundle, row, group_rows, shard_rows,
                   traffic) -> SpatialBundle:
    """Append one window's scalar row + panel rows at the shared index."""
    import jax.numpy as jnp

    cap = b.hist.cols.shape[0]
    i = jnp.minimum(b.hist.idx, cap - 1)
    return SpatialBundle(
        hist=record(b.hist, row),
        panels=Panels(group=b.panels.group.at[i].set(group_rows),
                      shard=b.panels.shard.at[i].set(shard_rows),
                      traffic=b.panels.traffic.at[i].set(traffic)))


def record_window(hist, row, st=None, spec=None, shard_index=0,
                  gather=None, psum=None, relerr=None):
    """THE per-window recording entry for the six run-to-coverage fns:
    a plain History append when spatial is off (spec None -- byte-
    identical trace to the pre-spatial build), the bundle append with a
    spatial probe when on."""
    if spec is None:
        return record(hist, row)
    g, s, t = spatial_probe(st, spec, shard_index=shard_index,
                            gather=gather, psum=psum, relerr=relerr)
    return record_spatial(hist, row, g, s, t)


def gossip_probe(st, sir: bool, psum=None, pmax=None, rumors: int = 0,
                 inflight_hwm: int = 0, relerr=None):
    """One GOSSIP_COLS row from either epidemic engine's state (duck-typed
    like models/state.in_flight: EventState has the mail ring, SimState the
    pending ring).  `psum`/`pmax` are the sharded engines' cross-shard
    reductions for the per-shard quantities (removed flags, ring occupancy);
    the totals are already psum-replicated by the step functions.  `rumors`
    (static R; 0 = single-rumor) adds the count of rumors that have hit the
    coverage target -- rumor_done is replicated on every engine, so no
    reduction applies.  `inflight_hwm` (static, per engine build) is the
    high-water mark of exchange buffers alive at once on the sharded
    routed path: 0 = no collective in the program (single device /
    non-sharded), 1 = the serial route->drain loop, 2 = the
    double-buffered pipeline (-exchange-pipeline double -- one staged
    drain in flight behind the dispatched all_to_all).  `relerr` is the
    pushsum engines' per-window max relative error vs the true network
    mean, pre-scaled to int32 parts-per-billion (already pmax-replicated
    by the sharded step); None = not a numeric-gossip run, column 0."""
    import jax
    import jax.numpy as jnp

    I32 = jnp.int32
    z = jnp.zeros((), I32)
    if hasattr(st, "flags"):  # event engine
        from gossip_simulator_tpu.models.event import REMOVED

        removed = ((st.flags & REMOVED) > 0).sum(dtype=I32) if sir else z
        high = st.mail_cnt.max().astype(I32)
        dropped = st.mail_dropped
    else:
        removed = st.removed.sum(dtype=I32) if sir else z
        # Per-(slot, node) arrival-count high-water -- the ring engine's
        # analog of the fullest mailbox.
        high = st.pending.max().astype(I32)
        dropped = z
    if psum is not None:
        removed = psum(removed)
    if pmax is not None:
        high = pmax(high)
    msg = jax.lax.bitcast_convert_type(st.total_message, I32)
    rdone = (st.rumor_done[:rumors] >= 0).sum(dtype=I32) if rumors else z
    return [st.tick, st.total_received, msg[0], msg[1], st.total_crashed,
            removed, high, dropped, st.exchange_overflow,
            st.scen_crashed, st.scen_recovered, st.heal_repaired,
            st.part_dropped, rdone, jnp.asarray(inflight_hwm, I32),
            jnp.asarray(relerr, I32) if relerr is not None else z]


def overlay_probe(st):
    """One OVERLAY_COLS row from either overlay engine's state (the
    tick-faithful engine carries `tick`, the rounds engine `round`; the
    window counters are already global/replicated on both)."""
    clock = st.tick if hasattr(st, "tick") else st.round
    return [clock, st.win_makeups, st.win_breakups, st.mailbox_dropped]


def gossip_history_cap(cfg) -> int:
    """Phase-2 window capacity: every engine's poll window advances at least
    WINDOW_MS ticks in ticks mode (event.poll_window_steps rounds UP) and
    one round in rounds mode, so ceil(max_rounds / window) bounds the rows."""
    window = 1 if cfg.effective_time_mode == "rounds" else 10
    return max(1, -(-cfg.max_rounds // window) + 2)


def fetch_history(hist) -> Optional[dict]:
    """ONE device->host transfer of a whole history buffer.  A spatial
    bundle rides the same single device_get: the snapshot dict gains
    `spatial_group` / `spatial_shard` / `spatial_traffic` arrays trimmed
    to the recorded window count."""
    if hist is None:
        return None
    import jax

    if isinstance(hist, SpatialBundle):
        idx, cols, pg, ps, pt = jax.device_get(
            (hist.hist.idx, hist.hist.cols, hist.panels.group,
             hist.panels.shard, hist.panels.traffic))
    else:
        idx, cols = jax.device_get((hist.idx, hist.cols))
        pg = ps = pt = None
    recorded = int(idx)
    cols = np.asarray(cols)
    out = {"count": min(recorded, cols.shape[0]), "recorded": recorded,
           "truncated": recorded > cols.shape[0], "cols": cols}
    if pg is not None:
        count = out["count"]
        out["spatial_group"] = np.asarray(pg)[:count]
        out["spatial_shard"] = np.asarray(ps)[:count]
        out["spatial_traffic"] = np.asarray(pt)[:count]
    return out


def host_history(rows: list) -> Optional[dict]:
    """Same shape as fetch_history for host-side recorded rows (the split
    overlay round's host loop)."""
    if not rows:
        return None
    cols = np.asarray(rows, np.int32).reshape(len(rows), -1)
    return {"count": len(rows), "recorded": len(rows), "truncated": False,
            "cols": cols}


# --- replay -----------------------------------------------------------------

def replay_overlay(printer, hist: Optional[dict], clock_scale: float,
                   quiesced: bool = True) -> None:
    """Re-emit the phase-1 per-window lines exactly as the windowed loop
    would have: the quiescing window itself is never printed
    (simulator.go:227-230 prints only when *not* stabilizing)."""
    if not hist:
        return
    cols, count = hist["cols"], hist["count"]
    last = count - 1 if quiesced else count
    for i in range(max(0, last)):
        # clock_scale 1.0 (faithful ticks) reproduces float(tick) exactly;
        # the rounds engine's round * mean_delay is the windowed loop's
        # identical float expression.
        printer.overlay_window(int(cols[i, OCOL["breakups"]]),
                               int(cols[i, OCOL["makeups"]]),
                               float(cols[i, OCOL["clock"]]) * clock_scale)


def replay_gossip(printer, hist: Optional[dict], n: int) -> None:
    """Re-emit the phase-2 coverage lines: same float math as the windowed
    driver loop (coverage = int received / int n, pct rounded to 4)."""
    if not hist:
        return
    cols = hist["cols"]
    for i in range(hist["count"]):
        pct = (int(cols[i, GCOL["received"]]) / n if n else 0.0) * 100.0
        printer.coverage_window(round(pct, 4), float(cols[i, GCOL["tick"]]))


def _msg64_col(cols: np.ndarray) -> np.ndarray:
    """Reassemble the bitcast [hi, lo] int32 column pair into uint64."""
    hi = GCOL["msg_hi"]
    pair = cols[:, hi:hi + 2].astype(np.int32).view(np.uint32) \
        .astype(np.uint64)
    return (pair[:, 0] << np.uint64(32)) | pair[:, 1]


# --- host-side session ------------------------------------------------------

class TelemetrySession:
    """Per-stepper holder: device histories for both phases plus the
    wall-clock phase ledger.  `compile_s` is the host time of the dispatch
    of each phase's first bounded device call -- the jitted call up to its
    return: trace, lowering, XLA compile or persistent-cache load, enqueue
    (a warm cache makes it mostly the first three, not a compile).
    `execute_s` is the rest of that call, waiting on the device, and every
    later call whole."""

    def __init__(self, cfg, n_shards: int = 1):
        self.cfg = cfg
        self.n_shards = n_shards  # panel geometry on spatial runs
        self.phases: dict[str, float] = {}
        self._gossip: Optional[History] = None
        self._overlay: Optional[History] = None
        self._overlay_host_rows: list = []
        self._gossip_calls = 0
        self._overlay_calls = 0
        self._gossip_fetched: Optional[dict] = None
        self._overlay_fetched: Optional[dict] = None

    # --- phase ledger ---------------------------------------------------
    def add_phase(self, key: str, seconds: float) -> None:
        self.phases[key] = self.phases.get(key, 0.0) + seconds

    def _tally_call(self, first: bool, dispatch_s: float,
                    seconds: float) -> None:
        if first:
            self.add_phase("compile_s", dispatch_s)
            seconds -= dispatch_s
        self.add_phase("execute_s", seconds)

    def tally_gossip_call(self, dispatch_s: float, seconds: float) -> None:
        """One bounded phase-2 call: `seconds` whole, `dispatch_s` of it
        until the jitted call returned."""
        self._tally_call(self._gossip_calls == 0, dispatch_s, seconds)
        self._gossip_calls += 1

    def tally_overlay_call(self, dispatch_s: float, seconds: float) -> None:
        """One bounded phase-1 call (see tally_gossip_call)."""
        self._tally_call(self._overlay_calls == 0, dispatch_s, seconds)
        self._overlay_calls += 1

    # --- phase-2 history ------------------------------------------------
    def begin_gossip(self):
        if self._gossip is None:
            cap = gossip_history_cap(self.cfg)
            hist = empty_history(cap, len(GOSSIP_COLS))
            spec = spatial_spec(self.cfg, self.n_shards)
            self._gossip = hist if spec is None else \
                SpatialBundle(hist=hist, panels=empty_panels(cap, spec))
        return self._gossip

    def end_gossip(self, hist) -> None:
        self._gossip = hist

    def reset_gossip(self) -> None:
        """Drop phase-2 history (a reset_state rerun records afresh)."""
        self._gossip = None
        self._gossip_fetched = None

    def gossip_snapshot(self) -> Optional[dict]:
        if self._gossip_fetched is None and self._gossip is not None:
            self._gossip_fetched = self._fetch(self._gossip)
        return self._gossip_fetched

    def _fetch(self, hist) -> Optional[dict]:
        import time

        from gossip_simulator_tpu.utils import trace as _trace

        t0 = time.perf_counter()
        with _trace.span("telemetry.fetch", cat="device"):
            out = fetch_history(hist)
        self.add_phase("fetch_s", time.perf_counter() - t0)
        return out

    # --- phase-1 history ------------------------------------------------
    def begin_overlay(self, cap: int) -> History:
        if self._overlay is None:
            self._overlay = empty_history(cap, len(OVERLAY_COLS))
        return self._overlay

    def end_overlay(self, hist: History) -> None:
        self._overlay = hist

    def overlay_host_row(self, row) -> None:
        """Host-side recording for the split-round overlay (its round is a
        host-driven call sequence; the per-round device_get it already pays
        carries the counters)."""
        self._overlay_host_rows.append([int(v) for v in row])

    def overlay_snapshot(self) -> Optional[dict]:
        if self._overlay_fetched is None:
            if self._overlay is not None:
                self._overlay_fetched = self._fetch(self._overlay)
            elif self._overlay_host_rows:
                self._overlay_fetched = host_history(self._overlay_host_rows)
        return self._overlay_fetched


# --- report -----------------------------------------------------------------

@dataclasses.dataclass
class TelemetryReport:
    """Host-side view of one run's telemetry: phase ledger, throughput and
    the per-window trajectory (what the reference never had)."""

    n: int
    phases: dict
    overlay: Optional[dict] = None
    gossip: Optional[dict] = None
    overlay_clock_scale: float = 1.0

    def summary(self) -> dict:
        out: dict = {"phases_s": {k: round(v, 6)
                                  for k, v in sorted(self.phases.items())}}
        execute = self.phases.get("execute_s", 0.0) \
            + self.phases.get("compile_s", 0.0)
        if self.overlay:
            out["overlay_windows"] = self.overlay["count"]
            if self.overlay["truncated"]:
                out["overlay_truncated"] = True
        if self.gossip:
            cols, count = self.gossip["cols"], self.gossip["count"]
            out["gossip_windows"] = count
            if self.gossip["truncated"]:
                out["gossip_truncated"] = True
            if count:
                c = cols[:count]

                def col(name: str) -> np.ndarray:
                    return c[:, GCOL[name]]

                ticks = int(col("tick")[-1])
                msg = _msg64_col(c)
                out["sim_ticks"] = ticks
                out["total_message"] = int(msg[-1])
                if execute > 0:
                    out["node_updates_per_sec"] = round(
                        self.n * ticks / execute, 1)
                    out["messages_per_sec"] = round(int(msg[-1]) / execute, 1)
                per = {
                    "tick": col("tick").tolist(),
                    "received": col("received").tolist(),
                    "message": [int(v) for v in msg],
                    "crashed": col("crashed").tolist(),
                    "removed": col("removed").tolist(),
                    "mail_high": col("mail_high").tolist(),
                    "dropped": col("dropped").tolist(),
                    "overflow": col("overflow").tolist(),
                }
                # Optional trailing blocks, registry-driven (scenario
                # quartet only when a scenario ran, rumors_done only when
                # rumors completed, inflight depth only when a routed
                # exchange ran, relerr only on pushsum runs): a group is
                # emitted whole when the build carries its columns and
                # any is nonzero.
                for grp in OPTIONAL_BLOCK_GROUPS:
                    srcs = [src for _, src in grp]
                    have = cols.shape[1] > max(GCOL[s] for s in srcs)
                    if have and bool(np.stack([col(s)
                                               for s in srcs]).any()):
                        for key, src in grp:
                            per[key] = col(src).tolist()
                out["per_window"] = per
                out["deltas"] = {
                    "received": np.diff(col("received"),
                                        prepend=0).tolist(),
                    "message": np.diff(msg.astype(np.int64),
                                       prepend=np.int64(0)).tolist(),
                }
        return out

    def summary_block(self) -> str:
        """The `-telemetry-summary` end-of-run stdout block."""
        s = self.summary()
        ph = s.get("phases_s", {})
        lines = ["\n=== Telemetry ==="]
        lines.append("phases: " + " ".join(
            f"{k[:-2]} {v:.3f}s" for k, v in ph.items()) if ph
            else "phases: (none recorded)")
        if "overlay_windows" in s:
            lines.append(f"overlay: {s['overlay_windows']} windows")
        if "gossip_windows" in s:
            g = f"gossip: {s['gossip_windows']} windows"
            if "sim_ticks" in s:
                g += f", {s['sim_ticks']} simulated ms"
            lines.append(g)
        if "node_updates_per_sec" in s:
            lines.append(f"throughput: {s['node_updates_per_sec']:g} "
                         f"node-updates/s, {s['messages_per_sec']:g} "
                         "messages/s")
        return "\n".join(lines)
