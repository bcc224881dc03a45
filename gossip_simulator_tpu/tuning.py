"""Registered tunable constants and per-platform tuning tables (ISSUE 12).

Every hot path in the TPU-native engine is governed by constants picked
from profiling sessions at a handful of scales (the drain-chunk sweeps,
the 64k overlay delivery optimum, the Chernoff pad, ...).  This module
makes that hand-tuned surface a declared, searchable parameter space:

* ``REGISTRY`` -- every tunable, with its home module, bit-identical
  default, legal candidate ladder, provenance artifact and the workload
  shapes it affects.  Call sites read constants through :func:`value`
  instead of a literal; with no table and no override the returned value
  IS the old constant, so a registry-wired build is bit-identical to the
  constants it replaced (pinned by tests/test_autotune.py).
* ``SPACES`` -- named sweep spaces for ``scripts/autotune.py`` (which
  tunables to search together and the workload shape that exercises
  them).  The ``chunk_ladder`` space folds in the deleted
  ``scripts/chunk_sweep.py`` / ``chunk_sweep_f6.py`` candidate ladders.
* Tuning tables -- committed JSON (``TUNING_TABLE.json`` at the repo
  root) keyed by (platform, device_kind, scale band), plus a workload
  shape for entries carrying gate-validated tunables.  ``Config``
  consults EVERY matching entry at build time (entries from different
  spaces coexist in one band without shadowing each other); the
  resolution order per tunable is

      explicit CLI flag (checked at the call site, e.g. -compact-chunk,
          -event-chunk, -event-slot-cap)
    > autotune override context (scripts/autotune.py candidates)
    > active tuning-table entry (-tuning-table auto|off|PATH)
    > registered / module default.

The ``+``-joined ids of every active entry (or ``"defaults"``) are
stamped into ``Config.resolved_gates()`` and hence every run-dir
``config.json`` and terminal ``result`` record, so
``scripts/compare_runs.py`` can name a table mismatch as the first
divergence suspect.

Correctness contract: ``scripts/autotune.py`` rejects ANY candidate
whose run-dir trajectory fingerprint differs from the default-constants
twin (the neutrality gate -- perf search can never change results).
What a passed gate is worth differs per tunable, so each one declares a
``persist`` class:

* ``"contract"`` -- trajectory-neutral at ANY shape by construction
  (chunk widths under the rank-continuation delivery contract, the
  bit-identical rank-path width): a gate pass is confirmation, and a
  winner persists band-wide.
* ``"gated"`` -- trajectory-affecting in principle (the event drain
  chunk: a chunk-boundary re-broadcast uses the first-encountered
  delivery tick, models/event.py), so a gate pass at one shape does NOT
  transfer.  A winner persists only after the gate also passes at extra
  probe shapes (other seeds / other n in the band), and its entry
  carries the swept workload shape (:func:`workload_shape`): the values
  apply only to runs matching that shape, never band-wide.
* ``"never"`` -- capacity or semantics constants (slot_headroom,
  chernoff_pad, spill_margin, the Pallas PRNG block height): sweeps are
  timing evidence only, nothing is ever persisted.

This module imports no jax at import time; platform resolution is lazy
(first table lookup), keeping ``Config.validate()`` jax-free.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import importlib
import json
import os
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED_TABLE = os.path.join(REPO_ROOT, "TUNING_TABLE.json")
# Schema 2: entries may carry a "shape" key (required for entries whose
# values include persist="gated" tunables) and several entries can be
# active at once (one per space).
TABLE_SCHEMA = 2

# Scale bands keying table entries: a winner measured at one n applies
# to the band it was swept in, not the whole axis (per-op floors vs
# element-count costs cross over with n -- the drain-chunk sweeps put
# the 1e7 and 1e8 optima 2-8x apart).  Bands follow the repo's own
# banded constants (1M ticks-auto / 32M memory bands sit inside them).
SCALE_BANDS = ((1_048_576, "<=1m"), (16_777_216, "1m-16m"),
               (67_108_864, "16m-64m"), (134_217_728, "64m-128m"))


def scale_band(n: int) -> str:
    for lim, name in SCALE_BANDS:
        if n <= lim:
            return name
    return ">128m"


@dataclasses.dataclass(frozen=True)
class Tunable:
    """One registered constant: where it lives, what it may legally be,
    and how a swept winner may persist (see module docstring)."""

    name: str  # "module.constant", the registry key
    module: str  # home module (dotted path, for docs/provenance)
    default: float  # bit-identical to the constant it replaced
    candidates: tuple  # legal sweep ladder (default always included)
    kind: type  # int or float
    persist: str  # "contract" | "gated" | "never" (module docstring)
    provenance: str  # PROFILE_*/BENCH_* artifact the default came from
    shapes: str  # workload shapes the constant affects
    cfg_field: str = ""  # explicit Config field that outranks everything
    # "module:function" computing the DERIVED constant the tunable feeds
    # at a given cfg (e.g. event.drain_chunk).  The autotuner compares it
    # under override vs default and skips candidates that cannot change
    # the compiled program at the swept shape ("unexercised" -- their
    # timing deltas would be pure noise).  Empty = the value itself.
    effect: str = ""


PERSIST_CLASSES = ("contract", "gated", "never")
REGISTRY: dict[str, Tunable] = {}


def _register(name: str, module: str, default, candidates, kind,
              persist: str, provenance: str, shapes: str,
              cfg_field: str = "", effect: str = "") -> None:
    assert persist in PERSIST_CLASSES, (name, persist)
    cands = tuple(sorted(set(tuple(candidates) + (default,))))
    REGISTRY[name] = Tunable(name=name, module=module, default=default,
                             candidates=cands, kind=kind, persist=persist,
                             provenance=provenance, shapes=shapes,
                             cfg_field=cfg_field, effect=effect)


# --- the hand-tuned constant surface (defaults bit-identical) --------------
_register("overlay.delivery_chunk_base", "gossip_simulator_tpu.models.overlay",
          65_536, (32_768, 65_536, 131_072, 262_144), int, "contract",
          "PROFILE_OVERLAY.json",
          "rounds-overlay mailbox delivery (v5e full-construction sweep "
          "optimum at n=1e6)", cfg_field="compact_chunk")
_register("overlay.delivery_chunk_cap", "gossip_simulator_tpu.models.overlay",
          1_048_576, (524_288, 1_048_576, 2_097_152), int, "contract",
          "PROFILE_OVERLAY.json",
          "rounds-overlay delivery n/128 ramp ceiling (>=128M rows)",
          cfg_field="compact_chunk")
_register("overlay.adaptive_chunk_max", "gossip_simulator_tpu.models.overlay",
          8_388_608, (2_097_152, 4_194_304, 8_388_608, 16_777_216), int,
          "contract", "PROFILE_OVERLAY.json",
          "fattest rung of the occupancy-adaptive hosted-chunk ladder "
          "(split-round band, >=32M rows)")
_register("overlay.spill_margin", "gossip_simulator_tpu.models.overlay",
          1.6, (1.2, 1.6, 2.0, 2.5), float, "never",
          "unrecorded: pre-PR-21 TPU sweep, re-measure",
          "static-boot burst spill sizing (cap-8 band); too small drops "
          "messages -- capacity, not chunking, so never table-persisted")
_register("overlay_ticks.delivery_chunk_cap",
          "gossip_simulator_tpu.models.overlay_ticks",
          2_097_152, (1_048_576, 2_097_152, 4_194_304), int, "contract",
          "PROFILE_OVERLAY.json",
          "ticks-overlay slot-drain chunk ceiling (re-swept 2026-07-31 "
          "at 10M)", cfg_field="compact_chunk")
_register("exchange.rank_max_shards",
          "gossip_simulator_tpu.parallel.exchange",
          16, (8, 16, 32, 64), int, "contract",
          "PROFILE_EXCHANGE.json",
          "widest mesh served by the sort-free one-hot bucketing rank "
          "(both paths bit-identical; pinned by test_sharded)")
_register("exchange.chernoff_pad", "gossip_simulator_tpu.parallel.exchange",
          8, (6, 8, 10, 12), int, "never",
          "PROFILE_EXCHANGE.json",
          "wire-cap pad multiplier (pad = max(64, k*sqrt(mean))); smaller "
          "raises overflow odds -- capacity, never table-persisted")
_register("exchange.pipeline_depth",
          "gossip_simulator_tpu.parallel.event_sharded",
          2, (1, 2), int, "contract",
          "PROFILE_EXCHANGE.json",
          "staged exchange buffers under -exchange-pipeline double "
          "(2 = drain one batch behind the all_to_all, 1 = the serial "
          "schedule; only the append is deferred, so both are "
          "bit-identical -- pinned by test_sharded)")
_register("exchange.pipeline_chunk",
          "gossip_simulator_tpu.parallel.event_sharded",
          0, (0, 65_536, 131_072, 262_144), int, "contract",
          "PROFILE_EXCHANGE.json",
          "per-buffer staged emission-batch width cap under the "
          "pipelined exchange (0 = inherit sender_compaction_cap); "
          "batch boundaries are trajectory-free in the zero-overflow "
          "regime (narrow_tail_cap's envelope)")
_register("event.slot_headroom", "gossip_simulator_tpu.models.event",
          1.5, (1.25, 1.5, 2.0), float, "never",
          "BENCH_SELF_r05.json",
          "event mail-ring slot-cap skew headroom; too small overflows "
          "(counted, and the neutrality gate rejects it) -- capacity, "
          "never table-persisted", cfg_field="event_slot_cap",
          effect="gossip_simulator_tpu.models.event:drain_geometry")
# The four drain-chunk knobs are persist="gated", NOT contract-neutral:
# a window draining in multiple chunks re-broadcasts a boundary-spanning
# node from its first-ENCOUNTERED delivery tick (models/event.py module
# docstring), so a different effective chunk can move the trajectory.
# The gate catches that at the swept shape; persistence additionally
# requires cross-shape probe passes and shape-keyed table entries.
_register("event.drain_chunk_floor", "gossip_simulator_tpu.models.event",
          131_072, (32_768, 65_536, 131_072, 262_144, 524_288), int, "gated",
          "BENCH_SELF_r03.json",
          "event drain-chunk auto ramp floor (dominant term below "
          "n ~ 16M)", cfg_field="event_chunk",
          effect="gossip_simulator_tpu.models.event:drain_geometry")
_register("event.drain_chunk_hi", "gossip_simulator_tpu.models.event",
          1_048_576, (262_144, 524_288, 1_048_576, 2_097_152), int, "gated",
          "BENCH_SELF_r05.json",
          "event drain-chunk ceiling, mean_degree/4 >= 1.5 (the fanout-6 "
          "ladder scripts/chunk_sweep_f6.py swept)", cfg_field="event_chunk",
          effect="gossip_simulator_tpu.models.event:drain_geometry")
_register("event.drain_chunk_hi_lowdeg", "gossip_simulator_tpu.models.event",
          524_288, (524_288, 1_048_576, 2_097_152, 4_194_304), int, "gated",
          "BENCH_SELF_r03.json",
          "event drain-chunk ceiling, low-degree branch (the fanout-3 "
          "ladder scripts/chunk_sweep.py swept)", cfg_field="event_chunk",
          effect="gossip_simulator_tpu.models.event:drain_geometry")
_register("event.drain_chunk_hi_suppress",
          "gossip_simulator_tpu.models.event",
          4_194_304, (1_048_576, 2_097_152, 4_194_304, 8_388_608), int,
          "gated", "unrecorded: pre-PR-21 TPU sweep, re-measure",
          "event drain-chunk ceiling under duplicate suppression (1e8 "
          "fanout-6 sweep 2026-07-31)", cfg_field="event_chunk",
          effect="gossip_simulator_tpu.models.event:drain_geometry")
_register("pallas_graph.block_rows", "gossip_simulator_tpu.ops.pallas_graph",
          512, (256, 512, 1024, 2048), int, "never",
          "PALLAS_VALIDATION.json",
          "Pallas graph-generator grid block; NOT neutral: the TPU PRNG "
          "seeds per block (row0 // block + blk), so a different block "
          "height generates a different graph -- the gate always rejects "
          "alternatives")
_register("config.overlay_ticks_auto_max", "gossip_simulator_tpu.config",
          10_000_000, (1_000_000, 10_000_000), int, "never",
          "unrecorded: pre-PR-21 TPU sweep, re-measure",
          "overlay_mode auto band: switches the phase-1 engine (true vs "
          "estimated stabilization clock) -- semantics, never "
          "table-persisted")


@dataclasses.dataclass(frozen=True)
class Space:
    """One named sweep: the tunables searched together and the workload
    shape (a Config-kwargs dict scripts/autotune.py completes with n and
    seed) that exercises them."""

    name: str
    tunables: tuple
    workload: dict
    doc: str
    tpu_only: bool = False


SPACES: dict[str, Space] = {
    "chunk_ladder": Space(
        name="chunk_ladder",
        tunables=("event.drain_chunk_floor", "event.drain_chunk_hi",
                  "event.drain_chunk_hi_lowdeg",
                  "event.drain_chunk_hi_suppress"),
        workload=dict(fanout=6, graph="kout", backend="jax", crashrate=0.0,
                      coverage_target=0.95, max_rounds=3000),
        doc="Event-engine drain chunk (folds the deleted "
            "scripts/chunk_sweep.py fanout-3 and chunk_sweep_f6.py "
            "fanout-6 ladders; only tunables the workload shape actually "
            "reaches are swept)"),
    "overlay_chunk": Space(
        name="overlay_chunk",
        tunables=("overlay.delivery_chunk_base", "overlay.delivery_chunk_cap",
                  "overlay.adaptive_chunk_max",
                  "overlay_ticks.delivery_chunk_cap"),
        workload=dict(graph="overlay", backend="jax", crashrate=0.001,
                      coverage_target=0.95, max_rounds=3000),
        doc="Overlay delivery chunk ladders (rounds engine base/cap, "
            "adaptive rung ceiling, ticks drain cap)"),
    "exchange": Space(
        name="exchange",
        tunables=("exchange.rank_max_shards", "exchange.chernoff_pad"),
        workload=dict(fanout=6, graph="kout", backend="sharded",
                      crashrate=0.0, coverage_target=0.95, max_rounds=3000),
        doc="Sharded exchange rank path and wire-cap pad"),
    "event_caps": Space(
        name="event_caps",
        tunables=("event.slot_headroom",),
        workload=dict(fanout=6, graph="kout", backend="jax", crashrate=0.0,
                      coverage_target=0.95, max_rounds=3000),
        doc="Event mail-ring capacity headroom (timing evidence only; "
            "never table-persisted)"),
    "block_shapes": Space(
        name="block_shapes",
        tunables=("pallas_graph.block_rows",),
        workload=dict(fanout=6, graph="kout", backend="jax", crashrate=0.0,
                      coverage_target=0.95, max_rounds=3000, pallas=True),
        doc="Pallas graph-generator block height (TPU only: the gate "
            "rejects every alternative by construction -- the sweep "
            "documents the cost of the 512 default, it cannot move it)",
        tpu_only=True),
}


# --- resolution ------------------------------------------------------------
# Autotune candidate overrides: process-global so they reach cfg-less
# call sites (route_multi's auto rank path, chernoff_cap, the pallas
# graph wrappers) during a candidate's build+run.
_OVERRIDES: dict[str, float] = {}
# Ambient config stack: driver.run_simulation pushes its cfg so cfg-less
# call sites resolve the active tuning table too.
_AMBIENT: list = []


@contextlib.contextmanager
def override(values: dict):
    """Apply candidate values for the dynamic extent (scripts/autotune.py
    only -- production resolution goes through tables).  Unknown names
    raise; values are coerced to the tunable's kind."""
    coerced = {}
    for name, v in values.items():
        t = REGISTRY.get(name)
        if t is None:
            raise KeyError(f"unknown tunable {name!r} "
                           f"(registered: {sorted(REGISTRY)})")
        coerced[name] = t.kind(v)
    saved = dict(_OVERRIDES)
    _OVERRIDES.update(coerced)
    try:
        yield
    finally:
        _OVERRIDES.clear()
        _OVERRIDES.update(saved)


@contextlib.contextmanager
def ambient(cfg):
    """Make `cfg` the table-resolution context for cfg-less call sites
    (driver.run_simulation wraps each run in this)."""
    _AMBIENT.append(cfg)
    try:
        yield
    finally:
        _AMBIENT.pop()


# The Config fields that pin a table entry's workload shape (raw field
# values, not resolved properties: deterministic, jax-free, and JSON
# round-trip stable).  n and seed are deliberately absent -- the scale
# band covers n, and the cross-shape probe gate in scripts/autotune.py
# varies exactly those two axes before a gated winner may persist.
SHAPE_FIELDS = ("backend", "engine", "graph", "protocol", "fanout", "fanin",
                "delaylow", "delayhigh", "crashrate", "rumors",
                "dup_suppress")


def workload_shape(cfg) -> dict:
    """The shape key stamped into (and matched against) table entries
    carrying persist="gated" tunables."""
    return {f: getattr(cfg, f) for f in SHAPE_FIELDS}


def shape_digest(shape: dict) -> str:
    """Short stable digest of a shape key (entry-id component, so two
    sweeps of the same space at different workloads coexist)."""
    raw = json.dumps(shape, sort_keys=True).encode()
    return hashlib.sha256(raw).hexdigest()[:8]


def effective_value(name: str, cfg):
    """The derived constant the tunable actually feeds at `cfg` (the
    registered ``effect`` function, e.g. event.drain_chunk), or the
    resolved value itself when no effect is declared.  The autotuner
    compares this under override vs default: a candidate that cannot
    change it at the swept shape ran the identical program, so its
    timing delta is noise and its neutrality verdict vacuous."""
    t = REGISTRY[name]
    if not t.effect:
        return value(name, cfg)
    mod_name, _, fn_name = t.effect.partition(":")
    return getattr(importlib.import_module(mod_name), fn_name)(cfg)


def table_path(cfg) -> Optional[str]:
    """Resolve -tuning-table: "off" -> None, "auto" -> the committed
    table when present, else the explicit path."""
    sel = getattr(cfg, "tuning_table", "auto")
    if sel == "off":
        return None
    if sel == "auto":
        return COMMITTED_TABLE if os.path.exists(COMMITTED_TABLE) else None
    return sel


_TABLE_CACHE: dict = {}


def load_table(path: str) -> dict:
    """Read + sanity-check a tuning table (cached per (path, mtime))."""
    key = (path, os.stat(path).st_mtime_ns)
    if key in _TABLE_CACHE:
        return _TABLE_CACHE[key]
    with open(path) as fh:
        doc = json.load(fh)
    if doc.get("schema") != TABLE_SCHEMA:
        raise ValueError(f"{path}: tuning-table schema "
                         f"{doc.get('schema')!r} != {TABLE_SCHEMA}")
    for e in doc.get("entries", ()):
        for field in ("id", "platform", "scale_band", "values"):
            if field not in e:
                raise ValueError(f"{path}: entry missing {field!r}: {e}")
        gated = [k for k in e["values"]
                 if k in REGISTRY and REGISTRY[k].persist == "gated"]
        if gated and "shape" not in e:
            # A gated value with no shape key would apply band-wide --
            # exactly the transfer the persist taxonomy forbids.  Failing
            # the load degrades every consumer to defaults (entries_for
            # swallows the error), never to a mis-applied constant.
            raise ValueError(f"{path}: entry {e['id']!r} carries gated "
                             f"tunables {gated} without a workload shape")
    _TABLE_CACHE.clear()  # one live table per path in practice
    _TABLE_CACHE[key] = doc
    return doc


_PLATFORM_CACHE: Optional[tuple[str, str]] = None


def _platform() -> tuple[str, str]:
    """(backend_platform, device_kind) -- the env.json fingerprint's
    fields a table entry keys on.  Lazy jax import (post-setup paths
    only; Config.validate() never reaches here); cached, since every
    tunable read resolves it."""
    global _PLATFORM_CACHE
    if _PLATFORM_CACHE is None:
        import jax

        devs = jax.devices()
        kind = getattr(devs[0], "device_kind", "") if devs else ""
        _PLATFORM_CACHE = (jax.default_backend(), str(kind))
    return _PLATFORM_CACHE


def entries_for(cfg) -> list[dict]:
    """ALL table entries matching this config's platform + scale band
    (+ workload shape, for entries carrying one), sorted by id.  Entries
    from different spaces coexist -- values are merged across them, not
    shadowed by whichever happens to match first.  Any resolution error
    returns [] (a tuning table must never be able to fail a run that
    would run on defaults)."""
    try:
        path = table_path(cfg)
        if path is None:
            return []
        doc = load_table(path)
        platform, kind = _platform()
        band = scale_band(cfg.n)
        shape = None
        out = []
        for e in doc.get("entries", ()):
            if e["platform"] != platform or e["scale_band"] != band:
                continue
            want_kind = e.get("device_kind", "")
            if want_kind and want_kind != kind:
                continue
            if "shape" in e:
                if shape is None:
                    shape = workload_shape(cfg)
                if e["shape"] != shape:
                    continue
            out.append(e)
        return sorted(out, key=lambda e: e["id"])
    except Exception:
        return []


def entry_for(cfg) -> Optional[dict]:
    """First matching entry or None (driver banner convenience; value()
    and entry_id() merge across entries_for)."""
    es = entries_for(cfg)
    return es[0] if es else None


def entry_id(cfg) -> str:
    """The "+"-joined ids of every active tuning-table entry, or
    "defaults".  Never raises -- stamped by Config.resolved_gates() into
    every artifact, so compare_runs attributes the full constant set."""
    es = entries_for(cfg)
    return "+".join(e["id"] for e in es) if es else "defaults"


def value(name: str, cfg=None, default=None):
    """Resolve one tunable (see module docstring for the order).  The
    explicit-CLI-flag rung lives at the call site (e.g. delivery_chunk
    checks cfg.compact_chunk first), mirroring how those overrides
    already short-circuit the constants.  `default`, when given, stands
    in for the registered default so monkeypatched module globals (the
    SPILL_CAP/ADAPTIVE_CHUNK_MAX test pattern) keep working; cfg=None
    call sites fall back to the ambient config pushed by the driver."""
    t = REGISTRY[name]
    if name in _OVERRIDES:
        return _OVERRIDES[name]
    c = cfg if cfg is not None else (_AMBIENT[-1] if _AMBIENT else None)
    if c is not None:
        for e in entries_for(c):
            if name not in e.get("values", {}):
                continue
            if t.persist == "gated" and "shape" not in e:
                # Belt under the load_table check: a gated value only
                # ever applies from a shape-matched entry.
                continue
            return t.kind(e["values"][name])
    return t.default if default is None else default


def registry_rows() -> list[dict]:
    """Registry as plain dicts (README generator / tests)."""
    return [dataclasses.asdict(t) for t in REGISTRY.values()]
