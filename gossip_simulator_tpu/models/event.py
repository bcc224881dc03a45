"""Event-list epidemic engine: cost O(arrivals), not O(n x ticks).

The ring engine (models/epidemic.py) stores arrival *counts* per (slot, node)
-- every tick drains an n-length row, so a 280-tick run at n=1e7 pays 280
O(n) passes even though only ~24M messages ever exist.  This engine stores
the messages themselves -- the TPU-native analog of the reference's per-node
mailbox channels (simulator.go:51-54), batched by delivery time.

Batching granularity is a WINDOW of B = min(10, delaylow) ticks: every
network delay is >= delaylow >= B, so a message delivered inside a window
cannot cause another delivery in the same window -- the whole window drains
as one chunked batch with zero intra-batch causality.  (B collapses to 1 for
sub-window delays, recovering per-tick processing.)  Per-op dispatch
overhead dominates on this platform (each fusion-breaking op costs ~2-5ms
regardless of 16k-256k size), so one batch per window instead of ten is the
difference between the event engine winning and losing to the ring engine.

Mail ring: `mail_ids[dw, cap]` holds PACKED entries `dst * B + tick_off`
(delivery tick within the window), `mail_cnt[dw]` the counts.
Reservations are exact-size, so every entry within a slot's count is a
live message (or SIR trigger) -- the `n * B` sentinel appears only as the
drain's fill for positions beyond the count.  Draining sorts each chunk by
(id, crash-fired-first, tick_off): a node's entries become one contiguous
run whose FIRST element answers everything -- did any crash draw fire, and
(if not) the earliest delivery tick, which seeds the re-broadcast delay
draw.  Infection dedupe across chunks rides the packed `flags` array
(bit0 received, bit1 crashed, bit2 removed under SIR -- one uint8 per
node, so the drain's random-access flag traffic is one gather + one
scatter per chunk).

RNG parity with the ring engine: drop masks and delay slots are drawn from
the identical (seed, delivery-tick, op, sender-row) streams, so with
crashrate=0 the wave trajectory -- totals and window-resolution timing --
is bit-identical to the ring engine (tested).  Documented divergences, all
crash-path only:
* Crash draws are per *message* (keyed by mailbox position), like the
  reference's per-reception draw (simulator.go:112-116), instead of the
  ring engine's aggregated 1-(1-p)^c per node-tick.
* Within one window, a crash does not black-hole the node's other
  deliveries of that window (the reference's channel would, for messages
  queued behind the crash; the margin is ~crashrate x multi-delivery rate).
* A node that would be infected at tick t1 and crashed at t2 > t1 in the
  SAME window is treated as crashed-before-infected (no broadcast).
* When a window drains in multiple chunks, a node whose entries span a
  chunk boundary re-broadcasts from its first-ENCOUNTERED delivery tick
  rather than its globally earliest one (dedupe itself stays exact via the
  received array).
* SIR only: a re-broadcast trigger firing in the same CHUNK as a data
  reception whose crash draw fires still fires (trigger eligibility reads
  chunk-start state); the ring engine's same-tick `due & ~crashed` blocks
  it.  Margin ~crashrate x (trigger co-arrival rate), crash-path only.

Control-flow note: built from outer fori windows, inner dynamic-trip fori
chunks, gathers, flat 1-D mode="drop" scatters (2-D index scatters are
~15x slower here) and lax.sort.  Deliberately NO lax.cond (see the NOTE
in epidemic.make_tick_fn).

Capacity: slot_cap(cfg) packed entries per window slot; appends beyond it
are dropped and counted in `mail_dropped` (Stats.mailbox_dropped), never
silent.  Reservations are exact-size, so SI in-flight is ~n * mean_degree
spread over the delay span; the default covers peak skew ~1.5x over.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gossip_simulator_tpu import scenario as _scen
from gossip_simulator_tpu import tuning as _tuning
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import epidemic
# in_flight: canonical engine-agnostic definition in models/state.py,
# re-exported here for the backends that import event.in_flight.
from gossip_simulator_tpu.models.state import (in_flight,  # noqa: F401
                                               init_exch_counts,
                                               msg64_add, msg64_zero)
from gossip_simulator_tpu.utils import rng as _rng

I32 = jnp.int32


# flags bit layout: one uint8 per node instead of separate received/crashed
# bool arrays -- the drain's random-access traffic on n-sized arrays halves
# (one gather + one scatter per chunk instead of two of each; on this
# platform op count, not element count, sets the floor).
RECEIVED = jnp.uint8(1)
CRASHED = jnp.uint8(2)
REMOVED = jnp.uint8(4)  # SIR: stopped re-broadcasting (still counts coverage)


class EventState(NamedTuple):
    """SI epidemic state with packed message lists instead of count rings."""

    flags: jnp.ndarray  # uint8[n]: bit0 received, bit1 crashed, bit2 removed (SIR)
    friends: jnp.ndarray  # int32[n, k]
    friend_cnt: jnp.ndarray  # int32[n]
    # Flat (dw * cap + ring_tail,) packed ring: slot s occupies
    # [s*cap, (s+1)*cap).  Stored flat (not (dw, cap)) so the append scatter
    # updates it in place -- a reshape round-trip defeats XLA's donation
    # aliasing and copies the multi-GB ring once per chunk (measured
    # 6s/window at n=5e7).  The tail slack (ring_tail) holds the diverted
    # trash writes at UNIQUE positions (letting the append scatter claim
    # unique_indices -- explicit in-bounds cells, no reliance on mode="drop"
    # OOB semantics, see epidemic.deposit_local) and keeps
    # the last drain dynamic_slice of a full slot from clamping (a clamped
    # slice would misalign entry validity).
    mail_ids: jnp.ndarray  # int32[dw * cap + ring_tail]
    # (1, dw): node-axis-leading so the sharded backend stacks shards'
    # counts to (S, dw) under a P('nodes', None) spec.
    mail_cnt: jnp.ndarray  # int32[1, dw]
    # Deferred total_message credits from duplicate suppression, bucketed
    # by arrival window slot (append_messages docstring); credited into
    # the window's dm when it drains and zeroed with mail_cnt.  Bound: one
    # window's suppressed edges <= n * k < 2^31 at every reachable config
    # (n is already bounded tighter by flat mailbox addressing).
    sup_cnt: jnp.ndarray  # int32[1, dw]
    tick: jnp.ndarray  # int32[]
    total_message: jnp.ndarray  # uint32[2] hi/lo 64-bit pair (state.msg64_*)
    total_received: jnp.ndarray  # int32[]
    total_crashed: jnp.ndarray  # int32[]
    mail_dropped: jnp.ndarray  # int32[]  slot-capacity overflow (counted)
    # Cross-shard all_to_all bucket overflow (always 0 on one device).
    exchange_overflow: jnp.ndarray  # int32[]
    # --- fault-injection scenario (scenario.py; see SimState) ------------
    down_since: jnp.ndarray  # int32[n | 1]  crash tick, -1 = live/unknown
    scen_crashed: jnp.ndarray  # int32[]
    scen_recovered: jnp.ndarray  # int32[]
    part_dropped: jnp.ndarray  # int32[]
    heal_repaired: jnp.ndarray  # int32[]
    # --- multi-rumor traffic (Config.multi_rumor; placeholders otherwise,
    # the down_since convention -- the single-rumor program never traces a
    # rumor-axis op) ------------------------------------------------------
    # Per-entry payload words, same flat ring layout/length as mail_ids:
    # the entry at flat position p carries the W = ceil(R/32) uint32 rumor
    # bits mail_words[p] (the sender's NEW bits at send time).
    mail_words: jnp.ndarray  # uint32[dw * cap + ring_tail, W | 1x1]
    rumor_words: jnp.ndarray  # uint32[n, W | 1x1]  per-node infection bits
    # Per-rumor infected counts / completion tick, padded to W*32 lanes
    # (lanes >= R stay 0 / -1).  Replicated across shards (psum'd deltas).
    rumor_recv: jnp.ndarray  # int32[W * 32 | 1]
    rumor_done: jnp.ndarray  # int32[W * 32 | 1]  tick coverage hit, -1 else
    # Spatial-telemetry routed-exchange counters (state.init_exch_counts;
    # 1x1 placeholder unless the panels record under S > 1 shards).
    exch_counts: jnp.ndarray  # int32[1, S+2 | 1x1]


def batch_ticks(cfg: Config, n_local: int | None = None) -> int:
    """Window size B: delays >= delaylow >= B guarantee no intra-window
    causality.  Also bounded so the packed id*B+tick_off fits int32 --
    SIR additionally packs re-broadcast triggers at (n+1+id)*B+off
    (see trigger_base), doubling the range."""
    n = n_local if n_local is not None else cfg.n
    b = max(1, min(10, cfg.delaylow))
    span = 2 * n + 3 if cfg.protocol == "sir" else n + 1
    while b > 1 and span * b >= 2**31:
        b //= 2
    return b


def trigger_base(n: int, b: int) -> int:
    """SIR re-broadcast triggers are tagged self-messages packed as
    trigger_base + id*b + off: they sort after every data entry (and after
    the data padding sentinel n*b), so a node's data run stays contiguous
    and triggers form their own runs."""
    return (n + 1) * b


def ring_windows(cfg: Config, n_local: int | None = None) -> int:
    """Window-slot ring depth: max arrival offset in windows, plus current."""
    b = batch_ticks(cfg, n_local)
    return (b - 1 + cfg.delayhigh - 1) // b + 1


def slot_cap(cfg: Config, n_local: int | None = None) -> int:
    """Packed entries per window slot.  Reservations are exact-size, so SI
    total in-flight is ~n * mean_degree spread over delay_span ticks; a
    window aggregates B ticks of it, 1.5x covers skew (overflow is counted,
    never silent).  Clamped so the flat scatter index dw * cap stays in
    int32."""
    n = n_local if n_local is not None else cfg.n
    b = batch_ticks(cfg, n_local)
    dw = ring_windows(cfg, n_local)
    # Reservations are exact-size (no padding reaches the ring), so the
    # aggregate budget is the MEAN out-degree (for erdos ~3x smaller than
    # the padded column width), plus one for SIR's re-broadcast trigger.
    deg = cfg.mean_degree + (1 if cfg.protocol == "sir" else 0)
    # 1.5x skew headroom is a registered tunable (tuning.py); an explicit
    # -event-slot-cap outranks it entirely.
    headroom = _tuning.value("event.slot_headroom", cfg)
    cap = cfg.event_slot_cap if cfg.event_slot_cap > 0 else max(
        4096, int(math.ceil(headroom * n * deg * b
                            / max(cfg.delay_span, 1))))
    # One slot can never hold more than every SI message plus padding
    # (SIR re-broadcasts indefinitely, so the bound only applies to SI).
    if cfg.protocol != "sir":
        cap = min(cap, n * cfg.graph_width + cfg.graph_width)
    if cfg.event_slot_cap <= 0:
        # Auto sizing also respects HBM: bound the whole ring to ~3 GB
        # (validated headroom for the 100M single-chip run on a 16 GB v5e;
        # overflow past the cap is counted in mail_dropped, never silent).
        # An explicit -event-slot-cap overrides this.  Under duplicate
        # suppression (SI) the band halves: append-side filtering cut the
        # measured peak slot occupancy 1.86x (94.8M vs 176.4M at 1e8
        # fanout 6, 2026-07-31), and the scatter/gather cost of every
        # append batch scales with the RING size on this platform --
        # cap 1.34e8 (1.6 GB ring) ran the 100M/99% row 2.2s faster than
        # cap 2.68e8 (3.2 GB) at a 1.41x occupancy margin.  SIR keeps the
        # full band (re-broadcasts break the broadcast-once occupancy
        # argument).
        hbm = (3 * 2**30 if not (cfg.dup_suppress_resolved
                                 and cfg.protocol == "si")
               else 3 * 2**29)
        cap = min(cap, hbm // (4 * max(dw, 1)))
    # Tail-aware int32 clamp (advisor r5): the flat ring extends to
    # dw*cap + ring_tail and append diverts trash lanes to indices
    # dw*cap + lane, so the WHOLE range -- not just dw*cap -- must stay
    # in int32 or a large explicit -event-slot-cap wraps the trash
    # indices negative.  ring_tail needs drain_chunk which needs slot_cap
    # back; the cycle breaks with the PRE-clamp chunk request
    # (_chunk_want), an upper bound on the real chunk and hence -- via
    # the same width rule ring_tail applies -- on the real tail.
    cw = _chunk_want(cfg, n_local)
    scap = sender_compaction_cap(cfg, cw)
    width = scap if scap else cw
    tail_ub = max(cw, width * (cfg.graph_width
                               + (1 if cfg.protocol == "sir" else 0)))
    lim = (2**31 - 1 - tail_ub) // max(dw, 1)
    if lim <= 0:
        raise ValueError(
            f"-event-chunk {cfg.event_chunk} implies a ring tail of "
            f"{tail_ub} lanes, past int32 flat addressing; lower it")
    return min(cap, lim)


def ring_tail(cfg: Config, n_local: int | None = None) -> int:
    """Slack lanes past the last window slot.  Serves three purposes: an
    explicit trash region for diverted scatter lanes, drain-slice clamp
    protection (>= drain_chunk so the last dynamic_slice of a full slot
    never clamps), and -- sized to one full append batch's lane count --
    UNIQUE trash positions, which lets the mail scatter claim
    unique_indices=True and skip XLA's sort-based duplicate combining
    (profiled at 8.6 ms per batch at scap=1M x k=6 on v5e, plus combine
    overhead inside the scatter fusion itself).  graph_width bounds the
    per-sender lane count from above (kout tables are fanout wide;
    overlay tables max_degree); +1 is the SIR trigger column."""
    ccap = drain_chunk(cfg, n_local)
    scap = sender_compaction_cap(cfg, ccap)
    width = scap if scap else ccap
    lanes = width * (cfg.graph_width + (1 if cfg.protocol == "sir" else 0))
    return max(ccap, lanes)


def drain_geometry(cfg: Config, n_local: int | None = None) -> tuple:
    """(slot_cap, drain_chunk, ring_tail): every jit-time ring/drain
    constant the event-engine tunables (drain_chunk_*, slot_headroom)
    feed.  This is the autotuner's effect probe (tuning.effective_value):
    a candidate that leaves this tuple unchanged compiles the identical
    program, so its sweep row is unexercised noise."""
    return (slot_cap(cfg, n_local), drain_chunk(cfg, n_local),
            ring_tail(cfg, n_local))


def drain_chunk(cfg: Config, n_local: int | None = None) -> int:
    """Drain chunk size: auto = a degree-scaled n/128 ramp with
    r = mean_degree / 4 (the fanout-3 kout calibration; max_degree 4
    there): clamp(n/128 * r^3, 131k, hi) where hi = 1M for r >= 1.5 else
    512k, rounded UP to a power of two (the sort pads to one internally).

    Swept empirically on v5e.  Fanout 3 kout: n=1e7: 64k:752,
    128k:769->922 (post friend_cnt removal), 156k:882, 256k:718->794,
    512k:623, 1M:487 M node-updates/s -- op cost grows superlinearly past
    ~128k entries (sort passes, scatter contention), favoring small
    chunks; n=1e8: 128k:303, 256k:782, 512k:903, 1M:880 -- the n-sized
    flag gather/scatter per chunk grows with n, so fewer/larger chunks
    win.  Fanout 6 kout (the 99%-coverage north-star config, ~5x the
    entries per window; swept 2026-07-31): n=1e7: 131k:7.08s, 262k:6.53,
    512k:6.18, 1M:6.27 time-to-99%; n=1e8: 512k:57.8, 1M:49.5, 2M:55.6 --
    higher message volume pushes the optimum up roughly with degree^3
    over this range.  The scaled ramp lands within ~3% of all six
    measured optima; the cap keeps low-degree configs (incl. the proven
    1e8 fanout-3 headline at 512k) exactly where their sweeps put them."""
    return min(slot_cap(cfg, n_local), _chunk_want(cfg, n_local))


def _chunk_want(cfg: Config, n_local: int | None = None) -> int:
    """drain_chunk before its slot_cap clamp (the auto ramp / explicit
    -event-chunk, >= 256).  Split out so slot_cap's tail-aware int32
    clamp can bound the ring tail without calling drain_chunk back
    (which would recurse into slot_cap)."""
    n = n_local if n_local is not None else cfg.n
    if cfg.event_chunk > 0:
        want = cfg.event_chunk
    else:
        # The ramp's floor and per-branch ceilings are registered
        # tunables (tuning.py "chunk_ladder" space -- the ladder the
        # deleted scripts/chunk_sweep*.py swept by hand); an explicit
        # -event-chunk outranks any table entry via the branch above.
        r = max(1.0, cfg.mean_degree / 4.0)
        hi = (_tuning.value("event.drain_chunk_hi", cfg) if r >= 1.5
              else _tuning.value("event.drain_chunk_hi_lowdeg", cfg))
        if cfg.dup_suppress_resolved and r >= 1.5:
            # Suppression shrinks the drained entry volume ~1.4x and the
            # ring itself (slot_cap band), moving the optimum up again:
            # 1e8 fanout 6 @99% swept 2026-07-31 (cap 1.34e8): 1M:27.6,
            # 2M:24.9, 4M:24.3, 8M:26.6 s -- per-batch op floors beat
            # element growth until ~4M.
            hi = _tuning.value("event.drain_chunk_hi_suppress", cfg)
        floor = _tuning.value("event.drain_chunk_floor", cfg)
        want = min(hi, max(floor, int(n // 128 * r ** 3)))
        # Round up to a power of two: the sort pads to one internally, so
        # a 918k chunk costs a 1M sort but drains only 918k entries
        # (measured 55.6s vs 49.5s at the 1e8 fanout-6 config).
        want = 1 << (want - 1).bit_length()
    return max(256, want)


def init_rumor_leaves(cfg: Config, n: int, ring_len: int | None = None):
    """(mail_words, rumor_words, rumor_recv, rumor_done) -- full-size under
    Config.multi_rumor, 1-element placeholders otherwise (the down_since
    convention).  Shared by the single-device and sharded init paths and by
    the checkpoint loader's legacy-snapshot backfill."""
    if not cfg.multi_rumor:
        return (jnp.zeros((1, 1), jnp.uint32), jnp.zeros((1, 1), jnp.uint32),
                jnp.zeros((1,), I32), jnp.full((1,), -1, I32))
    w = cfg.rumor_word_count
    if ring_len is None:
        ring_len = ring_windows(cfg) * slot_cap(cfg, n) + ring_tail(cfg, n)
    return (jnp.zeros((ring_len, w), jnp.uint32),
            jnp.zeros((n, w), jnp.uint32),
            jnp.zeros((w * 32,), I32), jnp.full((w * 32,), -1, I32))


def injection_lanes(cfg: Config) -> int:
    """Static injection lane count per B-tick window: all R for oneshot
    (only window 0's lanes validate), else the max rumors whose schedule
    tick can land inside one window."""
    if not cfg.multi_rumor:
        return 0
    if cfg.traffic != "stream":
        return cfg.rumors
    b = batch_ticks(cfg)
    from gossip_simulator_tpu import arrivals as _arrivals

    table = _arrivals.table_or_none(cfg)
    if table is not None:
        # Windows are b-aligned (tick advances 0, b, 2b, ...), so the max
        # rumors per aligned bucket is the exact lane requirement.
        counts = np.unique(np.asarray(table, np.int64) // b,
                           return_counts=True)[1]
        return int(counts.max()) if len(counts) else 1
    return min(cfg.rumors, (b * cfg.stream_rate + 999) // 1000 + 1)


def injection_batch(cfg: Config, tick, base_key, b: int, dw: int,
                    n_local: int | None = None, shard=None):
    """Rumor injections whose schedule tick lands in the window
    [tick, tick+b): self-addressed mail entries (dst = source row,
    delivered at the rumor's inject tick) so injected rumors enter through
    the SAME ring/drain machinery as every relayed message -- the source
    is counted infected, and broadcasts, at its entry's drain.  Rumor r's
    tick is 0 (oneshot: every rumor at window 0) or r * 1000 //
    stream_rate (stream).  Source draws are keyed by rumor index ONLY
    (OP_INJECT -- no tick, no shard), so the schedule is shard-count
    invariant; `shard` non-None keeps only lanes the shard owns and
    localizes the destination row.  Returns (payload, words, wslot,
    valid) with injection_lanes(cfg) static lanes."""
    m = injection_lanes(cfg)
    r_total = cfg.rumors
    w = cfg.rumor_word_count
    stream = cfg.traffic == "stream"
    if stream:
        from gossip_simulator_tpu import arrivals as _arrivals

        table = _arrivals.table_or_none(cfg)
    else:
        table = None
    if table is not None:
        # Precomputed arrival schedule (non-fixed -arrivals, or a serve
        # admission-deferral override): the sorted table is a compile-time
        # constant (R <= 1024 int32s), so the window's first candidate
        # rumor is a searchsorted lookup and its tick a gather.  Same lane
        # validity/payload math as the arithmetic branch below.
        tab = jnp.asarray(table, I32)
        r0 = jnp.searchsorted(tab, tick, side="left").astype(I32)
        rr = r0 + jnp.arange(m, dtype=I32)
        t_r = tab[jnp.minimum(rr, r_total - 1)]
    elif stream:
        rate = cfg.stream_rate
        # Clamp before the multiply so tick * rate stays in int32 at any
        # max_rounds (past last_inject_tick every lane invalidates anyway;
        # validate() bounds stream_rate so the clamped product fits).
        tickc = jnp.minimum(tick, cfg.last_inject_tick + 1)
        r0 = (tickc * rate + 999) // 1000
        rr = r0 + jnp.arange(m, dtype=I32)
        t_r = rr * 1000 // rate
    else:
        rr = jnp.arange(m, dtype=I32)
        t_r = jnp.zeros((m,), I32)
    valid = (rr < r_total) & (t_r >= tick) & (t_r < tick + b)
    ik = jax.random.fold_in(base_key, _rng.OP_INJECT)
    src = jax.vmap(lambda r: jax.random.randint(
        jax.random.fold_in(ik, r), (), 0, cfg.n, dtype=I32))(rr)
    if shard is not None:
        valid = valid & (src // n_local == shard)
        src = src % n_local
    payload = src * b + t_r % b
    wslot = (t_r // b) % dw
    words = jnp.where(
        (rr[:, None] // 32) == jnp.arange(w, dtype=I32)[None, :],
        (jnp.uint32(1) << (rr % 32).astype(jnp.uint32))[:, None],
        jnp.uint32(0))
    return payload, words, wslot, valid


def stamp_rumor_done(cfg: Config, rumor_recv, rumor_done, tick):
    """Per-window completion stamping (metrics only -- the run cond keys on
    rumor_recv): rumor r is done at the first window-end tick where its
    infected count reaches the static ceil(coverage_target * n)."""
    target = int(math.ceil(cfg.coverage_target * cfg.n))
    hit = (rumor_recv >= target) & (rumor_done < 0)
    return jnp.where(hit, tick, rumor_done)


def init_state(cfg: Config, friends: jnp.ndarray,
               friend_cnt: jnp.ndarray, n_shards: int = 1) -> EventState:
    n = friends.shape[0]  # local rows: the shard slice under the sharded backend
    z = lambda: jnp.zeros((), I32)
    mail_words, rumor_words, rumor_recv, rumor_done = init_rumor_leaves(
        cfg, n)
    return EventState(
        flags=jnp.zeros((n,), jnp.uint8),
        friends=friends,
        friend_cnt=friend_cnt,
        mail_ids=jnp.zeros(
            (ring_windows(cfg) * slot_cap(cfg, n) + ring_tail(cfg, n),),
            I32),
        mail_cnt=jnp.zeros((1, ring_windows(cfg)), I32),
        sup_cnt=jnp.zeros((1, ring_windows(cfg)), I32),
        tick=z(), total_message=msg64_zero(), total_received=z(),
        total_crashed=z(),
        mail_dropped=z(), exchange_overflow=z(),
        down_since=_scen.init_down_since(cfg.faults_enabled, n),
        scen_crashed=z(), scen_recovered=z(), part_dropped=z(),
        heal_repaired=z(),
        mail_words=mail_words, rumor_words=rumor_words,
        rumor_recv=rumor_recv, rumor_done=rumor_done,
        exch_counts=init_exch_counts(cfg, n_shards),
    )


def _sender_keys(base_key, op: int, ticks, rows):
    """Per-sender key fold_in(fold_in(fold_in(base, tick), op), row) -- the
    exact stream epidemic.row_slot / row_bernoulli draw from for a sender
    broadcasting at `tick`, vectorized over per-sender delivery ticks."""
    def one(t, r):
        return jax.random.fold_in(_rng.tick_key(base_key, t, op), r)

    return jax.vmap(one)(ticks, rows)


def append_messages(cfg: Config, mail_ids, mail_cnt, dropped, sender_ids,
                    svalid, sticks, friends, friend_cnt, base_key,
                    strig=None, flags=None, gid0=0, swords=None,
                    mail_words=None, kernel: str = "xla"):
    """Emit each sender's broadcast (k sends, ONE shared delay drawn at its
    delivery tick -- simulator.go:141-142) into the packed mail ring.

    `flags` non-None enables guaranteed-duplicate suppression (sound only
    at crash_p == 0 -- Config.dup_suppress_resolved gates): a kept edge
    whose destination already has the received bit never enters the ring
    -- its delivery could only have incremented total_message
    (simulator.go:111,117-119; received is monotone, and at crash_p == 0
    there is no per-reception draw to preserve).  Suppressed edges are
    returned as per-ARRIVAL-WINDOW counts `sup_adds[dw]` that the caller
    banks in EventState.sup_cnt and credits to total_message when that
    window drains -- the exact step its deliveries would have counted --
    so every poll-cadence observable (per-window totals, stdout, JSONL,
    death tick) is bit-identical to the unsuppressed path, not just the
    final totals (A/B-tested).  Delay and drop draws are (tick,
    sender-row)-keyed, so filtering edges shifts no stream.  Remaining
    divergence envelope: under slot overflow a suppressed edge counts as
    delivered where the unsuppressed path might have counted it into
    mail_dropped (zero-overflow regimes -- every measured config -- are
    unaffected).

    A sender's messages share one arrival tick, hence one window slot.
    Reservations are EXACT-size: each sender takes as many contiguous
    positions as it has kept (non-dropped, real) edges -- a per-slot
    weighted exclusive prefix sum over (senders, dw) -- so the ring holds
    no padding and the drain touches only live entries.  (Erdos friends
    tables are ~72% tail padding at the default p; fixed-width
    reservations made the drain pay for all of it.)  The write is one
    flat 1-D scatter with non-edges diverted to the trash cell.

    SIR (`strig` mask set): senders also schedule their next re-broadcast as
    a tagged self-message (trigger_base + id*b + off) arriving with the SAME
    shared delay -- the event analog of the ring engine's
    `rebroadcast.at[dslot, ids]` (models/epidemic.py tick_core); it sits
    right after the sender's kept edges.

    Multi-rumor (`swords` (m, W) + `mail_words` set): every kept edge also
    writes the sender's delta words through the SAME flat positions --
    entry alignment is by construction, not by a second rank pass -- and
    the return gains the updated mail_words.  Mutually exclusive with
    `strig` (multi-rumor is SI-only, config.validate)."""
    n, k = friends.shape
    dw = ring_windows(cfg)
    cap = (mail_ids.shape[0] - ring_tail(cfg, n)) // dw
    b = batch_ticks(cfg)
    rows = jnp.where(svalid, sender_ids, n)
    sidx = jnp.where(svalid, sender_ids, 0)
    sf = friends.at[sidx].get()
    del friend_cnt  # not gathered: rows are prefix-compact, (sf >= 0) is the
    # edge mask (every generator -1-pads the tail; overlay.py appends at cnt
    # and swap-fills holes) -- profiled at ~1 ms/chunk, ~8% of the drain.
    dk = _sender_keys(base_key, _rng.OP_DELAY, sticks, rows)
    pk = _sender_keys(base_key, _rng.OP_DROP, sticks, rows)
    delay = jnp.maximum(jax.vmap(
        lambda kk: jax.random.randint(kk, (), cfg.delaylow, cfg.delayhigh,
                                      dtype=I32))(dk), 1)
    drop_p = epidemic.p_eff(cfg, cfg.droprate)
    if drop_p <= 0.0:
        drop = jnp.zeros(rows.shape + (k,), bool)
    elif drop_p >= 1.0:
        drop = jnp.ones(rows.shape + (k,), bool)
    else:
        drop = jax.vmap(
            lambda kk: jax.random.bernoulli(kk, drop_p, (k,)))(pk)
    arrive = sticks + delay
    wslot = (arrive // b) % dw
    off = arrive % b
    edge = svalid[:, None] & ~drop & (sf >= 0)
    scen = cfg.scenario_resolved
    blocked_n = 0
    if scen.has_partitions:
        # Send-time partition mask (scenario.partition_blocked): an edge
        # whose broadcast leaves inside an active partition never enters
        # the ring -- before the duplicate filter, so a blocked edge is
        # never credited as a delivered duplicate.  `gid0` globalizes the
        # sharded caller's local rows; sf destinations are global already.
        blocked = _scen.partition_blocked(
            scen, cfg.n, sticks[:, None], (gid0 + rows)[:, None], sf) & edge
        blocked_n = blocked.sum(dtype=I32)
        edge = edge & ~blocked
    dcnt = None
    if flags is not None:
        dstf = flags.at[jnp.where(sf >= 0, sf, 0)].get()
        dup = edge & ((dstf & RECEIVED) > 0)
        dcnt = dup.sum(axis=1, dtype=I32)  # suppressed edges per sender
        edge = edge & ~dup
    cols = jnp.cumsum(edge, axis=1, dtype=I32) - 1  # kept-edge rank in row
    ec = edge.sum(axis=1, dtype=I32)  # kept edges per sender
    payload = sf * b + off[:, None]
    if strig is not None:
        tb = trigger_base(n, b)
        # The trigger occupies the slot right after the kept edges.
        cols = jnp.concatenate([cols, ec[:, None]], axis=1)
        edge = jnp.concatenate([edge, strig[:, None]], axis=1)
        payload = jnp.concatenate(
            [payload, (tb + sender_ids * b + off)[:, None]], axis=1)
        ec = ec + strig.astype(I32)
    # Per-slot exclusive prefix of reservation sizes (emission order).
    # Row r's slot column is picked by ONE-HOT ARITHMETIC, not a gather:
    # dw is tiny (~3), so (x * oh).sum(axis=1) fuses into the cumsum while
    # take_along_axis / mail_cnt[0, wslot] each lower to a ccap-sized
    # random gather costing a full per-op floor (profiled at ~24 ms per
    # window combined at n=1e7, ~17% of the drain).  Rows with svalid
    # False get seg = base = 0 instead of the old column-0 values; both
    # versions are don't-cares there (every consumer masks with ok, a
    # subset of svalid) and live rows are bit-identical.
    oh = ((wslot[:, None] == jnp.arange(dw, dtype=I32)[None, :])
          & svalid[:, None]).astype(I32)
    # Suppressed-edge counts bucketed by arrival window via the SAME
    # one-hot (fused reduction, no dw-gather/scatter); independent of the
    # overflow check -- suppressed edges never consume ring capacity.
    sup_adds = ((oh * dcnt[:, None]).sum(axis=0) if dcnt is not None
                else jnp.zeros((dw,), I32))
    w = oh * ec[:, None]
    seg = ((jnp.cumsum(w, axis=0) - w) * oh).sum(axis=1)
    base = (mail_cnt[0][None, :] * oh).sum(axis=1)
    start = base + seg
    ok = svalid & (start + ec <= cap)
    # Dead lanes divert to UNIQUE trash positions (ring_tail sizes the
    # slack to one batch's lane count); live reservations are disjoint by
    # construction, so the scatter can claim unique_indices and skip XLA's
    # sort-based duplicate combining (profiled 8.6 ms/batch at 6.3M lanes).
    nlanes = edge.shape[0] * edge.shape[1]
    lane = jnp.arange(nlanes, dtype=I32).reshape(edge.shape)
    flat = jnp.where(edge & ok[:, None],
                     wslot[:, None] * cap + start[:, None] + cols,
                     dw * cap + lane)
    ivals = jnp.where(edge, payload, 0).reshape(-1)
    if swords is not None:
        wvals = jnp.where(edge[:, :, None],
                          jnp.broadcast_to(swords[:, None, :],
                                           edge.shape + swords.shape[-1:]),
                          jnp.uint32(0)).reshape(-1, swords.shape[-1])
    if kernel == "pallas":
        # Fused dual-ring write: id ring and word ring share their unique
        # reservation positions, so one serial pass writes both (order
        # immaterial -- bit-identical to the unique_indices scatters).
        from gossip_simulator_tpu.ops import pallas_deliver
        if swords is not None:
            mail_ids, mail_words = pallas_deliver.fused_unique_set(
                (mail_ids, mail_words), flat.reshape(-1), (ivals, wvals))
        else:
            (mail_ids,) = pallas_deliver.fused_unique_set(
                (mail_ids,), flat.reshape(-1), (ivals,))
    else:
        mail_ids = mail_ids.at[flat.reshape(-1)].set(
            ivals, unique_indices=True)
        if swords is not None:
            mail_words = mail_words.at[flat.reshape(-1)].set(
                wvals, unique_indices=True)
    # Overflowed senders are a per-slot suffix (start grows monotonically
    # within a slot), so counting only written reservations keeps
    # positions contiguous.
    adds = (w * ok[:, None]).sum(axis=0)
    new_cnt = mail_cnt + adds[None, :]
    lost = (edge & ~ok[:, None]).sum(dtype=I32)
    # CAVEAT (SIR): an overflowed reservation loses the sender's
    # re-broadcast trigger along with its data messages, permanently muting
    # that node's re-broadcast chain -- a qualitatively larger distortion
    # than the per-message count suggests.  slot_cap budgets mean_degree+1
    # per sender precisely so this stays at zero; a nonzero mail_dropped
    # under SIR should be treated as an undersized -event-slot-cap, not as
    # ordinary message loss (see README divergence table).  blocked_n is
    # the partition-masked edge count (a Python 0 without partitions).
    if swords is not None:
        return mail_ids, new_cnt, dropped + lost, sup_adds, blocked_n, \
            mail_words
    return mail_ids, new_cnt, dropped + lost, sup_adds, blocked_n


# Pre-drain compaction engages only once received-fraction crosses this
# (measured 2026-07-31, 1e8 fanout 6 v5e: at 42%/78% received the filter's
# RANDOM flags gather costs more than the sorted drain it shrinks -- +1.05s
# and +0.56s per window -- while at 96% it wins -1.0s; the sort's ascending
# locality is what the platform rewards, same lesson as the scatter-min
# dead end).  Below the threshold the filter runs zero chunks.
PREDRAIN_MIN_RECV_FRAC = 0.9


def predrain_compact(b: int, n_rows: int, dw: int, cap: int, ccap: int,
                     sir: bool, flags, mail_ids, slot, m):
    """Filter the due window's slot against the CURRENT flags before the
    chunked drain (crash_p == 0 gate -- same soundness as append-side
    suppression, shared by the single-device and sharded steps): a data
    entry whose destination's received bit is set can only increment
    total_message in this very window, so it is counted here and compacted
    away instead of paying the sorted drain.  Catches the duplicates the
    append-side filter structurally cannot -- those appended BEFORE their
    destination flipped received (the exponential-phase majority: measured
    ~80% of endgame ring traffic at 1e8 fanout 6).  The slot's content is
    frozen once its window starts (delay >= B), so filtering at drain
    start sees final content.  Stable compaction (rank = running kept
    count) preserves entry order, so retained entries keep the exact
    first-encountered semantics; chunk boundaries shift with occupancy,
    the same envelope as any event_chunk change.  SIR: triggers
    (ent >= n*b) are never data and always kept.

    In-place safety: chunk j's scatter writes land strictly below
    position (j+1)*ccap (kept <= j*ccap), so no later chunk reads a
    written lane.  `m` may be a traced scalar and the caller may pass 0
    chunks' worth (m=0 disables); returns
    (mail_ids, kept_total, filtered_data)."""
    nf = (m + ccap - 1) // ccap

    def fbody(j, carry):
        mail, kept, fdat = carry
        off0 = j * ccap
        pos = off0 + jnp.arange(ccap, dtype=I32)
        valid = pos < m
        ent = jax.lax.dynamic_slice(mail, (slot * cap + off0,), (ccap,))
        is_data = valid & (ent < n_rows * b) if sir else valid
        idx = jnp.where(is_data, jnp.minimum(ent // b, n_rows - 1), 0)
        f = flags.at[idx].get()
        drop = is_data & ((f & RECEIVED) > 0)
        keep = valid & ~drop
        rank = kept + jnp.cumsum(keep.astype(I32)) - 1
        lane = jnp.arange(ccap, dtype=I32)  # unique trash (ccap <= tail)
        tgt = jnp.where(keep, slot * cap + rank, dw * cap + lane)
        mail = mail.at[tgt].set(jnp.where(keep, ent, 0),
                                unique_indices=True)
        return mail, kept + keep.sum(dtype=I32), fdat + drop.sum(dtype=I32)

    return jax.lax.fori_loop(
        0, nf, fbody,
        (mail_ids, jnp.zeros((), I32), jnp.zeros((), I32)))


def drain_chunk_core(crash_p: float, b: int, n_rows: int, flags, packed,
                     evalid, entry_pos, ckey, sir: bool = False,
                     track_crashed: bool = False, down_since=None,
                     win_tick=None, words=None, rumor_words=None):
    """Crash/infect/dedupe one drained chunk of packed entries (shared by the
    single-device and sharded engines; `n_rows` is the local row count).

    Sorts by (id, crash-fired-first, tick_off): a node's entries become one
    contiguous run whose FIRST element answers whether any per-message crash
    draw fired (keyed by mailbox position -- append order is deterministic --
    like the reference's per-reception draw, simulator.go:112-116) and, if
    not, its earliest delivery tick.  The sort also turns the flags
    gather/scatter into ascending-id access (better HBM locality than the
    raw mailbox order).

    `flags` packs received (bit0) and crashed (bit1) per node; within a
    chunk a node's winning entry sets at most one new bit, so the update is
    a single duplicate-free scatter-add.

    With `sir` (static -- compiles to the identical SI program when False):
    trigger entries (trigger_base + id*b + off) sort after all data into
    their own per-node runs.  Data entries infect exactly as in SI; a
    trigger FIRES -- the node re-broadcasts at its tick -- iff the node is
    infected and neither crashed nor removed as of the chunk start (the
    ring engine's `due & ~crashed & ~removed`; same-chunk crash-vs-trigger
    ordering divergence is documented in the module docstring).  Crash
    draws fire on data receptions only; removal draws happen in the caller
    (per sender, at send time, matching tick_core's removal-after-send).

    `track_crashed` forces the pre-crash flag read even at crash_p == 0:
    under a fault scenario, nodes crash OUTSIDE the per-reception draw
    (crash waves / churn), and deliveries to them must still black-hole
    (counted like the ring engine's `where(crashed, 0, arrivals)`).
    `down_since`/`win_tick` non-None stamp the crash clock on reception
    crashes (the scenario reboot/detection timeline; window-start
    granularity -- the crash draw itself is window-batched already).

    Multi-rumor (`words` (ccap, W) + `rumor_words` set; SI only): the
    entry payload words ride the sort as extra operands, a reversed
    segmented OR-scan folds each node-run's words into its FIRST lane
    (suffix-OR over the run -- read only at run starts), and the winner's
    NEW bits (run OR minus the node's current words) update rumor_words,
    per-rumor counts, and become the node's forwarded payload.  The
    winner gate drops `~pre_recv`: an already-infected node gaining new
    bits still delivers and re-forwards (first-touch-wins is per RUMOR,
    not per node).  `dm` still counts every delivered entry -- a delivery
    bringing no new bits walks the channel like any reference duplicate.
    A crash draw firing at the run's first lane voids the whole run's
    delivery (crashed-before-infected, the single-rumor rule, now
    per-run).  Returns three extra values (rumor_words, delta_words,
    drecv) and `senders` becomes win & (delta != 0).

    Returns (flags, dm, dr, dc, ids_s, toff_s, senders, down_since);
    senders is newly-infected for SI, newly | firing for SIR (disjoint: a
    trigger implies the node was already infected)."""
    ccap = packed.shape[0]
    tb = trigger_base(n_rows, b)
    sentinel = tb + n_rows * b if sir else n_rows * b
    packed = jnp.where(evalid, packed, sentinel)  # sentinel sorts last
    wcols = ()
    if words is not None:
        # Stale ring lanes past the count carry garbage words: zero them
        # (their sentinel keys sort them into non-data runs anyway).
        words = jnp.where(evalid[:, None], words, jnp.uint32(0))
        wcols = tuple(words[:, i] for i in range(words.shape[1]))
    if crash_p > 0.0:
        ck = _rng.row_keys(ckey, entry_pos)
        draw = jax.vmap(lambda kk: jax.random.bernoulli(kk, crash_p))(ck)
        crash_e = draw & evalid
        if sir:
            crash_e = crash_e & (packed < n_rows * b)  # not on triggers
        sub = (1 - crash_e.astype(I32)) * b + packed % b
        # Single-key sort of id*2b + sub (sub < 2b): the same order -- and
        # the same tie-stability -- as the 2-key (id*b, sub) sort, at half
        # the sorted bytes and a simpler compare.  uint32 range: batch_ticks
        # guarantees span*b < 2^31, hence span*2b < 2^32 exactly.
        comb = (packed // b).astype(jnp.uint32) * jnp.uint32(2 * b) \
            + sub.astype(jnp.uint32)
        if words is not None:
            comb_s, *wcols_s = jax.lax.sort((comb,) + wcols, num_keys=1)
        else:
            comb_s = jax.lax.sort(comb)
        key1_s = (comb_s // jnp.uint32(2 * b)).astype(I32) * b
        sub_s = (comb_s % jnp.uint32(2 * b)).astype(I32)
        toff_s = sub_s % b
        crash_s = sub_s < b
    else:
        if words is not None:
            packed_s, *wcols_s = jax.lax.sort((packed,) + wcols,
                                              num_keys=1)
        else:
            packed_s = jnp.sort(packed)
        key1_s = packed_s // b * b
        toff_s = packed_s % b
        crash_s = jnp.zeros((ccap,), bool)
    is_data = key1_s < n_rows * b
    if sir:
        is_trig = (key1_s >= tb) & (key1_s < sentinel)
        ids_s = jnp.where(is_trig, (key1_s - tb) // b, key1_s // b)
        touched = is_data | is_trig
    else:
        ids_s = key1_s // b
        touched = is_data
    # Touched lanes are a PREFIX (sentinels sort last) with ascending ids,
    # so for SI the gather/scatter below can claim sorted indices (trash
    # lanes ride at n_rows, clamped by the gather / dropped by the
    # scatter).  SIR cannot: trigger ids restart below the data run's
    # tail.
    srt = not sir
    idx = jnp.where(touched, ids_s, n_rows)
    pre = flags.at[idx].get(indices_are_sorted=srt, mode="clip")
    pre_recv = (pre & RECEIVED) > 0
    if crash_p > 0.0 or track_crashed:
        pre_crash = ((pre & CRASHED) > 0) & touched
    else:
        pre_crash = jnp.zeros((ccap,), bool)
    counted = is_data & ~pre_crash
    dm = counted.sum(dtype=I32)
    prev = jnp.concatenate([jnp.full((1,), -1, I32), key1_s[:-1]])
    first = (key1_s != prev) & is_data
    dc = jnp.zeros((), I32)
    newly = first & counted & ~pre_recv & ~crash_s
    dr = newly.sum(dtype=I32)
    delta = newly.astype(jnp.uint8) * RECEIVED
    if crash_p > 0.0:
        run_crash = first & crash_s & ~pre_crash
        dc = run_crash.sum(dtype=I32)
        delta = delta + run_crash.astype(jnp.uint8) * CRASHED
        if down_since is not None:
            down_since = down_since.at[
                jnp.where(run_crash, ids_s, n_rows)].set(
                win_tick, mode="drop")
    # (No sorted claim here: non-winning lanes divert to n_rows BETWEEN
    # the ascending winners, breaking monotonicity.)
    flags = flags.at[jnp.where(delta > 0, ids_s, n_rows)].add(
        delta, mode="drop")
    senders = newly
    if sir:
        fire = is_trig & pre_recv & ~pre_crash & ~((pre & REMOVED) > 0)
        senders = newly | fire
    if words is not None:
        words_s = jnp.stack(wcols_s, axis=1)
        # Reversed segmented OR-scan: reversing keeps runs contiguous and
        # turns each run's LAST lane into its segment start, so the
        # inclusive scan leaves the whole-run OR at the run's original
        # FIRST lane (the winner; other lanes hold suffix-ORs, unread).
        last = jnp.concatenate([key1_s[:-1] != key1_s[1:],
                                jnp.ones((1,), bool)])

        def _seg_or(a, c):
            af, av = a
            cf, cv = c
            return af | cf, jnp.where(cf[..., None], cv, av | cv)

        _, rv = jax.lax.associative_scan(
            _seg_or, (last[::-1], words_s[::-1]))
        run_or = rv[::-1]
        win = first & counted & ~crash_s  # newly minus the ~pre_recv gate
        idxw = jnp.where(win, ids_s, n_rows)
        old = rumor_words.at[jnp.minimum(idxw, n_rows - 1)].get()
        delta_w = jnp.where(win[:, None], run_or & ~old, jnp.uint32(0))
        rumor_words = rumor_words.at[idxw].set(
            jnp.where(win[:, None], old | delta_w, jnp.uint32(0)),
            mode="drop")
        drecv = jnp.concatenate([
            ((delta_w[:, wi][:, None]
              >> jnp.arange(32, dtype=jnp.uint32)[None, :])
             & jnp.uint32(1)).astype(I32).sum(axis=0)
            for wi in range(words.shape[1])])
        senders = win & (delta_w != 0).any(axis=1)
        return (flags, dm, dr, dc, ids_s, toff_s, senders, down_since,
                rumor_words, delta_w, drecv)
    return flags, dm, dr, dc, ids_s, toff_s, senders, down_since


def sender_compaction_cap(cfg: Config, ccap: int) -> int:
    """Sender-compaction batch width (0 = dense append), shared by the
    single-device and sharded window steps so the two engines cannot
    drift.

    At mean degree d only ~1/(0.9 d) of drained entries are NEW senders,
    yet the dense append pays friends-gather + mail-scatter at full
    ccap x k width -- profiled at 65% of the fanout-6 window (mail
    scatter 33% incl. its internal 3M-lane sort, friends gather 26%),
    both element-bound at these widths.  Compacting senders via ONE
    cumsum-rank + ONE packed scatter (not the 5-op first_true_indices
    selection that measured 6-10% slower at fanout 3 in r2) shrinks
    those widths 2-4x; the reservation order -- hence the mail layout,
    hence every position-keyed crash draw -- is bit-identical on the
    single-device path (ranks ascend in chunk order, batches continue
    sequentially), verified against the exact pre-compaction totals at
    1e7/1e8 fanout 3 and 6, and pinned by a dense-vs-compacted A/B test.
    CAVEAT: the identity holds while mail_dropped stays 0 (auto slot_cap
    budgets for exactly that).  Under slot-cap overflow the paths
    diverge at the margin: an overflowed sender in an early batch
    reserves nothing, so later batches start at lower offsets and may
    fit entries the dense single-call append -- whose per-chunk prefix
    counts overflowed senders' reservations -- would also have
    overflowed.  Measured 2026-07-31 (warm, v5e): 1e7
    fanout 6: 6.29 -> 3.61s; 1e8 fanout 6: 49.5 -> 37.3s; 1e7 fanout 3
    headline: 2.61 -> 2.36s (1.19B node-updates/s).  The batch width
    tracks the typical sender fraction (ccap/2 covers the ~38% of
    actual degree 3; ccap/4 the ~20% of degree >= 5; the >= 3.0 bound
    admits erdos lambda=3, whose sender fraction matches kout fanout 3
    -- kout mean_degree is the column width fanout+1); actual degree
    <= 2 keeps the dense path -- nearly every entry is a new sender
    there, so batching would only add ops."""
    if cfg.mean_degree >= 5.0:
        return ccap // 4
    if cfg.mean_degree >= 3.0:
        return ccap // 2
    return 0


def narrow_tail_cap(scap: int) -> int:
    """Width of the narrow TAIL batches (0 = no narrow path).

    The append's per-batch cost has two regimes: the mail scatter and
    friends gather are element-bound at full scap width (profiled 6.3 +
    2.6 ms at scap=262k, fanout 6, v5e) but drop toward the ~1-2 ms
    per-op floor at ~1/8 width.  Near the coverage target most chunks
    produce only a few thousand NEW senders, yet each paid one
    full-width batch -- at the 1e7 fanout-6 endgame that was 27
    batches/window for near-empty sender sets (~45% of the window).
    Remainders <= 2*narrow widths run as 1-2 narrow batches instead;
    larger remainders keep the full-width batch (3+ narrow trips would
    cost more than the one element-bound batch they replace).
    Bit-identicality: reservation layout depends only on the sender
    ORDER (per-slot starts ride mail_cnt across batches) and every draw
    is (tick, row)-keyed, so batch-boundary placement cannot change the
    trajectory in the zero-overflow regime (same envelope as
    sender_compaction_cap's caveat; pinned by the narrow-tail A/B
    test)."""
    if scap <= 0:
        return 0
    # Strictly scap//8 (no floor-clamp): a clamped width in [scap/2, scap)
    # would make `tail` always true and split every remainder into two
    # near-half-width batches -- same elements, double the op floor.  Below
    # scap=8192 the batches are op-floor-bound at EITHER width, so the
    # narrow path is disabled rather than widened.
    ns = scap // 8
    return ns if ns >= 1024 else 0


def narrow_tail_trips(count, scap: int, nscap: int):
    """Trip counts (nfull, nnarrow) covering `count` senders: full-width
    batches, then -- when the remainder fits 1-2 narrow batches -- the
    narrow tail; larger remainders keep one more full-width batch.  The
    ONE scheduling rule shared by the single-device and sharded steps
    (sharded passes the pmax-agreed count so collective counts stay
    uniform across shards); `count` is a traced scalar."""
    rem = count % scap
    tail = rem <= 2 * nscap
    nfull = count // scap + jnp.where(tail, 0, 1)
    nnarrow = jnp.where(tail, (rem + nscap - 1) // nscap, 0)
    return nfull, nnarrow


def run_narrow_tail(make_abody, carry, count, scap: int, between=None):
    """Drive the batched append schedule: full scap-wide batches, then --
    when narrow_tail_cap engages -- the 1-2 narrow tail batches.  The ONE
    driver shared by the single-device and sharded steps; `make_abody`
    builds a fori body for a (width, lo_of) pair, `count` is the (traced)
    sender count -- pmax-agreed by the sharded caller so collective
    counts stay uniform.  `between`, when given, transforms the carry
    after the full-width loop and before the narrow tail (and is applied
    unconditionally even when the narrow loop runs zero trips): the
    pipelined sharded append uses it to flush the last full batch's
    staged drain, so the homogeneous-shape pend carry never crosses into
    the differently-shaped narrow batches."""
    nscap = narrow_tail_cap(scap)
    if nscap:
        nfull, nnarrow = narrow_tail_trips(count, scap, nscap)
    else:
        nfull = (count + scap - 1) // scap
    carry = jax.lax.fori_loop(
        0, nfull, make_abody(scap, lambda jb: jb * scap), carry)
    if between is not None:
        carry = between(carry)
    if nscap:
        full_end = nfull * scap
        carry = jax.lax.fori_loop(
            0, nnarrow,
            make_abody(nscap, lambda jb: full_end + jb * nscap), carry)
    return carry


def sender_batch(senders, srank, scnt, spacked, b: int, scap: int, jb,
                 lo=None, sdelta=None):
    """Extract compacted sender batch `jb`: rows with rank in
    [lo, lo+scap) land at rank-relative positions via one packed
    scatter (in-bounds trash cell at scap, sliced off).  `lo` defaults
    to jb*scap (uniform batches); the narrow-tail path passes the
    absolute start rank.  Returns (sids, stoff, svalid) of static width
    scap; with `sdelta` (multi-rumor per-lane payload words, (ccap, W))
    a fourth value carries each compacted sender's word row."""
    if lo is None:
        lo = jb * scap
    pos = srank - lo
    sel = senders & (pos >= 0) & (pos < scap)
    idx = jnp.where(sel, pos, scap)
    buf = jnp.zeros((scap + 1,), I32).at[idx].set(
        jnp.where(sel, spacked, 0))[:scap]
    sids = buf // b
    stoff = buf - sids * b
    svalid = jnp.arange(scap, dtype=I32) < (scnt - lo)
    if sdelta is not None:
        bufw = jnp.zeros((scap + 1, sdelta.shape[1]), jnp.uint32).at[
            idx].set(jnp.where(sel[:, None], sdelta, jnp.uint32(0)))[:scap]
        return sids, stoff, svalid, bufw
    return sids, stoff, svalid


def apply_fault_window_flags(cfg: Config, flags, down_since, tick,
                             ids_global, base_key, nticks: int):
    """Event-engine adapter for scenario.fault_window: the crashed mask
    lives in flags bit1.  Applied at window start (the window's drain then
    black-holes deliveries to freshly crashed nodes, the event analog of
    the ring engine's per-tick `where(crashed, 0, arrivals)`).  Recovery
    clears ONLY the crashed bit: a recovered node keeps its received (and
    SIR removed) history.  Returns (flags, down_since, d_crash,
    d_recover); a no-op with Python-zero deltas when the scenario has no
    fault events."""
    scen = cfg.scenario_resolved
    if not scen.has_faults:
        return flags, down_since, 0, 0
    crashed = (flags & CRASHED) > 0
    new_crash, recover, down, dc, drc = _scen.fault_window(
        scen, cfg.n, tick, nticks, ids_global, crashed, down_since,
        base_key)
    flags = jnp.where(recover, flags & ~CRASHED, flags)
    flags = jnp.where(new_crash, flags | CRASHED, flags)
    return flags, down, dc, drc


def make_window_step_fn(cfg: Config, n_local: int | None = None):
    """One B-tick window transition: drain this window's packed list in
    chunks (drain_chunk_core), and emit the newly infected nodes' broadcasts
    at their actual delivery ticks.  SIR adds re-broadcast triggers and
    per-sender removal draws (drain_chunk_core with sir=True).

    Scenario faults (crash waves / churn / recovery) apply at window
    start; partition masks filter every append at send time.  With
    -scenario off and -overlay-heal off every gate below is Python-False
    and the traced program is the pre-scenario one, byte for byte."""
    b = batch_ticks(cfg)
    dw = ring_windows(cfg)
    ccap = drain_chunk(cfg, n_local)
    tail = ring_tail(cfg, n_local)
    crash_p = epidemic.p_eff(cfg, cfg.crashrate)
    sir = cfg.protocol == "sir"
    removal_p = epidemic.p_eff(cfg, cfg.removal_rate) if sir else 0.0
    scap = sender_compaction_cap(cfg, ccap)
    # Guaranteed-duplicate suppression (append_messages docstring); the
    # resolved gate implies crash_p == 0 (config.validate rejects "on"
    # otherwise), so the per-reception draw stream it would shift is empty.
    suppress = cfg.dup_suppress_resolved
    scen = cfg.scenario_resolved
    faults = cfg.faults_enabled
    # Scenario gates: the drain must honor crashed bits even at
    # crash_p == 0 once faults can set them; the crash clock is carried
    # only when reception crashes can stamp it; the partition counter is
    # carried only when partitions exist.
    track_crashed = faults or scen.has_faults
    track_down = faults and crash_p > 0.0
    track_part = scen.has_partitions
    # Multi-rumor (static): entry payload words ride the carry alongside
    # mail_ids; injection replaces the seed.  Off => every gate below is
    # Python-False and the traced program is the single-rumor one.
    multi = cfg.multi_rumor
    if multi:
        from gossip_simulator_tpu.ops.mailbox import ring_append
    # Resolved at BUILD time, outside the trace below.
    dkern = cfg.deliver_kernel_resolved

    def step_fn(st: EventState, base_key: jax.Array) -> EventState:
        n = st.flags.shape[0]
        w = st.tick // b
        slot = w % dw
        if multi:
            # Streaming/oneshot injection: self-addressed source entries
            # appended BEFORE the slot count is read, so a rumor due this
            # window drains -- and its source starts forwarding -- this
            # window.  make_seed_fn is an identity under multi.
            ipay, iwords, iwslot, ivalid = injection_batch(
                cfg, st.tick, base_key, b, dw)
            icap = (st.mail_ids.shape[0] - tail) // dw
            (mi, mw), icnt, idrop = ring_append(
                (st.mail_ids, st.mail_words), st.mail_cnt,
                st.mail_dropped, (ipay, iwords), iwslot, ivalid, dw,
                icap, kernel=dkern)
            st = st._replace(mail_ids=mi, mail_words=mw, mail_cnt=icnt,
                             mail_dropped=idrop)
        m = st.mail_cnt[0, slot]
        dm0 = st.sup_cnt[0, slot]
        mail0 = st.mail_ids
        flags1, down1, dsc, dsr = apply_fault_window_flags(
            cfg, st.flags, st.down_since, st.tick,
            jnp.arange(n, dtype=I32), base_key, b)
        st = st._replace(flags=flags1, down_since=down1)
        if suppress:
            # Pre-drain compaction: duplicates that slipped past the
            # append-side filter die here, before the sorted drain pays
            # for them -- but only in the endgame regime where the
            # filter's random gather beats the drain it removes
            # (PREDRAIN_MIN_RECV_FRAC).  Zero filter chunks otherwise.
            cap0 = (mail0.shape[0] - tail) // dw
            go = st.total_received >= I32(
                int(PREDRAIN_MIN_RECV_FRAC * n))
            mail0, kept, fdat = predrain_compact(
                b, n, dw, cap0, ccap, sir, st.flags, mail0, slot,
                jnp.where(go, m, 0))
            m = jnp.where(go, kept, m)
            dm0 = dm0 + fdat
        chunks = (m + ccap - 1) // ccap
        ckey = _rng.tick_key(base_key, w, _rng.OP_CRASH)

        # Conditional loop-carry tail: the crash clock rides the chunk
        # loop only when reception crashes can stamp it, the partition
        # counter only when partitions exist -- the scenario-off carry is
        # the pre-scenario tuple exactly.
        def pack(core, down, part, mt=()):
            c = list(core)
            if track_down:
                c.append(down)
            if track_part:
                c.append(part)
            return tuple(c) + tuple(mt)

        def unpack(c):
            core, i = c[:8], 8
            down = part = None
            if track_down:
                down, i = c[i], i + 1
            if track_part:
                part, i = c[i], i + 1
            return core, down, part, c[i:]

        def body(j, carry):
            (flags, mail_ids, mail_cnt, sup_cnt, dm, dr, dc,
             dropped), down, part, mt = unpack(carry)
            mail_words = rumor_words = rrecv = delta_w = None
            if multi:
                mail_words, rumor_words, rrecv = mt
            off0 = j * ccap
            entry_pos = off0 + jnp.arange(ccap, dtype=I32)
            evalid = entry_pos < m
            cap = (mail_ids.shape[0] - tail) // dw
            packed = jax.lax.dynamic_slice(
                mail_ids, (slot * cap + off0,), (ccap,))
            if multi:
                wchunk = jax.lax.dynamic_slice(
                    mail_words, (slot * cap + off0, 0),
                    (ccap, mail_words.shape[1]))
                (flags, cdm, cdr, cdc, ids_s, toff_s, senders, down,
                 rumor_words, delta_w, drecv) = drain_chunk_core(
                    crash_p, b, n, flags, packed, evalid, entry_pos,
                    ckey, sir=sir, track_crashed=track_crashed,
                    down_since=down, win_tick=st.tick, words=wchunk,
                    rumor_words=rumor_words)
                rrecv = rrecv + drecv
            else:
                flags, cdm, cdr, cdc, ids_s, toff_s, senders, down = \
                    drain_chunk_core(crash_p, b, n, flags, packed, evalid,
                                     entry_pos, ckey, sir=sir,
                                     track_crashed=track_crashed,
                                     down_since=down, win_tick=st.tick)
            dm, dr, dc = dm + cdm, dr + cdr, dc + cdc
            if scap:
                # Compact senders to <=scap-row batches (sender_batch),
                # then append at reduced width.  Same (tick, row)-keyed
                # RNG streams, same reservation order => bit-identical
                # mail layout and totals (canary-checked).
                srank = jnp.cumsum(senders.astype(I32)) - 1
                scnt = senders.sum(dtype=I32)
                spacked = ids_s * b + toff_s

                def make_abody(width, lo_of):
                    def abody(jb, acarry):
                        (aflags, amail_ids, amail_cnt, asup,
                         adropped) = acarry[:5]
                        i = 5
                        apart = awords = sw = None
                        if track_part:
                            apart, i = acarry[i], i + 1
                        if multi:
                            awords = acarry[i]
                            sids, stoff, svalid, sw = sender_batch(
                                senders, srank, scnt, spacked, b, width,
                                jb, lo=lo_of(jb), sdelta=delta_w)
                        else:
                            sids, stoff, svalid = sender_batch(
                                senders, srank, scnt, spacked, b, width,
                                jb, lo=lo_of(jb))
                        stick2 = w * b + stoff
                        strig = None
                        if sir:
                            # Removal draw per sender at its send tick
                            # (the ring engine's removal-after-send,
                            # tick_core); removed senders still broadcast
                            # this once but schedule no next trigger.
                            rows = jnp.where(svalid, sids, n)
                            rk = _sender_keys(base_key, _rng.OP_REMOVE,
                                              stick2, rows)
                            rem = (jax.vmap(
                                lambda kk: jax.random.bernoulli(
                                    kk, removal_p))(rk) & svalid) \
                                if removal_p > 0.0 \
                                else jnp.zeros((width,), bool)
                            aflags = aflags.at[
                                jnp.where(rem, sids, n)].add(
                                REMOVED, mode="drop")
                            strig = svalid & ~rem
                        if multi:
                            (amail_ids, amail_cnt, adropped, sa, ablk,
                             awords) = append_messages(
                                cfg, amail_ids, amail_cnt, adropped,
                                sids, svalid, stick2, st.friends,
                                st.friend_cnt, base_key, swords=sw,
                                mail_words=awords, kernel=dkern)
                        else:
                            (amail_ids, amail_cnt, adropped, sa,
                             ablk) = append_messages(
                                cfg, amail_ids, amail_cnt, adropped,
                                sids, svalid, stick2, st.friends,
                                st.friend_cnt, base_key, strig=strig,
                                flags=aflags if suppress else None,
                                kernel=dkern)
                        out = (aflags, amail_ids, amail_cnt,
                               asup + sa[None, :], adropped)
                        if track_part:
                            out = out + (apart + ablk,)
                        if multi:
                            out = out + (awords,)
                        return out
                    return abody

                # Small remainders run as 1-2 narrow batches at ~op-floor
                # cost instead of one element-bound full-width batch
                # (narrow_tail_cap's rationale; run_narrow_tail drives).
                acarry0 = (flags, mail_ids, mail_cnt, sup_cnt, dropped)
                if track_part:
                    acarry0 = acarry0 + (part,)
                if multi:
                    acarry0 = acarry0 + (mail_words,)
                out = run_narrow_tail(make_abody, acarry0, scnt, scap)
                (flags, mail_ids, mail_cnt, sup_cnt, dropped) = out[:5]
                if track_part:
                    part = out[5]
                if multi:
                    mail_words = out[-1]
                return pack((flags, mail_ids, mail_cnt, sup_cnt, dm, dr,
                             dc, dropped), down, part,
                            (mail_words, rumor_words, rrecv)
                            if multi else ())
            sticks = w * b + toff_s
            strig = None
            if sir:
                # Removal draw per sender at its send tick (the ring
                # engine's removal-after-send, tick_core); removed senders
                # still broadcast this once but schedule no next trigger.
                rows = jnp.where(senders, ids_s, n)
                rk = _sender_keys(base_key, _rng.OP_REMOVE, sticks, rows)
                rem = jax.vmap(lambda kk: jax.random.bernoulli(
                    kk, removal_p))(rk) & senders if removal_p > 0.0 \
                    else jnp.zeros(senders.shape, bool)
                flags = flags.at[jnp.where(rem, ids_s, n)].add(
                    REMOVED, mode="drop")
                strig = senders & ~rem
            # Dense append (low-degree configs): the mask feeds
            # append_messages directly -- senders appear in the same
            # ascending-id order a nonzero() compaction would produce, so
            # reservation ranks and the mail layout are bit-identical.
            # (Measured 2026-07-30: compacting senders to ccap/2 via
            # first_true_indices before the append was bit-identical but
            # ~6-10% SLOWER at n=1e7/1e8 fanout 3 -- the 5-op selection
            # cost more than the 2.4x width saving; the 2-op rank-scatter
            # compaction above pays only at higher degree.)
            if multi:
                (mail_ids, mail_cnt, dropped, sa, blk,
                 mail_words) = append_messages(
                    cfg, mail_ids, mail_cnt, dropped,
                    jnp.where(senders, ids_s, 0), senders, sticks,
                    st.friends, st.friend_cnt, base_key,
                    swords=delta_w, mail_words=mail_words, kernel=dkern)
            else:
                mail_ids, mail_cnt, dropped, sa, blk = append_messages(
                    cfg, mail_ids, mail_cnt, dropped,
                    jnp.where(senders, ids_s, 0), senders, sticks,
                    st.friends, st.friend_cnt, base_key, strig=strig,
                    flags=flags if suppress else None, kernel=dkern)
            if track_part:
                part = part + blk
            return pack((flags, mail_ids, mail_cnt, sup_cnt + sa[None, :],
                         dm, dr, dc, dropped), down, part,
                        (mail_words, rumor_words, rrecv)
                        if multi else ())

        z = jnp.zeros((), I32)
        # Credit this window's deferred duplicate counts (banked by
        # append_messages at append time) exactly where their deliveries
        # would have counted; appends during this drain only target later
        # windows (delay >= B), so the slot accrues nothing new before the
        # zeroing below.
        mt0 = ()
        if multi:
            mt0 = (st.mail_words, st.rumor_words,
                   jnp.zeros_like(st.rumor_recv))
        out = jax.lax.fori_loop(
            0, chunks, body,
            pack((st.flags, mail0, st.mail_cnt, st.sup_cnt,
                  dm0, z, z, st.mail_dropped), st.down_since, z, mt0))
        (flags, mail_ids, mail_cnt, sup_cnt, dm, dr, dc,
         dropped), down, part, mt = unpack(out)
        mail_cnt = mail_cnt.at[0, slot].set(0)
        sup_cnt = sup_cnt.at[0, slot].set(0)
        st = st._replace(
            flags=flags, mail_ids=mail_ids,
            mail_cnt=mail_cnt, sup_cnt=sup_cnt, tick=st.tick + b,
            total_message=msg64_add(st.total_message, dm),
            total_received=st.total_received + dr,
            total_crashed=st.total_crashed + dc,
            mail_dropped=dropped)
        if multi:
            # The drained slot's stale words are never zeroed: the next
            # cycle's appends rewrite the [0, count) prefix and the drain
            # zeroes words past the count (evalid gate in
            # drain_chunk_core), so no stale word is ever read.
            mail_words, rumor_words, rrecv = mt
            rumor_recv = st.rumor_recv + rrecv
            rumor_done = stamp_rumor_done(cfg, rumor_recv, st.rumor_done,
                                          st.tick)
            st = st._replace(mail_words=mail_words,
                             rumor_words=rumor_words,
                             rumor_recv=rumor_recv,
                             rumor_done=rumor_done)
        if track_down:
            st = st._replace(down_since=down)
        if scen.active:
            st = st._replace(
                scen_crashed=st.scen_crashed + dsc,
                scen_recovered=st.scen_recovered + dsr)
        if track_part:
            st = st._replace(part_dropped=st.part_dropped + part)
        return st

    return step_fn


def make_seed_fn(cfg: Config):
    """Uniform-random sender's initial broadcast (simulator.go:240-241),
    through the same append path as every later wave.  Uses the ring
    engine's SEED_TICK-keyed streams: a dedicated one-sender append so the
    seed's delay/drop draws do not depend on tick-0 window state.

    Multi-rumor: an identity -- sources are injected by the window step
    itself (injection_batch appends self-addressed entries, so a source
    counts as infected when its entry DRAINS, and oneshot lanes only
    validate in window 0).  Backends still call seed() unconditionally."""
    if cfg.multi_rumor:
        def seed_noop(st: EventState, base_key: jax.Array) -> EventState:
            return st

        return seed_noop

    def seed_fn(st: EventState, base_key: jax.Array) -> EventState:
        n = st.flags.shape[0]
        b = batch_ticks(cfg)
        dw = ring_windows(cfg)
        cap = (st.mail_ids.shape[0] - ring_tail(cfg, n)) // dw
        ks = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_SEED_NODE)
        kd = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_DELAY)
        kp = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_DROP)
        sender = jax.random.randint(ks, (), 0, n, dtype=I32)
        flags, total_received = st.flags, st.total_received
        if cfg.protocol == "sir" or not cfg.compat_reference:
            # Reference quirk: the seed itself is never marked received
            # (SURVEY §5.4); we count it unless compat is requested.  SIR
            # always marks it: trigger firing requires the received bit (the
            # reference has no SIR, so there is no compat surface to match).
            flags = flags.at[sender].set(RECEIVED)
            total_received = total_received + 1
        k = st.friends.shape[1]
        sf = st.friends[sender]
        scnt = st.friend_cnt[sender]
        delay = jnp.maximum(
            jax.random.randint(jax.random.fold_in(kd, sender), (),
                               cfg.delaylow, cfg.delayhigh, dtype=I32), 1)
        drop = _rng.bernoulli(jax.random.fold_in(kp, sender),
                              epidemic.p_eff(cfg, cfg.droprate), (k,))
        arrive = st.tick + delay
        wslot = (arrive // b) % dw
        edge = (jnp.arange(k, dtype=I32) < scnt) & ~drop & (sf >= 0)
        scen = cfg.scenario_resolved
        if scen.has_partitions:
            blocked = _scen.partition_blocked(
                scen, cfg.n, st.tick, sender, sf) & edge
            st = st._replace(
                part_dropped=st.part_dropped + blocked.sum(dtype=I32))
            edge = edge & ~blocked
        payload = sf * b + arrive % b
        cols = jnp.cumsum(edge, dtype=I32) - 1  # exact-size, like append
        ec = edge.sum(dtype=I32)
        if cfg.protocol == "sir":
            # The seed is a sender like any other: a removal draw decides
            # whether it schedules a re-broadcast trigger (the ring
            # engine's SEED_TICK OP_REMOVE draw).
            kr = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_REMOVE)
            keep = ~_rng.bernoulli(kr, epidemic.p_eff(cfg, cfg.removal_rate),
                                   ())
            tb = trigger_base(n, b)
            cols = jnp.concatenate([cols, ec[None]])
            edge = jnp.concatenate([edge, keep[None]])
            payload = jnp.concatenate(
                [payload, (tb + sender * b + arrive % b)[None]])
            ec = ec + keep.astype(I32)
        base = st.mail_cnt[0, wslot]
        ok = base + ec <= cap
        flat = jnp.where(edge & ok, wslot * cap + base + cols,
                         dw * cap + jnp.arange(edge.shape[0], dtype=I32))
        mail_ids = st.mail_ids.at[flat].set(
            jnp.where(edge, payload, 0))  # trash cell if !ok / non-edge
        mail_cnt = st.mail_cnt.at[0, wslot].add(jnp.where(ok, ec, 0))
        dropped = st.mail_dropped + jnp.where(ok, 0, ec)
        return st._replace(flags=flags, total_received=total_received,
                           mail_ids=mail_ids, mail_cnt=mail_cnt,
                           mail_dropped=dropped)

    return seed_fn


def make_heal_fn(cfg: Config, n_local: int | None = None):
    """Single-device event-engine overlay healing (None when off): condemn
    dead friends (scenario.detect_dead), replace them via the phase-1
    makeup draw, and append the infected healers' re-sends into the mail
    ring at their drawn arrival ticks (scenario.heal_and_wave)."""
    if not cfg.overlay_heal_resolved:
        return None
    from gossip_simulator_tpu.ops.mailbox import ring_append

    b = batch_ticks(cfg, n_local)
    dw = ring_windows(cfg, n_local)
    detect = cfg.heal_detect_ms
    multi = cfg.multi_rumor

    dkern = cfg.deliver_kernel_resolved

    def heal_fn(st: EventState, base_key: jax.Array) -> EventState:
        n, k = st.friends.shape
        ids = jnp.arange(n, dtype=I32)
        crashed = (st.flags & CRASHED) > 0
        detected = _scen.detect_dead(crashed, st.down_since, st.tick,
                                     detect)
        healer_ok = ~crashed
        sender_inf = ((st.flags & RECEIVED) > 0) & ~crashed \
            & ~((st.flags & REMOVED) > 0)
        bits = _scen.heal_peer_bits(detected, sender_inf)
        friends, resend, pull, delay, clear, rep, blk = _scen.heal_and_wave(
            cfg, st.friends, st.friend_cnt, bits, healer_ok, sender_inf,
            _scen.rejoined_mask(st.down_since), ids, st.tick, base_key)
        arrive = st.tick + delay  # per healer row (shared across its lanes)
        wslot = jnp.broadcast_to(((arrive // b) % dw)[:, None],
                                 (n, k)).reshape(-1)
        off = (arrive % b)[:, None]
        payload = (friends * b + off).reshape(-1)
        cap = (st.mail_ids.shape[0] - ring_tail(cfg, n_local)) // dw
        if multi:
            wc = st.rumor_words.shape[1]
            # Resends carry the healer's FULL rumor set; a churned node
            # rejoin-pulls ALL of its friend's rumors (the per-rumor
            # generalization of the single "infected" bit).
            rw = jnp.broadcast_to(st.rumor_words[:, None, :],
                                  (n, k, wc)).reshape(-1, wc)
            (mail, mailw), cnt, dropped = ring_append(
                (st.mail_ids, st.mail_words), st.mail_cnt,
                st.mail_dropped, (payload, rw), wslot,
                resend.reshape(-1), dw, cap, kernel=dkern)
            ppay = jnp.broadcast_to((ids * b)[:, None] + off,
                                    (n, k)).reshape(-1)
            fw = st.rumor_words[jnp.where(friends >= 0, friends,
                                          0)].reshape(-1, wc)
            (mail, mailw), cnt, dropped = ring_append(
                (mail, mailw), cnt, dropped, (ppay, fw), wslot,
                pull.reshape(-1), dw, cap, kernel=dkern)
            st = st._replace(mail_words=mailw)
        else:
            (mail,), cnt, dropped = ring_append(
                (st.mail_ids,), st.mail_cnt, st.mail_dropped, (payload,),
                wslot, resend.reshape(-1), dw, cap, kernel=dkern)
            # Rejoin pull responses deliver to the puller's OWN row.
            ppay = jnp.broadcast_to((ids * b)[:, None] + off,
                                    (n, k)).reshape(-1)
            (mail,), cnt, dropped = ring_append(
                (mail,), cnt, dropped, (ppay,), wslot, pull.reshape(-1),
                dw, cap, kernel=dkern)
        return st._replace(
            friends=friends, mail_ids=mail, mail_cnt=cnt,
            mail_dropped=dropped,
            down_since=jnp.where(clear, -1, st.down_since),
            heal_repaired=st.heal_repaired + rep,
            part_dropped=st.part_dropped + blk)

    return heal_fn


def make_window_fn(cfg: Config, window: int):
    """Advance ~`window` simulated ms as one device call (the driver's poll
    cadence): ceil(window / B) batched window steps, then -- with
    -overlay-heal on -- one healing pass (the same cadence and tick keys
    the fast-path loop heals at)."""
    step = make_window_step_fn(cfg)
    heal = make_heal_fn(cfg)
    steps = max(1, -(-window // batch_ticks(cfg)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def window_fn(st: EventState, base_key: jax.Array) -> EventState:
        st = jax.lax.fori_loop(0, steps, lambda _, s: step(s, base_key), st)
        if heal is not None:
            st = heal(st, base_key)
        return st

    return window_fn


def poll_window_steps(cfg: Config) -> int:
    """B-tick steps per 10 ms poll window: the cadence every fast-path run
    cond must check at so it reports the same death tick / totals as the
    windowed driver loop (with B < 10 a per-step check stops earlier).  The
    10 is base.WINDOW_MS, hardcoded to keep models/ free of backends/
    imports.  Shared by this engine's run fn and the sharded one
    (parallel/event_sharded.make_run_to_coverage_fn)."""
    return max(1, -(-10 // batch_ticks(cfg)))


def make_run_to_coverage_fn(cfg: Config, telemetry: bool = False):
    """Bounded device-side while_loop, same contract as the ring engine's
    (epidemic.make_run_to_coverage_fn / base.run_bounded_to_target).  With
    `telemetry`, carries the device-resident per-window History and records
    one counters row per poll window (signature gains a `hist` argument and
    the return becomes `(st, hist)`)."""
    step = make_window_step_fn(cfg)
    heal = make_heal_fn(cfg)
    max_steps = cfg.max_rounds
    steps = poll_window_steps(cfg)
    # Healing can revive an empty ring (pending dead-friend detections
    # re-send from infected healers), so heal-on runs drop the early-death
    # exit (see epidemic.make_run_to_coverage_fn).
    check_in_flight = not cfg.overlay_heal_resolved
    multi = cfg.multi_rumor
    rumors = cfg.rumors
    stream = cfg.traffic == "stream"
    last_inj = cfg.last_inject_tick

    def cond_live(s: EventState, target_count, until):
        # The in-flight term (a dw-element emptiness test -- free) stops
        # the loop the moment the wave dies instead of spinning empty
        # windows to max_rounds (the host-side exhaustion check only
        # runs between bounded calls).
        if multi:
            # Every rumor must hit the target; lanes >= R are padding
            # (always 0), so the static [:R] slice is load-bearing.
            recv = jnp.min(s.rumor_recv[:rumors])
        else:
            recv = s.total_received
        live = ((recv < target_count)
                & (s.tick < max_steps) & (s.tick < until))
        if check_in_flight:
            alive = in_flight(s) > 0
            if multi:
                # An empty ring is not death while the injection
                # schedule still has rumors to start -- including tick 0
                # of a oneshot run (last_inj = 0), where seeding happens
                # INSIDE the first window step rather than before the
                # loop (seed() is a no-op under the rumor axis).
                alive = alive | (s.tick <= last_inj)
            live = live & alive
        return live

    def run_window(s: EventState, base_key):
        s = jax.lax.fori_loop(0, steps, lambda _, x: step(x, base_key), s)
        if heal is not None:
            s = heal(s, base_key)
        return s

    if telemetry:
        from gossip_simulator_tpu.utils import telemetry as telem

        sir = cfg.protocol == "sir"
        spatial = telem.spatial_spec(cfg)

        @functools.partial(jax.jit, donate_argnums=(0, 4))
        def run_fn_t(st: EventState, base_key: jax.Array,
                     target_count: jax.Array, until: jax.Array,
                     hist: "telem.History"):
            def cond(carry):
                s, _ = carry
                return cond_live(s, target_count, until)

            def body(carry):
                s, h = carry
                s = run_window(s, base_key)
                row = telem.gossip_probe(
                    s, sir, rumors=rumors if multi else 0)
                return s, telem.record_window(h, row, st=s, spec=spatial)

            return jax.lax.while_loop(cond, body, (st, hist))

        return run_fn_t

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_fn(st: EventState, base_key: jax.Array, target_count: jax.Array,
               until: jax.Array) -> EventState:
        def cond(s: EventState):
            return cond_live(s, target_count, until)

        return jax.lax.while_loop(cond, lambda s: run_window(s, base_key), st)

    return run_fn


def removed_count(st) -> jnp.ndarray:
    """SIR removed-node count, engine-agnostic: no counter is threaded
    through the hot loop -- the removed set lives in the state (flags bit2 /
    SimState.removed), one O(n) reduction per host poll."""
    if hasattr(st, "flags"):
        return ((st.flags & REMOVED) > 0).sum(dtype=I32)
    return st.removed.sum(dtype=I32)
