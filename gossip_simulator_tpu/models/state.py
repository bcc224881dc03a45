"""Struct-of-arrays state pytrees.

The reference's per-node ``Node`` struct (simulator.go:34-46) becomes one
struct-of-arrays over the node axis; every field shards trivially on that
axis for the sharded backend.  Counters live on device and are fetched once
per progress window.

Counter widths (SURVEY §5.5 prescribes int64 where the reference's int32
atomics can overflow, simulator.go:26-31): ``total_received`` /
``total_crashed`` are bounded by n (int32 is safe to n = 2^31), but
``total_message`` counts every delivery and SIR re-broadcasts indefinitely --
at n = 1e8 it crosses 2^31 within a few hundred simulated seconds.  It is
therefore a 64-bit counter, represented as a uint32 ``[hi, lo]`` pair
(``msg64_*`` helpers below) rather than a jnp.int64 scalar: enabling
jax_enable_x64 globally would flip every unannotated jax.random draw to
float64/int64, changing the bit-exact RNG streams the parity tests pin and
dragging emulated-f64 ops onto the TPU hot path.  The pair costs three
scalar ops per accumulation and nothing else.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax.numpy as jnp


def msg64_zero() -> jnp.ndarray:
    """Device-side 64-bit counter: uint32 [hi, lo] = 0."""
    return jnp.zeros((2,), jnp.uint32)


def msg64_add(c: jnp.ndarray, delta) -> jnp.ndarray:
    """c + delta with carry.  `delta` is a nonnegative int32/uint32 scalar
    (per-tick/per-window deltas are bounded by the mail-ring / delay-ring
    per-slot capacities, all sized below 2^31 entries)."""
    d = delta.astype(jnp.uint32)
    lo = c[1] + d
    carry = (lo < d).astype(jnp.uint32)  # uint32 add wraps iff result < d
    return jnp.stack([c[0] + carry, lo])


def msg64_value(c) -> int:
    """Host-side Python int from a fetched [hi, lo] pair (also accepts a
    legacy scalar from pre-widening checkpoints)."""
    a = np.asarray(c)
    if a.ndim == 0:
        return int(a)
    a = a.astype(np.uint64)
    return int((a[0] << np.uint64(32)) | a[1])


class SimState(NamedTuple):
    """Epidemic-phase state (phase 2).  Node axis = leading axis of 1-D/2-D
    fields; `pending`/`rebroadcast` are ring buffers over delay ticks."""

    received: jnp.ndarray  # bool[n]   ever infected (simulator.go:38)
    crashed: jnp.ndarray  # bool[n]    (simulator.go:39)
    removed: jnp.ndarray  # bool[n]    SIR only; removed => stops forwarding
    friends: jnp.ndarray  # int32[n, k]  -1-padded adjacency (simulator.go:45)
    friend_cnt: jnp.ndarray  # int32[n]
    pending: jnp.ndarray  # int32[d * n] arrival-count ring (per shard: ring_row)
    rebroadcast: jnp.ndarray  # bool[d, n]  SIR re-broadcast schedule
    tick: jnp.ndarray  # int32[]
    total_message: jnp.ndarray  # uint32[2] hi/lo 64-bit pair (simulator.go:31)
    total_received: jnp.ndarray  # int32[]  (simulator.go:29)
    total_crashed: jnp.ndarray  # int32[]  (simulator.go:30)
    # Framework-only: cross-shard all_to_all bucket overflow (0 on one chip;
    # counted, never silently lost -- SURVEY §7.3 hard part #4).
    exchange_overflow: jnp.ndarray  # int32[]
    # --- fault-injection scenario (scenario.py) --------------------------
    # Crash tick per node (-1 = live / unknown): the recovery clock and the
    # healer's dead-friend detection window.  Full (n,) only when the
    # fault machinery is on (Config.faults_enabled); a 1-element
    # placeholder otherwise, so fault-free runs pay nothing.
    down_since: jnp.ndarray  # int32[n | 1]
    scen_crashed: jnp.ndarray  # int32[]  scenario-crashed (waves + churn)
    scen_recovered: jnp.ndarray  # int32[]  nodes rebooted after downtime
    part_dropped: jnp.ndarray  # int32[]  sends black-holed by partitions
    heal_repaired: jnp.ndarray  # int32[]  dead friend edges replaced
    # --- multi-rumor traffic (Config.multi_rumor) ------------------------
    # Packed per-rumor infection bits (W = ceil(R/32) uint32 words per
    # node) and per-rumor arrival counts over the delay ring.  1-element
    # placeholders when multi_rumor is off, so the default single-rumor
    # build traces no rumor-axis op (the down_since convention).
    pending_rumors: jnp.ndarray  # int32[d, n, R | 1x1x1]  per-rumor arrivals
    rumor_words: jnp.ndarray  # uint32[n, W | 1x1]  per-node rumor bitmask
    rumor_recv: jnp.ndarray  # int32[W*32 | 1]  per-rumor infected count
    rumor_done: jnp.ndarray  # int32[W*32 | 1]  tick rumor hit target (-1)
    # --- spatial telemetry (Config.telemetry_spatial) --------------------
    # Cumulative routed-exchange counters, int32[1, S+2] when the spatial
    # panels are on under S > 1 shards ([:S] delivered sends per dest
    # shard, [S] deliveries received, [S+1] bucket overflow), a 1x1
    # placeholder otherwise (the down_since convention).  Node-axis
    # leading like mail_cnt so shards stack to (S, S+2) under P(AXIS,).
    exch_counts: jnp.ndarray  # int32[1, S+2 | 1x1]


def init_exch_counts(cfg, n_shards: int = 1) -> jnp.ndarray:
    """Per-shard routed-exchange counter leaf (see SimState.exch_counts).
    Full (1, S+2) only when the spatial panels record under a sharded
    run; every other build keeps the 1x1 placeholder so the default
    program traces no counting op."""
    w = (n_shards + 2
         if (cfg.telemetry_spatial_enabled and n_shards > 1) else 1)
    return jnp.zeros((1, w), jnp.int32)


def in_flight(st) -> jnp.ndarray:
    """int32 0/1: nonzero iff any message is still undelivered --
    engine-agnostic (EventState or SimState; duck-typed on the mail ring so
    this module needs no import of either engine).  An indicator, NOT a
    count: every caller only tests emptiness, and a full count would
    overflow int32 when summed across shards near ring occupancy
    (event.slot_cap clamps each shard to ~2^31 entries).  THE single
    definition of "wave still alive": the host exhaustion check
    (backends/base.run_bounded_to_target) and every engine's device-side
    run cond all call this, so they cannot drift."""
    if hasattr(st, "mail_cnt"):
        # sup_cnt: deferred duplicate-suppression credits (EventState) --
        # pending windows must still drain so total_message is credited at
        # the same tick the unsuppressed path would have counted it.
        live = jnp.any(st.mail_cnt > 0)
        if hasattr(st, "sup_cnt"):
            live = live | jnp.any(st.sup_cnt > 0)
        return live.astype(jnp.int32)
    return (jnp.any(st.pending > 0) | jnp.any(st.rebroadcast)).astype(
        jnp.int32)


class OverlayState(NamedTuple):
    """Overlay-construction state (phase 1).  Message buffers hold the
    makeups/breakups emitted this round, delivered next round (the vectorized
    stand-in for the reference's delayed channel sends, simulator.go:151-164).
    """

    friends: jnp.ndarray  # int32[n, k]
    friend_cnt: jnp.ndarray  # int32[n]
    # Slot-major (slots, n) with slots = the mailbox cap EXACTLY: the node
    # axis is minormost (tile-friendly) and the slot count is a multiple
    # of the T(8,128) sublane tile -- (10, 1e8) padded 1.6x to 5.96 GB and
    # broke the 100M single-chip build (round 4).  Bootstrap emissions
    # (one per node per round) live in their own flat vector, delivered
    # after the reply slots -- the same order the (cap+2)-wide layout
    # produced.
    mk_dst: jnp.ndarray  # int32[cap, n]  makeup emissions (dst per slot; src=lane)
    bk_dst: jnp.ndarray  # int32[cap, n]  breakup emissions
    boot_dst: jnp.ndarray  # int32[n]  bootstrap makeups (src=lane)
    # Mailbox-overflow spill: (src, dst) pairs a full mailbox could not
    # take this round, re-delivered FIRST next round -- the reference's
    # channel-full backpressure delays membership traffic, never loses it
    # (simulator.go:51-54).  -1-padded; beyond-spill-capacity messages
    # still fall through to mailbox_dropped (counted, never silent).
    # Filled only on the single-device column-delivery paths (the
    # flagship-scale regime where overflow was ever observed); the
    # sharded hook path keeps counted drops.
    mk_spill: jnp.ndarray  # int32[2, SPILL_CAP(+1)]  overflowed makeups
    bk_spill: jnp.ndarray  # int32[2, SPILL_CAP(+1)]  overflowed breakups
    round: jnp.ndarray  # int32[]
    makeups: jnp.ndarray  # int32[]  cumulative processed (MakeUps)
    breakups: jnp.ndarray  # int32[]  (BreakUps)
    win_makeups: jnp.ndarray  # int32[]  this round's count
    win_breakups: jnp.ndarray  # int32[]
    mailbox_dropped: jnp.ndarray  # int32[]  capacity overflow (divergence counter)
