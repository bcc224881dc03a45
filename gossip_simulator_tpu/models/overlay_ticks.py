"""Tick-faithful dynamic-overlay construction (phase 1, `-overlay-mode ticks`).

The round-based engine (models/overlay.py) quantizes time: every emission is
delivered exactly one round later, and stabilization time is estimated as
rounds x mean_delay.  This engine keeps the reference's timing model instead:
every makeup/breakup send draws its OWN uniform delay in
[delaylow, delayhigh) ms (simulator.go:151-164, RandomNetworkDelay at
166-168), messages sit in a packed window-slot ring (the same layout as the
phase-2 event engine, models/event.py), and the stabilization clock is true
simulated milliseconds -- upgrading phase 1 to the same "option (b) faithful
ticks" story phase 2 already has (SURVEY §5.8).

Sequencing per B-tick window (B = min(10, delaylow), so a message emitted in
one window always arrives in a later one):
  1. drain this window's ring slot; stable-sort entries by arrival tick so
     per-node mailbox order is arrival order;
  2. deliver breakups / makeups into fixed-capacity mailboxes
     (ops/mailbox.deliver_pair) and process them slot-sequentially,
     node-parallel with the SAME per-message decision rules as the round
     engine (accept-under-fanin / evict-random / replace-on-breakup,
     simulator.go:66-94);
  3. every emission (replacement makeup, eviction breakup) is appended to
     the ring at its trigger's arrival tick plus a fresh per-message delay.

Bootstrap is a window-0 burst: the reference's needNewFriend loop re-arms
with no delay (simulator.go:103-105), so a node fills all `fanout` slots
at t~0, each makeup carrying an independent delay -- and once a node
reaches fanout it can never drop below it (breakup under/at fanout
replaces in place; removal only happens above fanout), so the loop never
re-fires.  init_state therefore draws the whole initial friends table and
appends the n*fanout makeup burst directly.

Quiescence is race-free and in the reference's own terms: a full 10 ms poll
window with zero processed membership messages AND an empty ring
(simulator.go:221-234 without the read-reset race, SURVEY §5.2).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models.overlay import (process_breakup_slot,
                                                 process_makeup_slot,
                                                 spill_enabled)
from gossip_simulator_tpu.ops.mailbox import deliver_pair
from gossip_simulator_tpu.ops.select import first_true_indices
from gossip_simulator_tpu.utils import rng as _rng

I32 = jnp.int32

MK = 0  # payload type bits: makeup
BK = 1  # breakup

# Mailbox-overflow spill capacity for THIS engine's cap-8 memory band
# (round 7, VERDICT r5 #4 -- the last counted-drop surface): overflowed
# (pay, typ*n+dst) pairs re-deliver FIRST next window instead of dropping,
# the reference's channel-full backpressure (senders block; membership
# traffic is delayed, never lost -- simulator.go:51-54).  Sizing rationale
# matches overlay.SPILL_CAP (the rounds engine observed 257 overflow
# messages TOTAL at 1e8/cap 8); past the spill cap messages still fall
# through to counted drops.  Module-level so tests can zero it (the
# control run of the spill suite).
SPILL_CAP = 65_536

# Prefix-dense drain delivery (round 7): after the drain's stable toff
# sort, the live entries are a packed PREFIX of known length (the ring
# count), so the chunked delivery runs plain ascending ranges with no
# per-chunk compaction scans (ops.mailbox deliver_pair prefix_len) --
# bit-identical, and the scans were the dominant term of the 10M chunk
# sweep (the justification for raising config.OVERLAY_TICKS_AUTO_MAX to
# 10M).  Module-level so the A/B test can pin prefix == masked.
PREFIX_DRAIN = True


def ticks_spill_cap(cfg: Config, n_rows: int | None = None) -> int:
    """Spill capacity for a single-device ticks surface (0 = disabled):
    engages exactly where drops were ever possible -- the slot-major
    memory band's shrunken stacked mailbox cap (spill_enabled mirrors the
    rounds engine: cap 16 overflow needs in-degree > 16 in one window,
    never observed, and threading the accumulator costs real op floors).
    The sharded hook path keeps counted drops (its routed delivery has no
    spill, like the sharded rounds overlay)."""
    n = n_rows if n_rows is not None else cfg.n
    cap_mb = cfg.mailbox_cap_for(n, stacked=True)
    return SPILL_CAP if (slotmajor(n) and spill_enabled(cap_mb)) else 0


# Narrowest occupancy-adaptive drain width (make_step_fn): windows with
# fewer live entries still sort this many lanes -- one sort's fixed cost
# is flat below ~262k on v5e, so narrower buys nothing in production.
# Module-level so a CPU test can lower it and drive the multi-branch
# switch at test n.
_DRAIN_WIDTH_FLOOR = 262_144


def batch_ticks(cfg: Config) -> int:
    """Window size B: delays >= delaylow >= B guarantee no intra-window
    causality; also bounded so pay = (src*2+type)*b + toff fits int32."""
    b = max(1, min(10, cfg.delaylow))
    while b > 1 and (2 * cfg.n + 2) * b >= 2**31:
        b //= 2
    return b


def ring_windows(cfg: Config) -> int:
    b = batch_ticks(cfg)
    return (b - 1 + cfg.delayhigh - 1) // b + 1


def slot_cap(cfg: Config, n_local: int | None = None) -> int:
    """Packed entries per window slot (per shard: destinations are uniform,
    so a shard's share of the traffic scales with its row count).  Peak
    traffic is the bootstrap burst (n*fanout makeups) spread over the delay
    span, plus a comparable response wave; 2x covers skew.  Overflow is
    counted, never silent."""
    n = n_local if n_local is not None else cfg.n
    b = batch_ticks(cfg)
    dw = ring_windows(cfg)
    cap = max(4096, int(math.ceil(
        2.0 * n * cfg.fanout * b / max(cfg.delay_span, 1))))
    cap = min(cap, (3 * 2**30) // (8 * max(dw, 1)))  # ~3 GB for both arrays
    return min(cap, (2**31 - 2) // max(dw, 1))


def emit_chunk(cfg: Config, n_local: int | None = None) -> int:
    """Emission-compaction chunk (the drain_chunk analog)."""
    n = n_local if n_local is not None else cfg.n
    return min(slot_cap(cfg, n_local), max(4096, min(262_144, n // 8)))


# Slot-major layout band (single-device; module-level so CPU tests can
# lower it and pin the band trajectory at test n).  Memory scale ONLY:
# a 4M band was tried 2026-08-01 to chase the 10M <=70s target and LOST
# (80.0 vs 72.7 s -- per-row emission scans move the same lane volume at
# settled windows, and the flat-mailbox dynamic-slice reads cost more
# than the 2-D column reads at 10M); above ~3.2e7 the node-major
# layouts are outright compile bombs and the band is mandatory.
TICKS_SLOTMAJOR_MIN_ROWS = 32_000_000


def slotmajor(n_rows: int) -> bool:
    """Memory/perf layout band for THIS engine (single-device only; the
    sharded hooks' per-shard slices stay node-major): above the band
    every (n_rows, small) node-major array is a TPU tiling liability --
    T(8,128) pads the narrow minor dim 16-25x, and the 100M ticks build
    died at compile on a 51 GB s32[1e8, 5] copy.  The band switches to
    the layouts the rounds engine adopted in round 4: slot-major
    (cap, n) emission buffers with per-ROW compaction scans (the full
    slots*n flat scan was the dominant settled-window cost), a
    rank-major FLAT stacked mailbox (ops.mailbox.deliver_pair flat=True)
    whose slots are contiguous dynamic_slices, and per-LANE-keyed
    bootstrap/emission draws (no (n, fanout) draw matrix).  Emission
    order becomes slot-major and the draw streams are lane-keyed -- a
    deterministic re-choice of arrival order and sample, the same move
    (and the same honesty argument: the reference's own arrival order is
    goroutine-racy) as the rounds engine's column band; every n below
    the band is bit-identical to round 4."""
    return n_rows >= TICKS_SLOTMAJOR_MIN_ROWS


def ticks_delivery_chunk(cfg: Config, n_rows: int) -> int:
    """Delivery chunk for THIS engine's slot drain (deliver_pair): its
    per-chunk cost is dominated by the scatters into the stacked
    [2n, cap] mailbox, which are ~10-20 ms FLAT per op at GB-scale
    targets regardless of lane count (README roadmap's device-span
    finding) -- so fewer, fatter chunks win at large n, unlike the
    rounds engine's n-wide deliver_columns where the 64k optimum stands
    (re-swept 2026-07-31 at 10M: 64k 3.40 s/window, 262k 2.60, 1M 2.26,
    2M 2.18; rounds mode with 1M chunks LOSES 733 -> 1134 ms/window).
    n/8 rounded up to a power of two (the sort pads internally), floor
    64k (<= 512k rows keeps the swept small-n optimum), cap 2M.
    Chunking is trajectory-neutral (rank continuation), so this is pure
    perf; -compact-chunk overrides.  The 2M cap is a registered tunable
    (tuning.py: overlay_ticks.delivery_chunk_cap)."""
    if cfg.compact_chunk > 0:
        return cfg.compact_chunk
    from gossip_simulator_tpu import tuning as _tuning

    cap = _tuning.value("overlay_ticks.delivery_chunk_cap", cfg)
    want = min(max(65_536, n_rows // 8), cap)
    return 1 << (want - 1).bit_length()


class OverlayTickState(NamedTuple):
    friends: jnp.ndarray  # int32[n, k]  -1 padded
    friend_cnt: jnp.ndarray  # int32[n]
    # Packed ring, slot s at [s*cap, (s+1)*cap); last element = trash cell.
    ring_dst: jnp.ndarray  # int32[dw*cap + 1]
    ring_pay: jnp.ndarray  # int32[dw*cap + 1]  (src*2 + type)*b + toff
    ring_cnt: jnp.ndarray  # int32[1, dw]
    # Mailbox-overflow spill pairs (pay, typ*n + dst), -1-padded key row;
    # re-delivered first next window (ticks_spill_cap; token (2, 1) where
    # the spill is disabled).  In-flight messages: quiescence requires an
    # empty spill.
    spill: jnp.ndarray  # int32[2, sc + 1]
    tick: jnp.ndarray  # int32[]  window-aligned simulated ms
    makeups: jnp.ndarray  # int32[]  cumulative processed (MakeUps)
    breakups: jnp.ndarray  # int32[]
    win_makeups: jnp.ndarray  # int32[]  this POLL window's counts
    win_breakups: jnp.ndarray  # int32[]
    mailbox_dropped: jnp.ndarray  # int32[]  mailbox + ring overflow


def _append(cfg: Config, ring_dst, ring_pay, ring_cnt, dropped,
            dst, pay, wslot, valid):
    """Append one (dst, pay) entry per True in `valid` into its window
    slot (shared one-hot reservation: ops.mailbox.ring_append)."""
    from gossip_simulator_tpu.ops.mailbox import ring_append

    dw = ring_windows(cfg)
    cap = (ring_dst.shape[0] - 1) // dw
    (ring_dst, ring_pay), ring_cnt, dropped = ring_append(
        (ring_dst, ring_pay), ring_cnt, dropped, (dst, pay), wslot, valid,
        dw, cap, kernel=cfg.deliver_kernel_resolved)
    return ring_dst, ring_pay, ring_cnt, dropped


def init_state(cfg: Config, base_key: jax.Array) -> OverlayTickState:
    """Initial friends table + the window-0 bootstrap makeup burst."""
    n, k, f = cfg.n, cfg.max_degree, cfg.fanout
    b = batch_ticks(cfg)
    dw = ring_windows(cfg)
    cap = slot_cap(cfg)
    ids = jnp.arange(n, dtype=I32)
    kb = _rng.tick_key(base_key, 0, _rng.OP_BOOTSTRAP)
    sm = slotmajor(n)
    if sm:
        # Memory band: per-LANE keyed draws, one (n,) column at a time --
        # never materializing the (n, fanout) draw matrix whose tiled
        # copy OOM'd the 100M compile (see slotmajor).  friends columns
        # land via one-hot blends (elementwise on (n, k), the layout the
        # rounds engine already proves at 1e8).
        friends = jnp.full((n, k), -1, I32)
        colsel = jnp.arange(k, dtype=I32)[None, :]
        for j in range(f):
            wj = _rng.row_randint(kb, n, ids * f + j, 1)[:, 0]
            wj = jnp.where(wj == ids, (wj + 1) % n, wj)
            friends = jnp.where(colsel == j, wj[:, None], friends)
        w = None
    else:
        # One independent draw per (node, slot), self patched (id+1)%n
        # (simulator.go:97-100); duplicates allowed, like the reference.
        w = jax.vmap(
            lambda kk: jax.random.randint(kk, (f,), 0, n, dtype=I32))(
            _rng.row_keys(kb, ids))
        w = jnp.where(w == ids[:, None], (w + 1) % n, w)
        friends = jnp.full((n, k), -1, I32).at[:, :f].set(w)
    cnt = jnp.full((n,), f, I32)

    ring_dst = jnp.zeros((dw * cap + 1,), I32)
    ring_pay = jnp.zeros((dw * cap + 1,), I32)
    ring_cnt = jnp.zeros((1, dw), I32)
    z = lambda: jnp.zeros((), I32)
    st = OverlayTickState(
        friends=friends, friend_cnt=cnt,
        ring_dst=ring_dst, ring_pay=ring_pay, ring_cnt=ring_cnt,
        spill=jnp.full((2, ticks_spill_cap(cfg) + 1), -1, I32),
        tick=z(), makeups=z(), breakups=z(),
        win_makeups=z(), win_breakups=z(), mailbox_dropped=z())
    # The burst: n*f makeups at t=0, each with its own delay.  Appended in
    # chunks through the same path as every later emission.
    kd = _rng.tick_key(base_key, 0, _rng.OP_DELAY)
    flat_n = n * f
    chunk = emit_chunk(cfg)

    def append_chunk(i, carry):
        ring_dst, ring_pay, ring_cnt, dropped = carry
        idx = i * chunk + jnp.arange(chunk, dtype=I32)
        valid = idx < flat_n
        src = jnp.where(valid, idx // f, 0)
        if sm:
            # Re-derive the lane's draw from its key (identical to the
            # friends column built above) instead of gathering from a
            # materialized matrix.
            dst = _rng.row_randint(kb, n, idx, 1)[:, 0]
            dst = jnp.where(dst == src, (dst + 1) % n, dst)
        else:
            dst = w.reshape(-1).at[jnp.where(valid, idx, 0)].get()
        delay = _rng.row_uniform_delay(kd, cfg.delaylow, cfg.delayhigh, idx)
        arrive = delay  # emitted at t=0
        return _append(cfg, ring_dst, ring_pay, ring_cnt, dropped,
                       dst, (src * 2 + MK) * b + arrive % b,
                       (arrive // b) % dw, valid)

    ring_dst, ring_pay, ring_cnt, dropped = jax.lax.fori_loop(
        0, -(-flat_n // chunk), append_chunk,
        (ring_dst, ring_pay, ring_cnt, st.mailbox_dropped))
    return st._replace(ring_dst=ring_dst, ring_pay=ring_pay,
                       ring_cnt=ring_cnt, mailbox_dropped=dropped)


def _emit_all(cfg: Config, st_ring, base_key, w, em_dst, em_toff, typ, op,
              lanes_major: bool = False):
    """Compact an (n, cap_mb) emission buffer and append every entry with a
    fresh per-message delay drawn at its trigger's arrival tick.

    `lanes_major` is the memory band's SLOT-major (cap_mb, n) buffer
    layout (see slotmajor): the flat scan order becomes slot-major and
    the sender id is idx % n -- a band-internal re-choice of emission
    order, like the rounds engine's column path."""
    ring_dst, ring_pay, ring_cnt, dropped = st_ring
    b = batch_ticks(cfg)
    dw = ring_windows(cfg)
    cols = em_dst.shape[1]
    flat_n = em_dst.shape[0] * cols
    dflat = em_dst.reshape(-1)
    tflat = em_toff.reshape(-1)
    valid_all = dflat >= 0
    total = valid_all.sum(dtype=I32)
    chunk = min(emit_chunk(cfg), flat_n)
    kd = _rng.tick_key(base_key, w, op)

    def make_body(base_lane, width):
        def body(_, carry):
            ring_dst, ring_pay, ring_cnt, dropped, remaining = carry
            ridx = first_true_indices(remaining, chunk)
            hit = jnp.zeros((width,), bool).at[ridx].set(True, mode="drop")
            remaining = remaining & ~hit
            # first_true_indices pads exhausted lanes to the MASK length
            # (`width`); in per-row mode base_lane + width is the next
            # row's first lane, so padding must be masked by ridx, not by
            # the global bound (a padded lane would otherwise read a real
            # NEXT-row emission and double-emit it).
            ok = ridx < width
            idx = base_lane + ridx  # global lane id (keys the delay draw)
            idx_g = jnp.where(ok, idx, flat_n)
            src = jnp.where(ok, idx % cols if lanes_major else idx // cols,
                            0)
            dst = dflat.at[idx_g].get(mode="fill", fill_value=-1)
            toff = tflat.at[idx_g].get(mode="fill", fill_value=0)
            valid = dst >= 0
            # Row-keyed by flat emission index: deterministic and
            # independent regardless of chunking.
            delay = _rng.row_uniform_delay(kd, cfg.delaylow, cfg.delayhigh,
                                           idx)
            arrive = w * b + toff + delay
            ring_dst, ring_pay, ring_cnt, dropped = _append(
                cfg, ring_dst, ring_pay, ring_cnt, dropped,
                jnp.where(valid, dst, 0),
                (src * 2 + typ) * b + arrive % b,
                (arrive // b) % dw, valid)
            return ring_dst, ring_pay, ring_cnt, dropped, remaining
        return body

    if lanes_major:
        # Per-ROW compaction (the deliver_columns move): each slot row is
        # a contiguous n-lane slice, so the scan pays n lanes per chunk
        # instead of slots*n -- the full flat scan was the dominant
        # settled-window cost at 10M.  Same entries, same slot-major
        # order, same lane-keyed draws; rows with zero emissions cost one
        # n-wide popcount.
        carry = (ring_dst, ring_pay, ring_cnt, dropped)
        for r in range(em_dst.shape[0]):
            rowv = valid_all[r * cols:(r + 1) * cols]
            rtotal = rowv.sum(dtype=I32)
            rchunk = min(chunk, cols)
            carry = jax.lax.fori_loop(
                0, (rtotal + rchunk - 1) // rchunk,
                make_body(r * cols, cols), carry + (rowv,))[:4]
        return carry

    out = jax.lax.fori_loop(0, (total + chunk - 1) // chunk,
                            make_body(0, flat_n),
                            (ring_dst, ring_pay, ring_cnt, dropped,
                             valid_all))
    return out[:4]


def make_step_fn(cfg: Config, n_local: int | None = None, ids_fn=None,
                 key_fn=None, sum_fn=None, emit_fn=None):
    """One B-tick window transition (drain -> deliver -> process -> emit).

    The four hooks make the SAME body run single-device or per-shard inside
    shard_map (parallel/overlay_ticks_sharded.py), mirroring
    overlay.make_round_fn's hook pattern so the two modes cannot diverge:
      ids_fn() -> global ids of the local rows (arange by default).
      key_fn(base_key, w, op) -> per-window op key (the sharded variant
          folds the shard index in first to decorrelate draws).
      sum_fn(x) -> global scalar reduction (identity / psum).
      emit_fn(ring, base_key, w, em_dst, em_toff, typ, op) -> ring, with
          `ring = (ring_dst, ring_pay, ring_cnt, local_dropped)`: local
          append by default, route-then-append when sharded.
    """
    n, k = cfg.n, cfg.max_degree
    n_rows = n_local if n_local is not None else cfg.n
    fanout, fanin = cfg.fanout, cfg.fanin_resolved
    b = batch_ticks(cfg)
    dw = ring_windows(cfg)
    cap = slot_cap(cfg, n_local)
    # Memory-band layouts (single-device only: the sharded hooks keep
    # node-major per-shard slices -- see slotmajor's docstring).
    sm = slotmajor(n_rows) and emit_fn is None
    # Per-LOCAL-rows cap, matching the sharded caller's emit_routed
    # (overlay_ticks_sharded uses the same stacked cap -- a mixed pair
    # would shape-mismatch the emission buffers past n ~ 1.34e8).
    # stacked=True: delivery here is deliver_pair's [2n, cap] addressing.
    cap_mb = cfg.mailbox_cap_for(n_rows, stacked=True)
    dchunk = ticks_delivery_chunk(cfg, n_rows)
    if ids_fn is None:
        ids_fn = lambda: jnp.arange(n_rows, dtype=I32)
    if key_fn is None:
        key_fn = _rng.tick_key
    if sum_fn is None:
        sum_fn = lambda x: x
    if emit_fn is None:
        def emit_fn(ring, base_key, w, em_dst, em_toff, typ, op):
            return _emit_all(cfg, ring, base_key, w, em_dst, em_toff,
                             typ, op)

    # Spill engages exactly where drops were ever possible (the slot-major
    # band's cap-8 stacked mailbox; `sm` is false on the sharded hook
    # path); everywhere else the token (2, 1) buffer passes through
    # untouched.
    sc = ticks_spill_cap(cfg, n_rows) if sm else 0
    prefix = PREFIX_DRAIN

    def _deliver_both(src_pay, dst, typ, evalid, m_live, spill_in):
        # Both message types in ONE sorted pass (ops.mailbox.deliver_pair;
        # bit-identical to two deliver() calls at ~half the op count).
        # Memory band: rank-major flat stacked buffer + per-type loads.
        # The drain sorts live entries into a packed prefix of length
        # `m_live`, so the chunked path skips its compaction scans
        # (prefix_len; PREFIX_DRAIN pins the A/B).  At the spill band the
        # last window's overflow pairs re-deliver first and this window's
        # overflow accumulates instead of dropping.
        plen = m_live if prefix else None
        dkern = cfg.deliver_kernel_resolved
        if sc > 0:
            acc = (jnp.full((2, sc + 1), -1, I32), jnp.zeros((), I32))
            return deliver_pair(src_pay, dst, typ, evalid, n_rows, cap_mb,
                                compact_chunk=dchunk, flat=sm,
                                prefix_len=plen, spill_in=spill_in,
                                spill=acc, kernel=dkern)
        return deliver_pair(src_pay, dst, typ, evalid, n_rows, cap_mb,
                            compact_chunk=dchunk, flat=sm,
                            prefix_len=plen, kernel=dkern) + (None,)

    def _drain_at_width(width, ring_dst, ring_pay, slot, m, spill_in):
        """Drain one window slot through a `width`-lane sort + delivery.
        Entries are rank-packed at [slot*cap, slot*cap + m), so any
        width >= m sees the whole live prefix; lanes past m hold stale
        cells masked exactly like the full-width form (sentinel toff key,
        stable sort) -- bit-identical mailboxes at any sufficient width."""
        dst_e = jax.lax.dynamic_slice(ring_dst, (slot * cap,), (width,))
        pay_e = jax.lax.dynamic_slice(ring_pay, (slot * cap,), (width,))
        evalid = jnp.arange(width, dtype=I32) < m
        # Arrival order within the window: stable sort by tick offset.
        toff_key = jnp.where(evalid, pay_e % b, b)
        toff_key, dst_e, pay_e = jax.lax.sort(
            (toff_key, dst_e, pay_e), num_keys=1, is_stable=True)
        evalid = toff_key < b
        typ = (pay_e // b) % 2
        mbox_pay = (pay_e // (2 * b)) * b + pay_e % b  # src*b + toff
        return _deliver_both(mbox_pay, dst_e, typ, evalid, m, spill_in)

    # Occupancy-adaptive drain widths (VERDICT r3 #5): slot_cap budgets
    # the worst-case window -- a 100M-lane 4-operand sort at 10M nodes --
    # but only the bootstrap-burst windows come anywhere near it; once
    # membership settles a window carries orders of magnitude fewer
    # entries.  lax.switch picks the narrowest power-of-4 width covering
    # the live count, so quiet windows sort thousands of lanes, not cap.
    widths = [cap]
    while widths[-1] > _DRAIN_WIDTH_FLOOR and len(widths) < 6:
        widths.append(max(_DRAIN_WIDTH_FLOOR, widths[-1] // 4))

    def step_fn(st: OverlayTickState, base_key: jax.Array) -> OverlayTickState:
        w = st.tick // b
        slot = w % dw
        m = st.ring_cnt[0, slot]
        spill_in = st.spill if sc > 0 else None
        if len(widths) == 1:
            drained = _drain_at_width(cap, st.ring_dst, st.ring_pay, slot,
                                      m, spill_in)
        else:
            # widths descend; ws[0] = cap >= m always, so the last
            # fitting index is count_of_fits - 1.
            sel = (jnp.asarray(widths, dtype=I32) >= m).sum(dtype=I32) - 1
            drained = jax.lax.switch(
                sel,
                [lambda rd, rp, sl, mm, w_=w_: _drain_at_width(
                    w_, rd, rp, sl, mm, spill_in)
                 for w_ in widths],
                st.ring_dst, st.ring_pay, slot, m)
        if sm:
            # Rank-major flat stacked mailbox: slot r of type t is the
            # contiguous range [r*2n + t*n, r*2n + (t+1)*n).
            pair_mbox, n_mk, n_bk, local_drops, spill_out = drained

            def mk_slot(sl):
                return jax.lax.dynamic_slice(pair_mbox,
                                             (sl * 2 * n_rows,), (n_rows,))

            def bk_slot(sl):
                return jax.lax.dynamic_slice(
                    pair_mbox, (sl * 2 * n_rows + n_rows,), (n_rows,))
        else:
            mk_mbox, bk_mbox, local_drops, spill_out = drained
            n_bk = (bk_mbox >= 0).sum(axis=1, dtype=I32).max(initial=0)
            n_mk = (mk_mbox >= 0).sum(axis=1, dtype=I32).max(initial=0)
            mk_slot = lambda sl: mk_mbox[:, sl]
            bk_slot = lambda sl: bk_mbox[:, sl]
        spill = spill_out[0] if spill_out is not None else st.spill
        ring_cnt = st.ring_cnt.at[0, slot].set(0)

        rkey = key_fn(base_key, w, _rng.OP_REPLACE)
        ekey = key_fn(base_key, w, _rng.OP_EVICT)
        ids = ids_fn()

        friends, cnt = st.friends, st.friend_cnt
        # Memory band: SLOT-major emission buffers (node axis minormost;
        # the node-major form tile-pads 16x at 1e8 -- see slotmajor).
        em_shape = (cap_mb, n_rows) if sm else (n_rows, cap_mb)
        mk_em_dst = jnp.full(em_shape, -1, I32)
        mk_em_toff = jnp.zeros(em_shape, I32)
        bk_em_dst = jnp.full(em_shape, -1, I32)
        bk_em_toff = jnp.zeros(em_shape, I32)

        def em_set(em, sl, vals):
            if sm:
                return em.at[sl].set(vals)
            return em.at[:, sl].set(vals)

        win_mk = jnp.zeros((), I32)
        win_bk = jnp.zeros((), I32)

        # --- breakups (simulator.go:76-94), slot-sequential ---------------
        # Decision rules are the SHARED kernels (overlay.process_*_slot);
        # this engine only threads the trigger's arrival tick through to
        # the emission so the reply's delay starts at the right time.
        def bk_body(sl, carry):
            friends, cnt, mk_em_dst, mk_em_toff, win_bk = carry
            pay = bk_slot(sl)
            has = pay >= 0
            src = jnp.where(has, pay // b, 0)
            toff = jnp.where(has, pay % b, 0)
            kk = jax.random.fold_in(rkey, sl)
            friends, cnt, nf, rp = process_breakup_slot(
                n, fanout, friends, cnt, src, has, ids, kk)
            mk_em_dst = em_set(mk_em_dst, sl, jnp.where(rp, nf, -1))
            mk_em_toff = em_set(mk_em_toff, sl, toff)
            return (friends, cnt, mk_em_dst, mk_em_toff,
                    win_bk + has.sum(dtype=I32))

        friends, cnt, mk_em_dst, mk_em_toff, win_bk = jax.lax.fori_loop(
            0, n_bk, bk_body,
            (friends, cnt, mk_em_dst, mk_em_toff, win_bk))

        # --- makeups (simulator.go:66-75) ----------------------------------
        def mk_body(sl, carry):
            friends, cnt, bk_em_dst, bk_em_toff, win_mk = carry
            pay = mk_slot(sl)
            has = pay >= 0
            src = jnp.where(has, pay // b, 0)
            toff = jnp.where(has, pay % b, 0)
            kk = jax.random.fold_in(ekey, sl)
            friends, cnt, victim, ev = process_makeup_slot(
                fanin, friends, cnt, src, has, kk)
            bk_em_dst = em_set(bk_em_dst, sl, jnp.where(ev, victim, -1))
            bk_em_toff = em_set(bk_em_toff, sl, toff)
            return (friends, cnt, bk_em_dst, bk_em_toff,
                    win_mk + has.sum(dtype=I32))

        friends, cnt, bk_em_dst, bk_em_toff, win_mk = jax.lax.fori_loop(
            0, n_mk, mk_body,
            (friends, cnt, bk_em_dst, bk_em_toff, win_mk))

        # --- emissions -> ring, per-message delays -------------------------
        ring = (st.ring_dst, st.ring_pay, ring_cnt, local_drops)
        if sm:
            ring = _emit_all(cfg, ring, base_key, w, mk_em_dst, mk_em_toff,
                             MK, _rng.OP_DELAY, lanes_major=True)
            ring = _emit_all(cfg, ring, base_key, w, bk_em_dst, bk_em_toff,
                             BK, _rng.OP_DELAY_BK, lanes_major=True)
        else:
            ring = emit_fn(ring, base_key, w, mk_em_dst, mk_em_toff,
                           MK, _rng.OP_DELAY)
            ring = emit_fn(ring, base_key, w, bk_em_dst, bk_em_toff,
                           BK, _rng.OP_DELAY_BK)
        ring_dst, ring_pay, ring_cnt, local_drops = ring

        win_mk = sum_fn(win_mk)
        win_bk = sum_fn(win_bk)
        return OverlayTickState(
            friends=friends, friend_cnt=cnt,
            ring_dst=ring_dst, ring_pay=ring_pay, ring_cnt=ring_cnt,
            spill=spill,
            tick=st.tick + b,
            makeups=st.makeups + win_mk, breakups=st.breakups + win_bk,
            win_makeups=st.win_makeups + win_mk,
            win_breakups=st.win_breakups + win_bk,
            mailbox_dropped=st.mailbox_dropped + sum_fn(local_drops))

    return step_fn


def _make_poll_body(cfg: Config):
    """One 10 ms poll window (ceil(10/B) steps), unjitted -- the SINGLE
    poll semantics shared by make_poll_fn (windowed host loop) and
    make_run_fn (bounded device loop), so the two paths cannot drift.
    win_makeups/win_breakups accumulate over the poll window, matching the
    reference's polled-atomics observation cadence (simulator.go:221-234)."""
    step = make_step_fn(cfg)
    steps = max(1, -(-10 // batch_ticks(cfg)))

    def poll(st: OverlayTickState, base_key) -> OverlayTickState:
        st = st._replace(win_makeups=jnp.zeros((), I32),
                         win_breakups=jnp.zeros((), I32))
        return jax.lax.fori_loop(0, steps, lambda _, s: step(s, base_key), st)

    return poll


def make_poll_fn(cfg: Config):
    """One poll window as one jitted device call (_make_poll_body)."""
    import functools

    poll = _make_poll_body(cfg)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def poll_fn(st: OverlayTickState, base_key) -> OverlayTickState:
        return poll(st, base_key)

    return poll_fn


def quiesced(st: OverlayTickState) -> jnp.ndarray:
    """A full poll window with zero processed messages AND an empty ring
    (spilled overflow pairs are in-flight messages: quiescing on them
    would lose them)."""
    return ((st.win_makeups == 0) & (st.win_breakups == 0)
            & ~jnp.any(st.ring_cnt > 0) & ~jnp.any(st.spill[1] >= 0)
            & (st.tick > 0))


def run_call_budget(cfg: Config, shards: int = 1) -> int:
    """Poll windows per bounded overlay_run_to_quiescence device call.
    The budget bounds how long one call holds the device before the host
    checks quiescence and signals again (epidemic.run_call_budget): about
    8 s/call at ~0.4 us/node/window, a sizing left to a measured PR.  `shards` scales for a mesh
    backend (device work tracks the per-SHARD slice), multiplying
    BEFORE the >=1 clamp so large n keeps the ratio."""
    return max(1, min(1024, int(2e7 * shards // max(cfg.n, 1))))


def make_run_fn(cfg: Config, telemetry: bool = False):
    """Up to `max_polls` poll windows per device call, stopping early at
    quiescence -- the phase-1 analog of the epidemic's bounded
    run-to-coverage while_loop.  The windowed host loop pays one jit
    dispatch + one device_get PER 10 simulated ms (profiled ~2.4x the
    device time at n=1e6); a quiet run has nothing to
    observe per window, so the whole stabilization runs device-side with
    one host sync per bounded call.  Trajectory-identical to the windowed
    path: the same step/key derivation (keys are (base_key, window)-
    indexed, not call-indexed) and the same quiescence predicate on the
    same post-window states."""
    from gossip_simulator_tpu.models.overlay import make_bounded_run

    return make_bounded_run(_make_poll_body(cfg), quiesced,
                            telemetry=telemetry)
