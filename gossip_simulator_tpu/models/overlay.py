"""Vectorized dynamic-overlay construction (phase 1).

The reference's membership protocol is inherently sequential per node: an
event loop multiplexing makeup / breakup / need-new-friend mailboxes
(simulator.go:62-106).  The vectorized analog runs the SAME per-message
decision rules, but batched: one "round" delivers last round's messages into
fixed-capacity mailboxes (ops/mailbox.py), then every node processes its
mailbox slots *sequentially across slots, in parallel across nodes* -- a
`fori_loop` over slot index k in which iteration k applies all nodes' k-th
message.  Message emissions (replacement makeups, eviction breakups,
bootstrap makeups) are buffered and delivered next round, standing in for the
reference's delayed channel sends (simulator.go:151-164).

Decision-rule parity (per message, against simulator.go):
* makeup  (simulator.go:66-75): under fanin -> append sender; else evict a
  uniform-random victim (sending it a breakup) and take its slot.
* breakup (simulator.go:76-94): first-match scan; over fanout -> remove
  (swap-with-last here -- order is immaterial because eviction is uniform);
  else replace in place with a fresh random peer (!= self, != leaver) and
  send it a makeup.
* bootstrap (simulator.go:95-106): while under fanout, add one uniform
  random friend per round (self patched as (id+1)%N, duplicates allowed)
  and send a makeup.

This preserves the stationary degree distribution (friend_cnt in
[fanout, max(fanout, fanin)], in-degree concentrated near fanin) rather than
the reference's exact event interleaving -- verified statistically against
the event-driven oracle in tests/test_overlay.py (SURVEY §7.3 hard part #1).

Quiescence is race-free: a round with zero processed AND zero in-flight
messages (the reference's polled check can terminate early, SURVEY §5.2).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from gossip_simulator_tpu import tuning as _tuning
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models.state import OverlayState
from gossip_simulator_tpu.ops.mailbox import deliver
from gossip_simulator_tpu.utils import rng as _rng

I32 = jnp.int32

# Above this row count the rounds engine delivers per COLUMN
# (ops.mailbox.deliver_columns, column-major arrival order) instead of the
# flattened row-major path; below it the per-column machinery is
# op-floor-bound (measured 4x slower at 1M -- make_round_fn's rationale).
# Module-level so a CPU test can lower it and pin the column-major
# trajectory band with a small-n golden (advisor r3: the band was
# otherwise exercisable only by on-TPU runs).
COLUMN_DELIVERY_MIN_ROWS = 4_000_000

# Mailbox-overflow spill capacity (pairs per message type): overflow is the
# in-degree tail past the mailbox cap -- 257 TOTAL messages over 31 rounds
# at the 100M build's cap 8 (r4), so 64k pairs (512 KB) is ~250x the
# largest observed round.  Spilled messages re-deliver first next round
# (the reference's channel-full backpressure: delayed, never lost,
# simulator.go:51-54); past the spill cap they fall back to counted drops.
SPILL_CAP = 65_536


def spill_enabled(cap: int) -> bool:
    """Spill engages only below the full cap 16 -- i.e. the memory-banded
    cap 8 (the ONLY regime that ever dropped: 257 messages at 1e8, r4)
    and explicit tiny test caps.  At cap 16 overflow needs in-degree > 16
    in one round (~1e-12 per node-round at the protocol's Poisson loads;
    never observed), and threading the spill accumulator through every
    delivery chunk costs real op floors (measured +10.6 s on the 27-round
    10M build, 2026-08-01) -- so cap-16 configs keep the counted-drop
    path."""
    return cap < 16


def _poisson_excess(lam: float, cap: int) -> float:
    """E[(X - cap)+] for X ~ Poisson(lam): the expected per-node mailbox
    overflow when a whole wave of uniform sends lands in ONE round.
    Config-time host float; terms summed to a ~10-sigma tail."""
    import math

    p = math.exp(-lam)
    e = 0.0
    for k in range(1, int(lam + 10.0 * math.sqrt(max(lam, 1.0)) + 20)):
        p = p * lam / k
        if k > cap:
            e += (k - cap) * p
    return e


def spill_cap_for(cfg: Config, n_rows: int) -> int:
    """Spill capacity (pairs) for a single-device rounds surface of
    `n_rows` rows; 0 = disabled (spill_enabled).  The static-bootstrap
    band needs burst sizing: the one-shot n*fanout makeup burst lands in
    ONE round, so in-degree is Poisson(fanout) all at once -- at cap 8 /
    fanout 5 that is E[(X-8)+] ~ 0.122 overflow messages per node
    (~12.2M pairs at 1e8, vs the 257 TOTAL the staggered schedule ever
    overflowed), and the round-2 breakup reply wave is bounded by the
    same lambda.  1.6x covers skew; the SPILL_CAP floor covers the
    settled regime.  Spilled pairs are DELAYED one round, never lost --
    the reference's cap-1024 channels absorb the same burst without
    blocking, so the divergence is arrival order only (the documented
    envelope)."""
    cap = cfg.mailbox_cap_for(n_rows)
    if not spill_enabled(cap):
        return 0
    if static_boot_applies(cfg, None):
        margin = _tuning.value("overlay.spill_margin", cfg)
        return SPILL_CAP + int(margin * n_rows
                               * _poisson_excess(float(cfg.fanout), cap))
    return SPILL_CAP


def static_boot_applies(cfg: Config, n_local: int | None,
                        hooked: bool = False) -> bool:
    """Whether the one-shot static bootstrap (config.overlay_static_boot)
    runs on this surface: single-device rounds engine only (the sharded
    hook path's routed init has no burst delivery and its per-shard
    slices sit below the band), and the burst must fit the mailbox-cap
    emission rows (fanout <= cap; always true for auto caps)."""
    n = n_local if n_local is not None else cfg.n
    return ((n_local is None and not hooked)
            and cfg.static_boot_for(cfg.n)
            and cfg.fanout <= cfg.mailbox_cap_for(n))


def init_state(cfg: Config, n_local: int | None = None,
               base_key: jax.Array | None = None) -> OverlayState:
    n = n_local if n_local is not None else cfg.n
    k = cfg.max_degree
    # Per-LOCAL-rows cap: one shard's slice keeps cap 16 far beyond the
    # single-device flat-addressing boundary (config.mailbox_cap_for).
    cap = cfg.mailbox_cap_for(n)
    z = lambda: jnp.zeros((), I32)
    # Emission buffers are SLOT-major (cap, n): the huge node axis is
    # minormost and the slot count tiles T(8,128) exactly (see the
    # OverlayState field comment -- node-major and off-multiple slot
    # counts both padded catastrophically at n=1e8); bootstrap emissions
    # are their own flat vector.
    # Non-spilling configs (spill_enabled) carry token-sized spill fields:
    # the buffers are loop-invariant pass-throughs there, but full-size
    # ones still measurably regressed the bounded phase-1 while_loop
    # (+4.7 s on the 27-round 10M build).  The static-boot band sizes for
    # the one-shot burst's concentrated overflow (spill_cap_for); the
    # sharded hook path (n_local given) never spills and keeps the flat
    # floor so its replicated token fields stay small.
    sc = (spill_cap_for(cfg, n) if n_local is None
          else (SPILL_CAP if spill_enabled(cap) else 0))
    if static_boot_applies(cfg, n_local):
        if base_key is None:
            # make_round_fn skips the per-round bootstrap under the same
            # gate; silently building a burst-less state here would leave
            # the overlay with no bootstrap at all.
            raise ValueError(
                "overlay.init_state: static bootstrap requires base_key")
        # One-shot static bootstrap (round 7; config.overlay_static_boot):
        # draw the whole initial friends table and stage the n*fanout
        # makeup burst as the first `fanout` emission rows, exactly the
        # way overlay_ticks.init_state always has -- the reference's
        # needNewFriend loop re-arms with no delay (simulator.go:103-105),
        # so every node fills all fanout slots at t~0, and once at fanout
        # it can never drop below it (breakup at/under fanout replaces in
        # place; removal only happens above) -- the per-round bootstrap
        # never fires again, and make_round_fn skips it entirely.  Draws
        # are per-LANE keyed (one (n,) column at a time; no (n, fanout)
        # matrix to tile-pad at 1e8), self patched (id+1)%N like every
        # bootstrap draw (simulator.go:97-100).
        f = cfg.fanout
        ids = jnp.arange(n, dtype=I32)
        kb = _rng.tick_key(base_key, 0, _rng.OP_BOOTSTRAP)
        friends = jnp.full((n, k), -1, I32)
        mk_dst = jnp.full((cap, n), -1, I32)
        colsel = jnp.arange(k, dtype=I32)[None, :]
        for j in range(f):
            wj = _rng.row_randint(kb, n, ids * f + j, 1)[:, 0]
            wj = jnp.where(wj == ids, (wj + 1) % n, wj)
            friends = jnp.where(colsel == j, wj[:, None], friends)
            mk_dst = mk_dst.at[j].set(wj)
        cnt = jnp.full((n,), f, I32)
    else:
        friends = jnp.full((n, k), -1, I32)
        cnt = jnp.zeros((n,), I32)
        mk_dst = jnp.full((cap, n), -1, I32)
    return OverlayState(
        friends=friends,
        friend_cnt=cnt,
        mk_dst=mk_dst,
        bk_dst=jnp.full((cap, n), -1, I32),
        boot_dst=jnp.full((n,), -1, I32),
        mk_spill=jnp.full((2, sc + 1), -1, I32),
        bk_spill=jnp.full((2, sc + 1), -1, I32),
        round=z(), makeups=z(), breakups=z(),
        win_makeups=z(), win_breakups=z(), mailbox_dropped=z(),
    )


def delivery_chunk(cfg: Config, n_rows: int) -> int:
    """Delivery-compaction chunk for the overlay mailbox deliver: 64k
    optimum from the v5e full-construction sweep (chunk n: 17.6s,
    131k: 13.2s, 65k: 9.6s, 32k: 11.4s at n=1e6 -- narrow chunks win
    because per-chunk sort/scatter width dominates the extra
    first_true_indices passes of the bootstrap burst); -compact-chunk
    overrides.  Above the n/128 knee (~8.4M rows) the chunk scales as
    n/128 (to 1M): each chunk pays an n-wide compaction scan, so a fixed
    64k chunk is O(n^2/chunk) on burst rows -- ~1526 full-1e8 scans per
    bootstrap row at the 100M build (measured ~87-215 s/round r4;
    n-scaling cuts the scan count 16x).  Chunk size never changes results (ascending
    ranges + rank continuation are bit-identical at any chunk).  Used by
    the ROUNDS engine (and its sharded variant); the tick-faithful
    engine's slot drain has its own scaling
    (overlay_ticks.ticks_delivery_chunk -- its per-chunk cost is
    scatter-floor-bound at GB-scale targets, favoring fat chunks).
    The 65_536 base and 1M cap are registered tunables (tuning.py):
    an explicit -compact-chunk outranks any table entry."""
    if cfg.compact_chunk > 0:
        return cfg.compact_chunk
    base = _tuning.value("overlay.delivery_chunk_base", cfg)
    cap = _tuning.value("overlay.delivery_chunk_cap", cfg)
    return min(n_rows, max(base, n_rows // 128), cap)


# Fattest rung of the adaptive hosted-chunk ladder (hosted_chunk_widths):
# dense burst rows at n=1e8 drop from 128 base-width chunks to 12, each
# chunk's flat scatter/sort paying its fixed cost once -- the scatter into
# the 3.2 GB rank-major mailbox is ~flat per op at GB-scale targets
# (README device-span finding; scripts/profile_overlay.py measures the
# per-width constants), so fewer, fatter chunks win on dense rows exactly
# as they did for the ticks drain (ticks_delivery_chunk).  Bounded so one
# chunk's sort stays a short device call and its operand pair is
# ~64 MB.  Module-level so tests can lower it.
ADAPTIVE_CHUNK_MAX = 8_388_608


def hosted_chunk_widths(cfg: Config, n_rows: int) -> tuple[int, ...]:
    """Occupancy-adaptive chunk-width ladder for the hosted (split-round)
    delivery: geometric x4 rungs from the swept base width
    (delivery_chunk) up to ADAPTIVE_CHUNK_MAX.  Each row picks the
    narrowest rung covering its live count in one chunk -- settled rows
    keep the swept narrow optimum, burst rows take the fat rungs.  Chunk
    width never changes results (deliver's compact_chunk contract), so
    the gate (config.overlay_adaptive_chunks) is pure perf; "off" pins
    the single pre-round-7 width."""
    base = delivery_chunk(cfg, n_rows)
    if not cfg.overlay_adaptive_chunks_resolved:
        return (base,)
    # The module global stays the monkeypatchable default (tests lower
    # it); a tuning-table entry overrides it per platform/band.
    rung_max = _tuning.value("overlay.adaptive_chunk_max", cfg,
                             default=ADAPTIVE_CHUNK_MAX)
    hi = max(base, min(n_rows, rung_max))
    widths = [base]
    while widths[-1] < hi:
        widths.append(min(widths[-1] * 4, hi))
    return tuple(widths)


def _col_onehot(cols, k: int):
    """bool[n, k]: row r's `cols[r]` column.  The friends width k is tiny
    (~6), so per-row column reads/writes are ONE-HOT ELEMENTWISE ops, not
    2-D index gathers/scatters -- which cost a full per-op floor each on
    this platform (~15x slower; see epidemic.deposit_local NOTE).  This
    one change took the 1M overlay round from ~500 to O(100) ms."""
    return jnp.arange(k, dtype=I32)[None, :] == cols[:, None]


def _col_get(arr, cols):
    """arr[rows, cols] via one-hot select (see _col_onehot)."""
    return jnp.where(_col_onehot(cols, arr.shape[1]), arr, 0).sum(
        axis=1, dtype=arr.dtype)


def _col_set(arr, cols, vals, mask=None):
    """arr[rows, cols] = vals (where mask) via one-hot blend."""
    oh = _col_onehot(cols, arr.shape[1])
    if mask is not None:
        oh = oh & mask[:, None]
    return jnp.where(oh, vals[:, None], arr)


def _masked_set(arr, rows, cols, vals, mask):
    """arr[rows, cols] = vals where mask (one-hot blend; `rows` must be
    the dense arange -- true for every caller)."""
    del rows
    return _col_set(arr, cols, vals, mask)


def process_breakup_slot(n, fanout, friends, cnt, src, has, ids, kk):
    """One mailbox slot of breakup decisions for ALL nodes in parallel
    (simulator.go:76-94): first-match scan; over fanout -> remove
    (swap-with-last); else replace in place with a fresh random peer
    (!= self, != leaver) to whom a makeup must be sent.

    Shared by the round engine and the tick-faithful engine
    (models/overlay_ticks.py) so the decision rules can never diverge.
    Returns (friends, cnt, reply_dst, reply_mask): send makeup to
    reply_dst where reply_mask."""
    k = friends.shape[1]
    in_range = jnp.arange(k, dtype=I32)[None, :] < cnt[:, None]
    match = (friends == src[:, None]) & in_range & has[:, None]
    found = match.any(axis=1)
    pos = jnp.argmax(match, axis=1).astype(I32)  # first match
    over = cnt > fanout
    rm = has & found & over
    rp = has & found & ~over
    nf = _rng.randint_excluding(kk, n, (cnt.shape[0],), src, ids)
    lastpos = jnp.maximum(cnt - 1, 0)
    lastval = _col_get(friends, lastpos)
    posval = jnp.where(rm, lastval,
                       jnp.where(rp, nf, _col_get(friends, pos)))
    friends = _col_set(friends, pos, posval)
    friends = _col_set(friends, lastpos,
                       jnp.full(cnt.shape, -1, I32), rm)
    cnt = cnt - rm.astype(I32)
    return friends, cnt, nf, rp


def process_makeup_slot(fanin, friends, cnt, src, has, kk):
    """One mailbox slot of makeup decisions (simulator.go:66-75): accept
    under fanin, else evict a uniform-random existing friend (to whom a
    breakup must be sent) and take its slot.  Shared like
    process_breakup_slot.  Returns (friends, cnt, victim_dst,
    victim_mask)."""
    k = friends.shape[1]
    under = cnt < fanin
    app = has & under
    appcol = jnp.minimum(cnt, k - 1)
    friends = _col_set(friends, appcol, src, app)
    cnt = cnt + app.astype(I32)
    ev = has & ~under
    vpos = jax.random.randint(kk, cnt.shape, 0, jnp.maximum(cnt, 1),
                              dtype=I32)
    victim = _col_get(friends, vpos)
    friends = _col_set(friends, vpos, src, ev)
    return friends, cnt, victim, ev


def heal_dead_friends(n_global: int, friends, friend_cnt, detected_global,
                      healer_ok, ids_global, heal_key):
    """Phase-2 re-entry of the bootstrap/needNewFriend draw
    (simulator.go:95-106): every live node replaces friends its failure
    detector has condemned with a fresh uniform random peer, self patched
    ``(id+1) % N`` exactly like the phase-1 bootstrap.  Vectorized over
    the whole (n, k) table at once -- the overlay's makeup *decision* is
    what re-runs here; the reciprocal fanin-side accept (the target
    adding the healer back, simulator.go:66-75) is not simulated, a
    documented divergence (README "Fault model & scenarios").

    `detected_global` is the full-axis bool[n_global] detector verdict
    (the sharded caller all_gathers its local verdicts first); draws are
    row-keyed on GLOBAL ids, so a shard's slice heals bit-identically to
    the single-device run.  The fresh draw is uniform and may itself land
    on a dead node (the reference's draws have no global liveness oracle
    either); a dead pick is condemned again next detection window.
    Returns (friends', dead_mask, repaired_count_local)."""
    k = friends.shape[1]
    in_range = jnp.arange(k, dtype=I32)[None, :] < friend_cnt[:, None]
    valid = in_range & (friends >= 0)
    dead = detected_global.at[jnp.maximum(friends, 0)].get() \
        & valid & healer_ok[:, None]
    w = _rng.row_randint(heal_key, n_global, ids_global, k)
    w = jnp.where(w == ids_global[:, None], (w + 1) % n_global, w)
    friends = jnp.where(dead, w, friends)
    return friends, dead, dead.sum(dtype=I32)


def make_round_fn(cfg: Config,
                  deliver_fn=None,
                  ids_fn=None,
                  sum_fn=None, n_rows: int | None = None,
                  ) -> Callable[[OverlayState, jax.Array], OverlayState]:
    """Build the per-round transition.

    The three hooks make the same body run single-device or per-shard inside
    shard_map (parallel/sharded_step.py):
      deliver_fn(src, dst, valid, cap) -> (mbox int32[n_local, cap], dropped)
          -- plain local mailbox delivery by default; routed all_to_all
             delivery when sharded.
      ids_fn() -> global ids of the local rows (arange(n) by default).
      sum_fn(x) -> global scalar reduction (identity by default; psum sharded).
    `n_rows` (local rows; defaults to cfg.n) sizes the mailbox cap -- it
    must match init_state's n_local so the emission widths agree.
    """
    n = cfg.n
    k = cfg.max_degree
    fanout, fanin = cfg.fanout, cfg.fanin_resolved
    cap = cfg.mailbox_cap_for(n_rows if n_rows is not None else n)
    # One-shot bootstrap (round 7): init_state staged the burst, so the
    # per-round bootstrap block is skipped -- must agree with init_state's
    # gate or the overlay would never bootstrap at all.
    static_boot = static_boot_applies(cfg, n_rows,
                                      hooked=deliver_fn is not None)
    # Mailboxes come back either 2-D (n, cap) or FLAT rank-major
    # (cap*n + 1; slot r contiguous at [r*n, (r+1)*n)) -- the large-n
    # path never materializes the (n, cap) shape, whose narrow minor dim
    # TPU tile layouts pad to 128 lanes (s32[1e8, 8] -> 51 GB, the
    # round-4 compile OOM).  `_slot(mbox, r)` reads slot r either way.
    flat_mbox = False
    if deliver_fn is None:
        # Emission lists are mostly empty once membership settles: compact
        # before the delivery sort (chunk sweep: see delivery_chunk).
        dchunk = delivery_chunk(cfg, n)
        from gossip_simulator_tpu.ops.mailbox import (deliver_columns,
                                                      flat_addressing_fits)

        dkern = cfg.deliver_kernel_resolved
        sc_band = spill_cap_for(cfg, n)
        if n > COLUMN_DELIVERY_MIN_ROWS and flat_addressing_fits(n, cap):
            # Per-SLOT delivery: same entries at ~1/slots the compaction
            # scan width (deliver_columns' rationale; the flattened form
            # was 84% of the round at 10M nodes: 42.5 -> 25.3 s there).
            # Arrival order becomes slot-major.  Below ~4M rows the
            # per-slot machinery is op-floor-bound (34 slots x
            # ceil-per-slot chunks measured 4x SLOWER at 1M) and the
            # flattened node-major path stays -- the canonical arrival
            # order is size-banded, deterministic per config, and pinned
            # by the goldens at small n.  This path SPILLS overflow into
            # (src, dst) pairs re-delivered first next round instead of
            # dropping (SPILL_CAP; lossless membership delivery).
            flat_mbox = True

            def deliver_matrix_fn(mats, cap, dep=None, spill_in=None):
                carry = None
                if dep is not None:
                    # Sequence this delivery's buffer allocations after
                    # `dep` so they reuse the previous delivery's dead
                    # buffers (see _dep_full).
                    carry = (_dep_full((n * cap + 1,), -1, dep),
                             _dep_full((n + 1,), 0, dep),
                             jnp.zeros((), I32))
                if sc_band == 0:
                    out = deliver_columns(mats, n, cap, dchunk, flat=True,
                                          carry=carry, kernel=dkern)
                    return out + (None,)
                acc = (jnp.full((2, sc_band + 1), -1, I32),
                       jnp.zeros((), I32))
                mbox, load, dropped, (pairs, _) = deliver_columns(
                    mats, n, cap, dchunk, flat=True, carry=carry,
                    spill_in=spill_in, spill=acc, kernel=dkern)
                return mbox, load, dropped, pairs
        else:
            # Small-n path, and past the flat-addressing boundary the
            # flattened path's dense 2-D fallback + one-time warning.
            # Slot-major flatten, matching the per-slot path's arrival
            # order exactly (sender = flat_idx % n) -- the canonical
            # order no longer changes across the size band.  No spill:
            # at cap 16 (every n in this band) overflow needs in-degree
            # > 16 in one round -- never observed; drops stay counted.
            def deliver_matrix_fn(mats, cap, dep=None, spill_in=None):
                flat = jnp.concatenate(mats, axis=0).reshape(-1)
                mbox, cnt, dropped = deliver(None, flat, flat >= 0, n, cap,
                                             compact_chunk=dchunk,
                                             src_mod=n, kernel=dkern)
                return mbox, cnt.max(initial=0), dropped, None
    else:
        # Hook supplied (the sharded backend's routed delivery): keep its
        # flattened (src, dst, valid) contract; the ids broadcast is only
        # materialized on this path.  Slot-major flatten (the emission
        # buffers' native layout; transposing at shard scale would
        # materialize the padded node-major shape).
        def deliver_matrix_fn(mats, cap, dep=None, spill_in=None):
            matc = jnp.concatenate(mats, axis=0)
            flat = matc.reshape(-1)
            ids_b = jnp.broadcast_to(ids_fn()[None, :],
                                     matc.shape).reshape(-1)
            mbox, dropped = deliver_fn(ids_b, flat, flat >= 0, cap)
            return mbox, (mbox >= 0).sum(axis=1, dtype=I32).max(initial=0), \
                dropped, None
    if ids_fn is None:
        ids_fn = lambda: jnp.arange(n, dtype=I32)
    if sum_fn is None:
        sum_fn = lambda x: x

    def _slot(mbox, r):
        """Mailbox slot r for every node: contiguous dynamic_slice on the
        flat rank-major layout, column read on the 2-D one.  Keyed on the
        array itself (ndim), not the size band: the split round's hosted
        delivery hands the pieces a flat mailbox at ANY n."""
        if mbox.ndim == 1:
            return jax.lax.dynamic_slice(mbox, (r * n,), (n,))
        return mbox[:, r]

    def _dep_full(shape, fill, dep):
        """Constant fill whose ALLOCATION is sequenced after `dep`: a
        plain jnp.full lowers to broadcast(constant), which XLA hoists to
        program start -- at n=1e8 that made every multi-GB buffer of the
        round co-live (both mailboxes + both emission buffers, 19.5 GB on
        a 15.75 GB chip).  Mixing a computed scalar in keeps the buffer's
        live range where the dataflow says it starts, letting it reuse a
        dead predecessor's allocation."""
        return jnp.broadcast_to(jnp.int32(fill) + dep * jnp.int32(0), shape)

    # --- the four round pieces -------------------------------------------
    # Factored so the fused round_fn and the memory-scale split variant
    # (make_split_round_fn: one jitted call PER PIECE) run the exact same
    # closures -- only the jit boundary moves.

    def p_bk_deliver(bk_dst, bk_spill):
        """Deliver last round's BREAKUP emissions (the overflow spill
        pairs first -- delayed messages arrive before this round's)."""
        return deliver_matrix_fn((bk_dst,), cap, spill_in=bk_spill)

    def p_bk_process(friends, cnt, bk_mbox, n_bk, drop2, round_, base_key):
        """Process the breakup mailbox (simulator.go:76-94), emitting
        replacement makeups into mk_em.  Also returns mk_cnt int32[cap]:
        each emission slot's entry count, recorded AT WRITE TIME (one
        scalar reduction per processed slot) -- the round-7 dead-row mask
        the hosted delivery consumes next round instead of popcounting
        every n-wide row (dead in the fused round; XLA drops it)."""
        ids = ids_fn()  # GLOBAL ids of local rows (identity comparisons)
        rkey = jax.random.fold_in(base_key, round_)
        # mk_em allocates after the bk delivery (see _dep_full).
        mk_em = _dep_full((cap, ids.shape[0]), -1, drop2)
        win_bk = jnp.zeros((), I32)
        mk_cnt = jnp.zeros((cap,), I32)

        def bk_body(slot, carry):
            friends, cnt, mk_em, win_bk, mk_cnt = carry
            src = _slot(bk_mbox, slot)
            has = src >= 0
            kk = jax.random.fold_in(
                jax.random.fold_in(rkey, _rng.OP_REPLACE), slot)
            friends, cnt, nf, rp = process_breakup_slot(
                n, fanout, friends, cnt, src, has, ids, kk)
            mk_em = mk_em.at[slot].set(jnp.where(rp, nf, -1))
            mk_cnt = mk_cnt.at[slot].set(rp.sum(dtype=I32))
            return friends, cnt, mk_em, win_bk + has.sum(dtype=I32), mk_cnt

        # Slot loops run to the MAX mailbox load this round (n_mk/n_bk from
        # the delivery), not the fixed capacity: slots are rank-contiguous,
        # so everything past a node's count is -1 (a no-op slot), and
        # typical max load is ~ln n/ln ln n << cap.  Local data-dependent
        # trip counts are fine under shard_map: the bodies contain no
        # collectives.
        return jax.lax.fori_loop(
            0, n_bk, bk_body, (friends, cnt, mk_em, win_bk, mk_cnt))

    def p_mk_deliver(mk_dst, boot_dst, mk_spill, friends, cnt, win_bk):
        """Deliver the MAKEUP emissions (the breakup mailbox is dead by
        now -- holding both ~3 GB mailboxes alive broke the 16 GB chip at
        n=1e8; sequencing is bit-identical since the deliveries are
        data-independent).  Spilled makeups first, then replies, then
        bootstrap makeups as one extra slot row AFTER the replies -- the
        same order the old (cap+2)-wide buffer delivered.  The
        optimization_barrier keeps XLA from hoisting this above the
        breakup processing in the fused form."""
        mk_src, boot_src, friends, cnt = jax.lax.optimization_barrier(
            (mk_dst, boot_dst, friends, cnt))
        mk_mbox, n_mk, drop1, mk_sp = deliver_matrix_fn(
            (mk_src, boot_src[None, :]), cap, dep=win_bk,
            spill_in=mk_spill)
        return mk_mbox, n_mk, drop1, friends, cnt, mk_sp

    def p_mk_process(mk_mbox, n_mk, drop1, drop2, friends, cnt, mk_em,
                     win_bk, round_, makeups0, breakups0, dropped0,
                     base_key, mk_sp=None, bk_sp=None,
                     spill0=None, mk_cnt=None, aux=False):
        """Process the makeup mailbox (simulator.go:66-75), bootstrap
        (simulator.go:95-106) and assemble the next state.

        With `aux` (the split round), also returns the round-7 dead-row
        bookkeeping: (mk_cnt, bk_cnt, boot_cnt, quiesced) -- per-slot
        emission counts recorded at write time (exactly the sums
        pending_emissions would reduce out of the (cap, n) buffers) plus
        the quiescence flag computed from them, so the split loop's
        per-round eager quiesced() never touches the multi-GB masks."""
        ids = ids_fn()
        n_local = ids.shape[0]
        rows = jnp.arange(n_local, dtype=I32)  # LOCAL row indices
        rkey = jax.random.fold_in(base_key, round_)
        bk_em = _dep_full((cap, n_local), -1, win_bk)
        dropped = dropped0 + sum_fn(drop1 + drop2)
        win_mk = jnp.zeros((), I32)
        bk_cnt = jnp.zeros((cap,), I32)

        def mk_body(slot, carry):
            friends, cnt, bk_em, win_mk, bk_cnt = carry
            src = _slot(mk_mbox, slot)
            has = src >= 0
            kk = jax.random.fold_in(
                jax.random.fold_in(rkey, _rng.OP_EVICT), slot)
            friends, cnt, victim, ev = process_makeup_slot(
                fanin, friends, cnt, src, has, kk)
            bk_em = bk_em.at[slot].set(jnp.where(ev, victim, -1))
            bk_cnt = bk_cnt.at[slot].set(ev.sum(dtype=I32))
            return friends, cnt, bk_em, win_mk + has.sum(dtype=I32), bk_cnt

        friends, cnt, bk_em, win_mk, bk_cnt = jax.lax.fori_loop(
            0, n_mk, mk_body, (friends, cnt, bk_em, win_mk, bk_cnt))

        if static_boot:
            # One-shot bootstrap at init (init_state's burst): cnt >=
            # fanout is invariant from round 0 -- breakup at/under fanout
            # replaces in place and removal only happens above it -- so
            # the per-round `under` mask is all-False forever and the
            # whole draw/append/emit block is dead weight (an n-wide
            # randint + 4 elementwise passes per round at 1e8).  Skipping
            # it is EXACTLY identical, not just statistically.
            boot_em = jnp.full((n_local,), -1, I32)
            boot_cnt = jnp.zeros((), I32)
        else:
            # --- bootstrap: one friend per round while under fanout --------
            kb = jax.random.fold_in(rkey, _rng.OP_BOOTSTRAP)
            w = jax.random.randint(kb, (n_local,), 0, n, dtype=I32)
            w = jnp.where(w == ids, (w + 1) % n, w)
            under = cnt < fanout
            appcol = jnp.minimum(cnt, k - 1)
            friends = _masked_set(friends, rows, appcol, w, under)
            cnt = cnt + under.astype(I32)
            boot_em = jnp.where(under, w, -1)
            boot_cnt = under.sum(dtype=I32)

        # Global reductions (psum when sharded): window counts feed both the
        # progress lines and the quiescence predicate, so they must be the
        # global sums the reference's atomics would show (simulator.go:224-230).
        win_mk = sum_fn(win_mk)
        win_bk = sum_fn(win_bk)
        # Spill pass-through: non-spilling delivery paths return None and
        # the state keeps its (always-empty) buffers; `spill0` supplies
        # them as an (mk, bk) tuple.
        mk_spill = mk_sp if mk_sp is not None else spill0[0]
        bk_spill = bk_sp if bk_sp is not None else spill0[1]
        st = OverlayState(
            friends=friends, friend_cnt=cnt, mk_dst=mk_em, bk_dst=bk_em,
            boot_dst=boot_em, mk_spill=mk_spill, bk_spill=bk_spill,
            round=round_ + 1,
            makeups=makeups0 + win_mk, breakups=breakups0 + win_bk,
            win_makeups=win_mk, win_breakups=win_bk,
            mailbox_dropped=dropped,
        )
        if not aux:
            return st
        # Counts == the emission-mask sums by construction (every slot row
        # is where(mask, value>=0, -1), so entries == mask trues; rows past
        # the trip count keep their zero), making this EXACTLY
        # overlay.quiesced(st) without the (cap, n) reductions.
        mk_sp_live = (mk_spill[1] >= 0).sum(dtype=I32)
        bk_sp_live = (bk_spill[1] >= 0).sum(dtype=I32)
        pending = (mk_cnt.sum(dtype=I32) + bk_cnt.sum(dtype=I32)
                   + boot_cnt + mk_sp_live + bk_sp_live)
        q = ((win_mk == 0) & (win_bk == 0) & (pending == 0)
             & (st.round > 0))
        return st, (mk_cnt, bk_cnt, boot_cnt, mk_sp_live, bk_sp_live, q)

    def round_fn(st: OverlayState, base_key: jax.Array) -> OverlayState:
        bk_mbox, n_bk, drop2, bk_sp = p_bk_deliver(st.bk_dst, st.bk_spill)
        friends, cnt, mk_em, win_bk, _mk_cnt = p_bk_process(
            st.friends, st.friend_cnt, bk_mbox, n_bk, drop2, st.round,
            base_key)
        mk_mbox, n_mk, drop1, friends, cnt, mk_sp = p_mk_deliver(
            st.mk_dst, st.boot_dst, st.mk_spill, friends, cnt, win_bk)
        return p_mk_process(
            mk_mbox, n_mk, drop1, drop2, friends, cnt, mk_em, win_bk,
            st.round, st.makeups, st.breakups, st.mailbox_dropped, base_key,
            mk_sp=mk_sp, bk_sp=bk_sp,
            spill0=(st.mk_spill, st.bk_spill))

    # make_split_round_fn's seam.
    round_fn.pieces = (p_bk_deliver, p_bk_process, p_mk_deliver,
                       p_mk_process)
    return round_fn


# Above this many rows the single-device rounds engine runs each round as
# FOUR jitted calls (make_split_round_fn): one fused round holds ~19.5 GB
# at n=1e8 (donated state is reserved for the whole call, so the delivery
# temps cannot reuse it, and the co-live temp set fragments badly) while
# the split pieces each hold one multi-GB temp and free dead buffers at
# every call boundary via donation (~13 GB peaks).  Module-level so a CPU
# test can lower it and pin split == fused.
SPLIT_ROUND_MIN_ROWS = 32_000_000


def make_split_round_fn(cfg: Config):
    """One overlay round as a HOST-driven sequence of bounded device
    calls (see SPLIT_ROUND_MIN_ROWS).  Bit-identical to the fused
    round_fn: the two slot-processing phases jit the SAME piece closures,
    and the two deliveries run ops.mailbox.make_hosted_column_delivery --
    the same chunk body as deliver_columns, with the chunk loop split
    across bounded calls (one fused burst delivery holds the device for
    over 10 s at n=1e8).  Every call donates its array
    arguments and the driver drops dead references + fences between
    calls, so each phase's multi-GB buffers are retired before the next
    arena is allocated (a fused round reserved everything at once and
    peaked at 19.5 GB on the 15.75 GB chip)."""
    from gossip_simulator_tpu.ops.mailbox import make_hosted_column_delivery

    fused = make_round_fn(cfg)
    _, p_bk_process, _, p_mk_process = fused.pieces
    n = cfg.n
    cap = cfg.mailbox_cap_for(n)
    dead_skip = cfg.overlay_dead_skip_resolved
    sc_split = spill_cap_for(cfg, n)
    hosted_deliver = make_hosted_column_delivery(
        n, cap, hosted_chunk_widths(cfg, n), spill_cap=sc_split,
        kernel=cfg.deliver_kernel_resolved)

    # bk_mbox is not donated for the same reason as b2_fn's mk_mbox (no
    # same-shaped output to alias; liveness frees it after the slot loop).
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def a2_fn(friends, cnt, bk_mbox, n_bk, drop2, round_, base_key):
        return p_bk_process(friends, cnt, bk_mbox, n_bk, drop2, round_,
                            base_key)

    # mk_mbox is NOT donated (advisor r4: the flat (n*cap+1) mailbox has
    # no same-shaped output to alias, so donating it only produced the
    # "donated buffers were not usable" warning -- at n=1e8 it is freed
    # by liveness right after the slot loop either way); friends/cnt/
    # mk_em/spills all alias same-shaped state outputs.
    @functools.partial(jax.jit, donate_argnums=(4, 5, 6, 13, 14),
                       static_argnums=(16,))
    def b2_fn(mk_mbox, n_mk, drop1, drop2, friends, cnt, mk_em, win_bk,
              round_, makeups0, breakups0, dropped0, base_key, mk_sp,
              bk_sp, mk_cnt, aux):
        return p_mk_process(mk_mbox, n_mk, drop1, drop2, friends, cnt,
                            mk_em, win_bk, round_, makeups0, breakups0,
                            dropped0, base_key, mk_sp=mk_sp, bk_sp=bk_sp,
                            mk_cnt=mk_cnt, aux=aux)

    fence_jit = jax.jit(lambda x: x + 1)
    reshape_boot = jax.jit(lambda b: b[None, :])

    def fence():
        """Full host<->device round trip, so the previous call's
        donated/dead buffers are retired before the next call's arena is
        allocated (at n=1e8 the call sequence ran out of device memory
        without this fence on an earlier TPU runtime).  Cost: one tiny cached jit
        + scalar transfer per phase, noise against seconds of device
        work at split scale."""
        jax.device_get(fence_jit(jnp.int32(1)))

    # Round-7 dead-row bookkeeping carried ACROSS rounds on the host: the
    # totals describe the state's emission buffers (counted at write time
    # inside b2/a2), so round r's deliveries skip last round's dead rows
    # without popcounting them, and the quiescence flag arrives as one
    # scalar instead of an eager multi-GB mask reduction.  None until the
    # first full round (and after a checkpoint restore, which builds a
    # fresh round fn): those rounds pay the popcount fallback once.
    carry = {"mk": None, "bk": None, "boot": None, "round": None,
             "mk_sp": None, "bk_sp": None}

    def round4(st: OverlayState | list, base_key) -> OverlayState:
        # Drop every dead reference before the next call: buffers whose
        # Python refs linger stay allocated on this platform, and the
        # arenas pile up into the OOM the split exists to avoid.  Callers
        # pass the state in a one-element list ("box"): popping it leaves
        # NO outer frame holding the old state through the round (a
        # caller's `self.ostate = round(self.ostate, ...)` binding
        # otherwise keeps all 9.6 GB alive).
        if isinstance(st, list):
            st = st.pop()
        friends, cnt = st.friends, st.friend_cnt
        mk_dst, boot_dst = st.mk_dst, st.boot_dst
        bk_dst = st.bk_dst
        mk_spill0, bk_spill0 = st.mk_spill, st.bk_spill
        round_, mk0, bk0, d0 = (st.round, st.makeups, st.breakups,
                                st.mailbox_dropped)
        del st
        if dead_skip and carry["mk"] is not None and (
                carry["round"] != int(jax.device_get(round_))):
            # The incoming state is not the one the totals describe (a
            # restored snapshot fed to a live round fn): stale totals
            # would silently skip live rows -- fall back to popcounts.
            carry["mk"] = carry["bk"] = carry["boot"] = None
        known = dead_skip and carry["mk"] is not None
        bk_totals = carry["bk"] if dead_skip else None
        mk_totals = carry["mk"] + [carry["boot"]] if known else None
        if sc_split > 0:
            # An empty spill's re-delivery is a no-op that still pays one
            # full-spill-width sort (at the static-boot band the buffer
            # is burst-sized, ~19M pairs at 1e8) -- skip it when the
            # carried EXACT live count says there is nothing in flight.
            bk_spin = None if (known and carry["bk_sp"] == 0) else bk_spill0
            bk_mbox, n_bk, drop2, bk_sp = hosted_deliver(
                (bk_dst,), spill_in=bk_spin, row_totals=bk_totals)
        else:
            bk_mbox, n_bk, drop2 = hosted_deliver((bk_dst,),
                                                  row_totals=bk_totals)
            bk_sp = bk_spill0  # always-empty pass-through
        del bk_dst, bk_spill0
        fence()
        friends, cnt, mk_em, win_bk, mk_cnt = a2_fn(
            friends, cnt, bk_mbox, n_bk, drop2, round_, base_key)
        del bk_mbox
        jax.block_until_ready(friends)
        fence()
        if sc_split > 0:
            mk_spin = None if (known and carry["mk_sp"] == 0) else mk_spill0
            mk_mbox, n_mk, drop1, mk_sp = hosted_deliver(
                (mk_dst, reshape_boot(boot_dst)), spill_in=mk_spin,
                row_totals=mk_totals)
        else:
            mk_mbox, n_mk, drop1 = hosted_deliver(
                (mk_dst, reshape_boot(boot_dst)), row_totals=mk_totals)
            mk_sp = mk_spill0
        del mk_dst, boot_dst, mk_spill0
        fence()
        out = b2_fn(mk_mbox, n_mk, drop1, drop2, friends, cnt, mk_em,
                    win_bk, round_, mk0, bk0, d0, base_key, mk_sp, bk_sp,
                    mk_cnt, dead_skip)
        del mk_mbox, friends, cnt, mk_em, mk_sp, bk_sp, mk_cnt
        if dead_skip:
            out, (a_mk, a_bk, a_boot, a_msp, a_bsp, q) = out
            jax.block_until_ready(out.friends)
            # One small transfer per round (cap-sized vectors + scalars),
            # riding the sync the split already pays.
            a_mk, a_bk, a_boot, a_msp, a_bsp, q, rnd = jax.device_get(
                (a_mk, a_bk, a_boot, a_msp, a_bsp, q, out.round))
            carry["mk"] = [int(v) for v in a_mk]
            carry["bk"] = [int(v) for v in a_bk]
            carry["boot"] = int(a_boot)
            carry["mk_sp"] = int(a_msp)
            carry["bk_sp"] = int(a_bsp)
            carry["round"] = int(rnd)
            round4.last_quiesced = bool(q)
        else:
            jax.block_until_ready(out.friends)
        fence()
        return out

    round4.last_quiesced = None
    return round4


def use_split_round(cfg: Config, n_rows: int | None = None) -> bool:
    """Single-device rounds engine at memory scale (the sharded hook path
    keeps the fused round: its per-shard slices sit far below the band).
    Bounded above by flat int32 mailbox addressing (the hosted delivery
    is rank-major flat with no dense fallback); past that (~2.7e8 rows
    at cap 8) the state alone exceeds a single chip's HBM anyway --
    shard the node axis."""
    from gossip_simulator_tpu.ops.mailbox import flat_addressing_fits

    rows = n_rows if n_rows is not None else cfg.n
    return (rows >= SPLIT_ROUND_MIN_ROWS
            and flat_addressing_fits(rows, cfg.mailbox_cap_for(rows)))


class OverlayResult(NamedTuple):
    friends: jnp.ndarray
    friend_cnt: jnp.ndarray
    rounds: int
    makeups: int
    breakups: int
    mailbox_dropped: int


def pending_emissions(st: OverlayState) -> jnp.ndarray:
    # Spilled overflow pairs are in-flight messages (delivered next
    # round): quiescing while any remain would lose them.
    return ((st.mk_dst >= 0).sum(dtype=I32) + (st.bk_dst >= 0).sum(dtype=I32)
            + (st.boot_dst >= 0).sum(dtype=I32)
            + (st.mk_spill[1] >= 0).sum(dtype=I32)
            + (st.bk_spill[1] >= 0).sum(dtype=I32))


def quiesced(st: OverlayState) -> jnp.ndarray:
    """Zero processed this round AND zero in flight (race-free version of
    simulator.go:221-234).  The round counter guards round 0 (nothing has
    happened yet)."""
    return ((st.win_makeups == 0) & (st.win_breakups == 0)
            & (pending_emissions(st) == 0) & (st.round > 0))


def run_call_budget(cfg: Config, shards: int = 1) -> int:
    """Rounds per bounded overlay_run_to_quiescence device call (see
    overlay_ticks.run_call_budget for the per-call bound); a round
    here costs ~0.2 us/node, half the ticks-mode window.  `shards`
    scales the budget for a mesh backend (per-call device work tracks
    the per-SHARD slice) -- it multiplies BEFORE the >=1 clamp so large
    n keeps the ratio instead of collapsing to 1*shards."""
    return max(1, min(1024, int(4e7 * shards // max(cfg.n, 1))))


def make_bounded_run(round_fn, quiesced_fn, telemetry: bool = False):
    """Bounded phase-1 device loop: up to `max_polls` windows per call,
    early exit at quiescence, returning (st, polls_run, quiesced) -- the
    flag rides the loop carry so callers need no eager host-side
    quiesced() recompute (pending_emissions reduces the full (n, cap)-
    sized emission buffers; at large n that is an un-jitted multi-kernel
    dispatch).  THE one harness behind overlay.make_run_fn,
    overlay_ticks.make_run_fn and the sharded backend's fast path
    (whose round_fn is the shard_map'd poll -- its quiescence counters
    are psum-replicated on the outer state, so the condition is
    mesh-uniform).  Trajectory-identical to the windowed host loop:
    round keys are state-indexed (st.round / st.tick), not
    call-indexed.

    With `telemetry` the loop carries a device-resident per-window History
    (utils/telemetry.py), recording (clock, win_makeups, win_breakups,
    dropped) after every poll -- one probe works for every overlay engine
    because the sharded poll psum-replicates its window counters.  The
    signature gains a trailing `hist` argument and the return becomes
    (st, polls, quiesced, hist)."""
    import functools

    if telemetry:
        from gossip_simulator_tpu.utils import telemetry as telem

        @functools.partial(jax.jit, donate_argnums=(0, 3))
        def run_fn_t(st, base_key, max_polls, hist):
            def body(carry):
                st, polls, _, h = carry
                st = round_fn(st, base_key)
                h = telem.record(h, telem.overlay_probe(st))
                return st, polls + 1, quiesced_fn(st), h

            def cond(carry):
                st, polls, q, _ = carry
                return (polls < max_polls) & ~q

            return jax.lax.while_loop(
                cond, body, (st, jnp.zeros((), I32), quiesced_fn(st), hist))

        return run_fn_t

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_fn(st, base_key, max_polls):
        def body(carry):
            st, polls, _ = carry
            st = round_fn(st, base_key)
            return st, polls + 1, quiesced_fn(st)

        def cond(carry):
            st, polls, q = carry
            return (polls < max_polls) & ~q

        return jax.lax.while_loop(
            cond, body, (st, jnp.zeros((), I32), quiesced_fn(st)))

    return run_fn


def make_run_fn(cfg: Config, telemetry: bool = False):
    """Bounded device-side run for the rounds engine (make_bounded_run)."""
    return make_bounded_run(make_round_fn(cfg), quiesced, telemetry=telemetry)
