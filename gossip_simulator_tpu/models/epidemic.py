"""Epidemic-phase step kernels (the measured hot path).

The reference's per-message scalar receive path (simulator.go:107-123) and
goroutine broadcast (simulator.go:140-149) become one fused array program per
simulated tick:

    drain ring slot -> count deliveries -> crash draw -> infect (idempotent)
    -> gather friends of new infections -> drop mask -> scatter-add future
    arrivals into the delay ring.

Time semantics ("ticks" mode): 1 tick == 1 simulated ms.  Every broadcast
draws ONE shared delay uniform in [delaylow, delayhigh) ticks -- exactly the
reference's RandomNetworkDelay applied once per broadcast goroutine
(simulator.go:141-142) -- and each link send has an independent drop draw
(simulator.go:144).  Messages sit in a ``pending`` ring buffer of d slots of
n arrival *counts* (one flat ``[d * n]`` array, per shard on the sharded
backend: ring_row) so duplicate deliveries are counted like the reference's
TotalMessage (simulator.go:111) while infection stays an idempotent OR.

"rounds" mode is the classic synchronous-epidemic accounting: every hop takes
exactly one round (ring depth 2).

Documented divergence: when c messages reach a node in the same tick, the
crash draw fires with p = 1-(1-p)^c and all c messages are counted; the
reference processes the channel serially, so messages queued behind an
earlier crash-triggering one go uncounted (simulator.go:108-116).
Distributionally negligible for small p; exact for c=1.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp

from gossip_simulator_tpu import scenario as _scen
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models.state import (SimState, in_flight,
                                               init_exch_counts,
                                               msg64_add, msg64_zero)
from gossip_simulator_tpu.ops.select import first_true_indices  # noqa: F401  (re-export: compaction callers import it from here)
from gossip_simulator_tpu.ops.select import chunk_plan, plan_chunk
from gossip_simulator_tpu.utils import rng as _rng

I32 = jnp.int32
SEED_TICK = 0x7FFFFFFF  # reserved "tick" for the one-off seed draws (fold_in needs uint32)

# Named scopes of the single-device phase-2 program (jax.named_scope: op
# metadata only, the optimized HLO is unchanged).  A profile attributes
# each device op to the innermost of these on its op_name path, else to
# the scope of the loop running it (benchmark/scopes.py): COMBINE the
# node-local tick (tick_core, counters), EMIT sender compaction, drop and
# delay draws and the edge list, DELIVER the scatter-add into the delay
# ring with the compaction chunk loop around it, POLL the per-window loop
# predicate and telemetry row.  The sharded engine adds ROUTE, the
# all_to_all exchange with its pack and unpack (parallel/sharded_step.py).
COMBINE, EMIT, DELIVER, POLL = "combine", "emit", "deliver", "poll"
ROUTE = "route"


def ring_depth(cfg: Config) -> int:
    """Delay ring slots: delays are clamped to [1, delayhigh) so `delayhigh`
    slots suffice; rounds mode needs only {this, next}."""
    return cfg.delayhigh if cfg.effective_time_mode == "ticks" else 2


def p_eff(cfg: Config, p: float) -> float:
    """Reference's 1%-resolution truncation under compat (simulator.go:172,180)."""
    return int(p * 100) / 100.0 if cfg.compat_reference else p


def init_rumor_leaves(cfg: Config, n: int):
    """(pending_rumors, rumor_words, rumor_recv, rumor_done) -- full-size
    under Config.multi_rumor, placeholders otherwise (the down_since
    convention).  The ring engine stores per-rumor arrival counts over the
    R axis directly (int32[d, n, R]) -- a scatter-ADD exists where a
    scatter-OR does not, and R <= 1024 (validate) bounds the ring."""
    if not cfg.multi_rumor:
        return (jnp.zeros((1, 1, 1), I32), jnp.zeros((1, 1), jnp.uint32),
                jnp.zeros((1,), I32), jnp.full((1,), -1, I32))
    w = cfg.rumor_word_count
    return (jnp.zeros((ring_depth(cfg), n, cfg.rumors), I32),
            jnp.zeros((n, w), jnp.uint32),
            jnp.zeros((w * 32,), I32), jnp.full((w * 32,), -1, I32))


def unpack_rumor_bits(words: jnp.ndarray, r: int) -> jnp.ndarray:
    """uint32 (n, W) word ladder -> bool (n, r) per-rumor bits."""
    n, w = words.shape
    bits = ((words[:, :, None]
             >> jnp.arange(32, dtype=jnp.uint32)[None, None, :])
            & jnp.uint32(1)).astype(bool).reshape(n, w * 32)
    return bits[:, :r]


def pack_rumor_bits(bits: jnp.ndarray, w: int) -> jnp.ndarray:
    """bool (n, r) per-rumor bits -> uint32 (n, W) word ladder."""
    n, r = bits.shape
    padded = jnp.pad(bits, ((0, 0), (0, w * 32 - r)))
    return (padded.reshape(n, w, 32).astype(jnp.uint32)
            << jnp.arange(32, dtype=jnp.uint32)[None, None, :]).sum(
        axis=2, dtype=jnp.uint32)


def init_state(cfg: Config, friends: jnp.ndarray, friend_cnt: jnp.ndarray,
               n_local: int | None = None, n_shards: int = 1) -> SimState:
    """SimState for the whole node axis, or one shard's slice of it when
    the sharded backend passes `n_local`.  The delay ring is flat, the
    shard's own when sharded (ring_row)."""
    n = n_local if n_local is not None else cfg.n
    d = ring_depth(cfg)
    d_rb = d if cfg.protocol == "sir" else 1
    z = lambda: jnp.zeros((), I32)
    pending_rumors, rumor_words, rumor_recv, rumor_done = init_rumor_leaves(
        cfg, n)
    return SimState(
        received=jnp.zeros((n,), bool),
        crashed=jnp.zeros((n,), bool),
        removed=jnp.zeros((n,), bool),
        friends=friends,
        friend_cnt=friend_cnt,
        pending=jnp.zeros((d * n,), I32),
        rebroadcast=jnp.zeros((d_rb, n), bool),
        tick=z(), total_message=msg64_zero(), total_received=z(),
        total_crashed=z(),
        exchange_overflow=z(),
        down_since=_scen.init_down_since(cfg.faults_enabled, n),
        scen_crashed=z(), scen_recovered=z(), part_dropped=z(),
        heal_repaired=z(),
        pending_rumors=pending_rumors, rumor_words=rumor_words,
        rumor_recv=rumor_recv, rumor_done=rumor_done,
        exch_counts=init_exch_counts(cfg, n_shards),
    )


def row_slot(cfg: Config, delay_key, tick, rows):
    """Delay-ring slot for each row's broadcast this tick.  Row-keyed
    (utils/rng.row_keys): row r's shared per-broadcast delay
    (simulator.go:141-142) depends only on (delay_key, r), so the compaction
    path can draw delays for just the gathered sender rows and land on
    exactly the dense path's values."""
    d = ring_depth(cfg)
    if cfg.effective_time_mode == "rounds":
        return jnp.broadcast_to((tick + 1) % d, rows.shape).astype(I32)
    delay = _rng.row_uniform_delay(delay_key, cfg.delaylow, cfg.delayhigh,
                                   rows)
    return ((tick + delay) % d).astype(I32)


def ring_row(pending, slot, n: int):
    """Ring slot `slot`'s n arrival counts.  The ring is flat, int32[d * n]
    with slot s at [s * n, (s + 1) * n) (deposit_local says why); on the
    sharded backend each shard holds its own such ring over its n_local
    rows."""
    return jax.lax.dynamic_slice(pending, (slot * n,), (n,))


def ring_clear(pending, slot, n: int):
    """`pending` with slot `slot` zeroed (see ring_row)."""
    return jax.lax.dynamic_update_slice(
        pending, jnp.zeros((n,), pending.dtype), (slot * n,))


def tick_keys(base_key: jax.Array, tick, shard: jax.Array | int | None = None):
    """Per-tick op keys; `shard` (axis index) decorrelates shards in the
    sharded backend."""
    if shard is not None:
        base_key = jax.random.fold_in(base_key, shard)
    return {
        "crash": _rng.tick_key(base_key, tick, _rng.OP_CRASH),
        "delay": _rng.tick_key(base_key, tick, _rng.OP_DELAY),
        "drop": _rng.tick_key(base_key, tick, _rng.OP_DROP),
        "remove": _rng.tick_key(base_key, tick, _rng.OP_REMOVE),
    }


def tick_core(cfg: Config, st: SimState, keys: dict):
    """The node-local physics of one tick -- everything except delivering the
    outgoing wave: drain ring slot, count, crash draw, infect, SIR removal /
    re-broadcast scheduling, shared-delay draw.

    Shard-agnostic: arrays may be the full node axis or one shard of it.
    Returns ``(st_partial, senders, dslot, deltas)`` where `st_partial` has
    everything updated except `pending` additions from the new wave, `senders`
    marks local rows broadcasting this tick, `dslot` is their target ring slot
    and `deltas = (d_message, d_received, d_crashed)` are LOCAL sums (callers
    psum them across shards before adding to the replicated totals).

    Multi-rumor (Config.multi_rumor; SI, single-device only -- validate):
    per-rumor arrivals drain from ``pending_rumors`` alongside the total
    counts, a node's NEW bits (arrived, not crashed, not yet held) fold
    into its rumor_words, `senders` becomes any-new-bit (an
    already-infected node gaining a new rumor re-broadcasts), and the
    return gains a trailing ``newbits`` bool (n, R) -- the payload the
    caller deposits (deposit_rumors).  The 4-tuple return is unchanged
    when multi is off.
    """
    sir = cfg.protocol == "sir"
    multi = cfg.multi_rumor
    crash_p = p_eff(cfg, cfg.crashrate)
    d = ring_depth(cfg)
    n = st.received.shape[0]
    ids = jnp.arange(n, dtype=I32)

    slot = st.tick % d
    arrivals = ring_row(st.pending, slot, n)
    pending = ring_clear(st.pending, slot, n)
    counted = jnp.where(st.crashed, 0, arrivals)  # black-hole, uncounted
    d_message = counted.sum(dtype=I32)
    has = counted > 0

    if crash_p > 0.0:
        pc = 1.0 - jnp.power(1.0 - crash_p, counted.astype(jnp.float32))
        new_crash = (jax.random.uniform(keys["crash"], (n,)) < pc) & has
    else:
        new_crash = jnp.zeros((n,), bool)
    crashed = st.crashed | new_crash
    d_crashed = new_crash.sum(dtype=I32)
    if cfg.faults_enabled and crash_p > 0.0:
        # Reception crashes stamp the crash clock too: under a scenario
        # every crash is subject to the reboot timeline (scenario.py's
        # "machines reboot" model) and to the healer's detection window.
        st = st._replace(down_since=jnp.where(
            new_crash, st.tick.astype(I32), st.down_since))

    newly = has & ~crashed & ~st.received
    received = st.received | newly
    d_received = newly.sum(dtype=I32)

    newbits = None
    if multi:
        arr_r = st.pending_rumors[slot]  # (n, R) per-rumor arrival counts
        pending_r = st.pending_rumors.at[slot].set(0)
        rbits = unpack_rumor_bits(st.rumor_words, cfg.rumors)
        newbits = (arr_r > 0) & ~crashed[:, None] & ~rbits
        rumor_words = st.rumor_words | pack_rumor_bits(
            newbits, cfg.rumor_word_count)
        rumor_recv = st.rumor_recv + jnp.pad(
            newbits.sum(axis=0, dtype=I32),
            (0, st.rumor_recv.shape[0] - cfg.rumors))
        st = st._replace(pending_rumors=pending_r, rumor_words=rumor_words,
                         rumor_recv=rumor_recv)

    # Dense per-row delay slots are only materialized when something consumes
    # them for all n rows (SIR's re-broadcast scheduling, the dense
    # delivery path, or the always-dense multi-rumor deposit); the compact
    # SI path draws slots per gathered row.
    if sir or multi or not cfg.compact_resolved:
        dslot = row_slot(cfg, keys["delay"], st.tick, ids)
    else:
        dslot = None

    if sir:
        due = st.rebroadcast[slot] & ~crashed & ~st.removed
        rb = st.rebroadcast.at[slot].set(False)
        senders = newly | due
        removal = _rng.bernoulli(keys["remove"], p_eff(cfg, cfg.removal_rate),
                                 (n,)) & senders
        removed = st.removed | removal
        rb = rb.at[dslot, ids].max(senders & ~removal)
    else:
        rb = st.rebroadcast
        senders = newbits.any(axis=1) if multi else newly
        removed = st.removed

    st_partial = st._replace(
        received=received, crashed=crashed, removed=removed, pending=pending,
        rebroadcast=rb, tick=st.tick + 1)
    if multi:
        return st_partial, senders, dslot, (d_message, d_received,
                                            d_crashed), newbits
    return st_partial, senders, dslot, (d_message, d_received, d_crashed)


def apply_fault_window(cfg: Config, st: SimState, ids_global, base_key,
                       nticks: int = 1):
    """Apply the scenario's crash/churn/recovery timeline to a SimState for
    the window [st.tick, st.tick + nticks) (scenario.fault_window; the ring
    engine steps per tick, nticks=1).  Returns ``(st, d_crash, d_recover)``
    with the masks applied but the replicated counters NOT yet updated --
    the sharded caller psums the deltas first.  A no-op (st unchanged,
    Python zeros) when the scenario has no fault events, so the traced
    program is untouched at ``-scenario off``."""
    scen = cfg.scenario_resolved
    if not scen.has_faults:
        return st, 0, 0
    new_crash, recover, down, dc, drc = _scen.fault_window(
        scen, cfg.n, st.tick, nticks, ids_global, st.crashed,
        st.down_since, base_key)
    crashed = (st.crashed & ~recover) | new_crash
    return st._replace(crashed=crashed, down_since=down), dc, drc


def edges_from_senders(cfg: Config, friends, friend_cnt, senders, dslot,
                       drop_key, tick=None, gid0=0):
    """Flatten this tick's outgoing wave into (dst_global, dslot, valid) flat
    arrays -- the message list the delivery layer (local scatter or
    cross-shard all_to_all route) consumes.  Per-link drop draw happens here
    (simulator.go:144), row-keyed so the compact path samples identically;
    the shared per-broadcast delay came in via dslot.

    `tick`/`gid0` feed the scenario partition mask (send-time semantics:
    scenario.partition_blocked); the fourth return is the count of edges it
    black-holed (a Python 0 when no partitions are configured, so the
    -scenario off trace is unchanged).  `gid0` is the global id of local
    row 0 (nonzero on the sharded backend's shards)."""
    n, k = friends.shape
    rows = jnp.arange(n, dtype=I32)
    drop = _rng.row_bernoulli(drop_key, p_eff(cfg, cfg.droprate), rows, k)
    edge = (jnp.arange(k, dtype=I32)[None, :] < friend_cnt[:, None]) \
        & senders[:, None] & ~drop & (friends >= 0)
    scen = cfg.scenario_resolved
    blocked_n = 0
    if scen.has_partitions and tick is not None:
        blocked = _scen.partition_blocked(
            scen, cfg.n, tick, (gid0 + rows)[:, None], friends) & edge
        blocked_n = blocked.sum(dtype=I32)
        edge = edge & ~blocked
    dst = jnp.where(edge, friends, -1).reshape(-1)
    slots = jnp.broadcast_to(dslot[:, None], (n, k)).reshape(-1)
    return dst, slots, edge.reshape(-1), blocked_n


def compact_chunk_cap(cfg: Config, n_local: int) -> int:
    """Static sender-compaction chunk size.  In ticks mode the per-tick wave
    is spread over the delay window; n/128 keeps each chunk's gather and
    scatter small with the chunked loop absorbing peak ticks; rounds mode
    processes everything at once."""
    if cfg.compact_chunk > 0:
        return min(n_local, cfg.compact_chunk)
    if cfg.effective_time_mode == "rounds":
        return n_local
    return min(n_local, max(4096, n_local // 128))


def compact_gather(cfg: Config, friends, friend_cnt, dslot, delay_key,
                   drop_key, tick, idx, gid0=0):
    """Edge list (dst, slot, valid) of the sender rows `idx` (one chunk of
    select.plan_chunk; fill rows, index n, gather as invalid) and the
    scenario-partition block count (Python 0 with no partitions -- see
    edges_from_senders).  Drop masks and delay slots are row-keyed
    (utils/rng.row_keys), drawn here for just the gathered rows --
    bit-identical to the dense path's draws for the same rows (tested)."""
    n, k = friends.shape
    cap = idx.shape[0]
    sf = friends.at[idx].get(mode="fill", fill_value=-1)
    scnt = friend_cnt.at[idx].get(mode="fill", fill_value=0)
    # Fill rows draw junk (row id n) but their edges are already invalid.
    sdrop = _rng.row_bernoulli(drop_key, p_eff(cfg, cfg.droprate), idx, k)
    if dslot is not None:
        sslot = dslot.at[idx].get(mode="fill", fill_value=0)
    else:
        sslot = row_slot(cfg, delay_key, tick, idx)
    edge = (jnp.arange(k, dtype=I32)[None, :] < scnt[:, None]) \
        & ~sdrop & (sf >= 0)
    scen = cfg.scenario_resolved
    blocked_n = 0
    if scen.has_partitions:
        # Same send-time predicate as the dense path, on just the gathered
        # rows (fill rows' edges are already invalid).
        blocked = _scen.partition_blocked(
            scen, cfg.n, tick, (gid0 + idx)[:, None], sf) & edge
        blocked_n = blocked.sum(dtype=I32)
        edge = edge & ~blocked
    dst = jnp.where(edge, sf, -1).reshape(-1)
    slots = jnp.broadcast_to(sslot[:, None], (cap, k)).reshape(-1)
    return dst, slots, edge.reshape(-1), blocked_n


def deposit_compact(cfg: Config, pending, friends, friend_cnt,
                    senders, dslot, delay_key, drop_key, tick):
    """Compacted equivalent of edges_from_senders + deposit_local: only
    actual sender rows reach the RNG, gather and scatter.  Row-keyed draws
    keep the trajectory bit-identical to the dense path (tested).  The
    senders are ranked once (select.chunk_plan); chunk j deposits those of
    rank [j*cap, (j+1)*cap), and the loop carries the ring, a scalar
    cursor and the partition count.  Returns ``(pending,
    partition_blocked_count)`` -- the count is a Python 0 when no
    partitions are configured."""
    n, k = friends.shape
    cap = compact_chunk_cap(cfg, n)
    dkern = cfg.deliver_kernel_resolved
    with jax.named_scope(EMIT):
        plan = chunk_plan(senders, cap)
    chunks = (plan.count + cap - 1) // cap

    def body(j, carry):
        pending, cursor, blk = carry
        with jax.named_scope(EMIT):
            idx, cursor = plan_chunk(plan, j, cursor)
            dst, slots, valid, b = compact_gather(
                cfg, friends, friend_cnt, dslot, delay_key, drop_key, tick,
                idx)
        return deposit_local(pending, dst, slots, valid, kernel=dkern,
                             n=n), cursor, blk + b

    zero = jnp.zeros((), I32)
    pending, _, blk = jax.lax.fori_loop(0, chunks, body,
                                        (pending, zero, zero))
    return pending, (blk if cfg.scenario_resolved.has_partitions else 0)


def deposit_local(pending, dst_local, slots, valid, *, n: int,
                  kernel="xla"):
    """Scatter arrivals into the flat pending ring of `n` rows per slot
    (ring_row; idempotent counting add: duplicates accumulate like the
    reference's per-message channel sends).

    NOTE: keep the ring flat, on one device and per shard.  The TPU
    compiler lowers a scatter-add only onto a linear buffer, so on a 2-D
    [d, n] ring it copied the whole ring out to a flat buffer and back
    around every call -- once per compaction chunk, most of phase 2's
    device time.  The flat scatter runs in place.  An invalid lane indexes
    d * n, outside the ring for every slot, so mode="drop" discards it
    explicitly (a sentinel dst = n would land on node 0 of the NEXT slot).
    chip_smoke.py's pinned totals check that the drop applies on the chip.
    kernel="pallas" routes to the fused serial add
    (ops/pallas_deliver.fused_deposit_add) whose in-range check replaces
    the scatter's OOB-drop explicitly -- integer adds commute, so it is
    bit-identical."""
    if kernel == "pallas":
        from gossip_simulator_tpu.ops import pallas_deliver
        dst = jnp.where(valid, dst_local, n)
        out = pallas_deliver.fused_deposit_add(pending.reshape(-1, n),
                                               slots, dst)
        return out.reshape(pending.shape)
    idx = jnp.where(valid, slots * n + dst_local, pending.shape[0])
    return pending.at[idx].add(1, mode="drop")


def deposit_rumors(pending_rumors, dst_local, slots, valid, newbits,
                   kernel="xla"):
    """Multi-rumor companion to deposit_local: each kept edge adds its
    sender's NEW rumor bits (one-hot int rows) into the destination's
    (slot, dst) per-rumor lane: a 2-D leading-index scatter into the
    [d, n, R] ring, the R axis riding as the scatter's trailing window
    dimension.  kernel="pallas" applies the whole R-row add in-register
    at the shared (slot, dst) cell (fused_deposit_rows) -- the multi-rumor
    combine rides the fused pass for free."""
    n, r = newbits.shape
    k = dst_local.shape[0] // n
    vals = jnp.broadcast_to(newbits[:, None, :].astype(I32),
                            (n, k, r)).reshape(n * k, r)
    dst = jnp.where(valid, dst_local, pending_rumors.shape[1])
    if kernel == "pallas":
        from gossip_simulator_tpu.ops import pallas_deliver
        return pallas_deliver.fused_deposit_rows(
            pending_rumors, slots, dst, vals)
    return pending_rumors.at[slots, dst].add(vals, mode="drop")


def make_tick_fn(cfg: Config) -> Callable[[SimState, jax.Array], SimState]:
    """Single-device per-tick transition for SI / SIR push gossip."""

    # NOTE: no lax.cond "skip empty ticks" fast path: a lax.cond around the
    # dynamic-trip-count chunk fori_loop, nested inside the window
    # fori_loop, once scattered every gathered chunk row regardless of
    # validity on TPU, and the skip measured no wall-clock win (empty slots
    # are rare once delays spread the wave).
    multi = cfg.multi_rumor
    dkern = cfg.deliver_kernel_resolved
    if multi:
        target = int(math.ceil(cfg.coverage_target * cfg.n))

    def tick_fn(st: SimState, base_key: jax.Array) -> SimState:
        with jax.named_scope(COMBINE):
            st, dsc, dsr = apply_fault_window(
                cfg, st, jnp.arange(st.received.shape[0], dtype=I32),
                base_key)
            keys = tick_keys(base_key, st.tick)
            out = tick_core(cfg, st, keys)
        stp, senders, dslot, (dm, dr, dc) = out[:4]
        n = stp.received.shape[0]
        if multi:
            # Always-dense delivery: the compact gather has no per-rumor
            # payload channel, and the multi configs are single-device
            # (validate) where the dense path is the proven form.
            newbits = out[4]
            with jax.named_scope(EMIT):
                dst, slots, valid, blk = edges_from_senders(
                    cfg, stp.friends, stp.friend_cnt, senders, dslot,
                    keys["drop"], tick=st.tick)
            with jax.named_scope(DELIVER):
                pending = deposit_local(stp.pending, dst, slots, valid,
                                        kernel=dkern, n=n)
                stp = stp._replace(pending_rumors=deposit_rumors(
                    stp.pending_rumors, dst, slots, valid, newbits,
                    kernel=dkern))
            with jax.named_scope(COMBINE):
                hit = (stp.rumor_recv >= target) & (stp.rumor_done < 0)
                stp = stp._replace(rumor_done=jnp.where(
                    hit, stp.tick, stp.rumor_done))
        elif cfg.compact_resolved:
            # The chunk loop is DELIVER and opens EMIT inside for its
            # gathers (deposit_compact): the loop's own bookkeeping and
            # any op the compiler makes there carry no op_name, and a
            # profile reads them under the loop's scope.  The scatter-add
            # writes the flat ring in place (deposit_local's NOTE).
            with jax.named_scope(DELIVER):
                pending, blk = deposit_compact(
                    cfg, stp.pending, stp.friends, stp.friend_cnt, senders,
                    dslot, keys["delay"], keys["drop"], st.tick)
        else:
            with jax.named_scope(EMIT):
                dst, slots, valid, blk = edges_from_senders(
                    cfg, stp.friends, stp.friend_cnt, senders, dslot,
                    keys["drop"], tick=st.tick)
            with jax.named_scope(DELIVER):
                pending = deposit_local(stp.pending, dst, slots, valid,
                                        kernel=dkern, n=n)
        with jax.named_scope(COMBINE):
            stp = stp._replace(
                pending=pending,
                total_message=msg64_add(stp.total_message, dm),
                total_received=stp.total_received + dr,
                total_crashed=stp.total_crashed + dc)
            if cfg.scenario_resolved.active:
                stp = stp._replace(
                    scen_crashed=stp.scen_crashed + dsc,
                    scen_recovered=stp.scen_recovered + dsr,
                    part_dropped=stp.part_dropped + blk)
        return stp

    return tick_fn


def make_seed_fn(cfg: Config) -> Callable[[SimState, jax.Array], SimState]:
    """Uniform-random sender's initial broadcast (simulator.go:240-241).
    Unless compat_reference, the seed itself is marked received (the reference
    never marks it -- SURVEY §5.4 quirk).

    Multi-rumor (oneshot only here -- stream requires the event engine):
    all R sources draw from the shard-invariant OP_INJECT-by-rumor-index
    streams (the event engine's injection_batch keying), their bits set
    immediately, and every source broadcasts in ONE dense deposit."""
    if cfg.multi_rumor:
        r_total, w = cfg.rumors, cfg.rumor_word_count

        def seed_multi(st: SimState, base_key: jax.Array) -> SimState:
            n = st.received.shape[0]
            rr = jnp.arange(r_total, dtype=I32)
            ik = jax.random.fold_in(base_key, _rng.OP_INJECT)
            srcs = jax.vmap(lambda q: jax.random.randint(
                jax.random.fold_in(ik, q), (), 0, n, dtype=I32))(rr)
            masks = jnp.where(
                (rr[:, None] // 32) == jnp.arange(w, dtype=I32)[None, :],
                (jnp.uint32(1) << (rr % 32).astype(jnp.uint32))[:, None],
                jnp.uint32(0))
            # Distinct bits never collide, so the scatter-ADD of colliding
            # source rows IS their OR.
            delta = jnp.zeros((n, w), jnp.uint32).at[srcs].add(masks)
            is_src = (delta != jnp.uint32(0)).any(axis=1)
            received = st.received | is_src
            total_received = st.total_received + is_src.sum(dtype=I32)
            rumor_recv = st.rumor_recv + (
                jnp.arange(st.rumor_recv.shape[0], dtype=I32)
                < r_total).astype(I32)
            kd = _rng.tick_key(base_key, SEED_TICK, _rng.OP_DELAY)
            kp = _rng.tick_key(base_key, SEED_TICK, _rng.OP_DROP)
            dslot = row_slot(cfg, kd, st.tick, jnp.arange(n, dtype=I32))
            dst, slots, valid, blk = edges_from_senders(
                cfg, st.friends, st.friend_cnt, is_src, dslot, kp,
                tick=st.tick)
            pending = deposit_local(st.pending, dst, slots, valid, n=n)
            pending_r = deposit_rumors(
                st.pending_rumors, dst, slots, valid,
                unpack_rumor_bits(delta, r_total))
            if cfg.scenario_resolved.has_partitions:
                st = st._replace(part_dropped=st.part_dropped + blk)
            return st._replace(
                received=received, total_received=total_received,
                pending=pending, pending_rumors=pending_r,
                rumor_words=st.rumor_words | delta, rumor_recv=rumor_recv)

        return seed_multi

    def seed_fn(st: SimState, base_key: jax.Array) -> SimState:
        n = st.received.shape[0]
        ks = _rng.tick_key(base_key, SEED_TICK, _rng.OP_SEED_NODE)
        kd = _rng.tick_key(base_key, SEED_TICK, _rng.OP_DELAY)
        kp = _rng.tick_key(base_key, SEED_TICK, _rng.OP_DROP)
        sender = jax.random.randint(ks, (), 0, n, dtype=I32)
        is_sender = jnp.arange(n, dtype=I32) == sender
        received, total_received = st.received, st.total_received
        if cfg.protocol != "si" or not cfg.compat_reference:
            # The seed-never-received quirk (SURVEY §5.4) is an SI compat
            # surface only: pushpull/SIR have no referent in the reference,
            # and the event engine needs the received bit for trigger
            # firing, so both engines mark+count the seed there.
            received = received | is_sender
            total_received = total_received + 1
        if cfg.protocol == "pushpull":
            return st._replace(received=received, total_received=total_received)
        dslot = row_slot(cfg, kd, st.tick, jnp.arange(n, dtype=I32))
        dst, slots, valid, blk = edges_from_senders(
            cfg, st.friends, st.friend_cnt, is_sender, dslot, kp,
            tick=st.tick)
        if cfg.scenario_resolved.has_partitions:
            st = st._replace(part_dropped=st.part_dropped + blk)
        pending = deposit_local(st.pending, dst, slots, valid, n=n)
        rb = st.rebroadcast
        if cfg.protocol == "sir":
            # The seed is a sender like any other: removal draw decides
            # whether it keeps re-broadcasting.
            kr = _rng.tick_key(base_key, SEED_TICK, _rng.OP_REMOVE)
            keep = ~_rng.bernoulli(kr, p_eff(cfg, cfg.removal_rate), ())
            rb = rb.at[dslot, jnp.arange(n, dtype=I32)].max(is_sender & keep)
        return st._replace(received=received, total_received=total_received,
                           pending=pending, rebroadcast=rb)

    return seed_fn


def packed_peer_state(received, crashed) -> jnp.ndarray:
    """uint8[n]: 0 susceptible, 1 infected, 2/3 crashed -- ONE random-access
    gather answers both "live?" (< 2) and "live and infected?" (== 1) for the
    pull side of anti-entropy; random access on (n, fanout) peer indices is
    the round's dominant cost at 10M x 23 peers."""
    return received.astype(jnp.uint8) + crashed.astype(jnp.uint8) * 2


def pushpull_chunk_cap(cfg: Config, n_local: int) -> int:
    """Wave-compaction chunk for the push-pull round: rows per gathered
    batch.  n/8 keeps the per-chunk (cap, f) draw+gather bounded (~29M
    lanes at 10M x f=23) while early/late rounds with small active sets
    run a single near-empty chunk; -compact-chunk overrides."""
    if cfg.compact_chunk > 0:
        return min(n_local, cfg.compact_chunk)
    return min(n_local, max(4096, n_local // 8))


def make_pushpull_fn(cfg: Config) -> Callable[[SimState, jax.Array], SimState]:
    """One synchronous push-pull anti-entropy round over uniform random peers
    (BASELINE.json config 3; no referent in the reference).  Push receptions
    are counted and can crash the receiver; pull responses from live peers are
    counted; infection crosses any surviving contact.

    Round 4: the peer and drop draws are ROW-KEYED (utils/rng.row_keys),
    so the wave-compacted path -- push over only the infected-live rows,
    pull over only the susceptible rows, the SI engines' compaction
    applied here -- draws exactly the dense path's values and stays
    bit-identical to it (tested; `-compact off` forces the dense form).
    The two active sets partition the live nodes, so compaction halves
    the per-round gather/draw volume on top of skipping dead rows.
    (Re-keying from the pre-r4 full-matrix draws changed this config's
    trajectory once -- same distribution, new sample; bench totals moved
    accordingly.)"""
    drop_p = p_eff(cfg, cfg.droprate)
    crash_p = p_eff(cfg, cfg.crashrate)
    f = cfg.fanout
    compact = cfg.compact != "off"

    def round_fn(st: SimState, base_key: jax.Array) -> SimState:
        n = st.received.shape[0]
        k1 = _rng.tick_key(base_key, st.tick, _rng.OP_BOOTSTRAP)
        k2 = _rng.tick_key(base_key, st.tick, _rng.OP_PULL)
        kd1 = _rng.tick_key(base_key, st.tick, _rng.OP_DROP)
        kd2 = _rng.tick_key(base_key, st.tick, _rng.OP_DELAY)
        kc = _rng.tick_key(base_key, st.tick, _rng.OP_CRASH)

        live = ~st.crashed
        inf = st.received & live
        sus = ~st.received & live
        packed = packed_peer_state(st.received, st.crashed)

        def compact_rows(mask, body, init):
            """Run `body(idx, valid, carry)` over <=cap-row batches of
            mask's True rows (the SI deposit_compact pattern)."""
            cap = pushpull_chunk_cap(cfg, n)
            chunks = (mask.sum(dtype=I32) + cap - 1) // cap

            def step(_, carry):
                state, remaining = carry
                idx = first_true_indices(remaining, cap)
                hit = jnp.zeros((n,), bool).at[idx].set(True, mode="drop")
                return body(idx, idx < n, state), remaining & ~hit

            out, _ = jax.lax.fori_loop(0, chunks, step, (init, mask))
            return out

        # --- push: infected -> fanout random peers --------------------------
        if compact:
            def push_body(idx, v, arriving):
                peers = _rng.row_randint(k1, n, idx, f)
                kept = ~_rng.row_bernoulli(kd1, drop_p, idx, f)
                edge = v[:, None] & kept
                # Explicit trash cell (index n, in bounds) instead of a
                # flat OOB-drop scatter (see deposit_local NOTE).
                dst = jnp.where(edge, peers, n).reshape(-1)
                return arriving.at[dst].add(1, mode="promise_in_bounds")

            arriving = compact_rows(
                inf, push_body, jnp.zeros((n + 1,), I32))[:n]
        else:
            rows = jnp.arange(n, dtype=I32)
            peers = _rng.row_randint(k1, n, rows, f)
            kept = ~_rng.row_bernoulli(kd1, drop_p, rows, f)
            edge = inf[:, None] & kept
            dst = jnp.where(edge, peers, n).reshape(-1)
            arriving = jnp.zeros((n + 1,), I32).at[dst].add(
                1, mode="promise_in_bounds")[:n]

        counted = jnp.where(live, arriving, 0)
        total_message = msg64_add(st.total_message, counted.sum(dtype=I32))
        if crash_p > 0.0:
            pc = 1.0 - jnp.power(1.0 - crash_p, counted.astype(jnp.float32))
            new_crash = (jax.random.uniform(kc, (n,)) < pc) & (counted > 0)
        else:
            new_crash = jnp.zeros((n,), bool)
        crashed = st.crashed | new_crash
        total_crashed = st.total_crashed + new_crash.sum(dtype=I32)
        newly_push = (counted > 0) & ~crashed & ~st.received

        # --- pull: surviving susceptible <- fanout random peers' state ------
        # A requester crashed by THIS round's push does not pull (its
        # requests go uncounted) -- the pre-r4 ordering, preserved; peer
        # state stays the pre-round snapshot.
        puller = sus & ~new_crash
        if compact:
            def pull_body(idx, v, carry):
                hit, msgs = carry
                peers2 = _rng.row_randint(k2, n, idx, f)
                kept2 = ~_rng.row_bernoulli(kd2, drop_p, idx, f)
                req = v[:, None] & kept2
                pstate = packed.at[peers2].get(mode="fill", fill_value=2)
                rowhit = (req & (pstate == 1)).any(axis=1)
                msgs = msgs + (req & (pstate < 2)).sum(dtype=I32)
                hit = hit.at[jnp.where(v, idx, n)].max(
                    rowhit, mode="promise_in_bounds")
                return hit, msgs

            pull_hit, pull_msgs = compact_rows(
                puller, pull_body,
                (jnp.zeros((n + 1,), bool), jnp.zeros((), I32)))
            pull_hit = pull_hit[:n]
        else:
            peers2 = _rng.row_randint(k2, n, rows, f)
            kept2 = ~_rng.row_bernoulli(kd2, drop_p, rows, f)
            req = puller[:, None] & kept2
            pstate = packed[peers2]
            pull_hit = (req & (pstate == 1)).any(axis=1)
            pull_msgs = (req & (pstate < 2)).sum(dtype=I32)
        total_message = msg64_add(total_message, pull_msgs)

        newly = (newly_push | pull_hit) & ~crashed & ~st.received
        received = st.received | newly
        total_received = st.total_received + newly.sum(dtype=I32)
        return st._replace(received=received, crashed=crashed,
                           tick=st.tick + 1, total_message=total_message,
                           total_received=total_received,
                           total_crashed=total_crashed)

    return round_fn


def make_step_fn(cfg: Config) -> Callable[[SimState, jax.Array], SimState]:
    if cfg.protocol == "pushpull":
        return make_pushpull_fn(cfg)
    return make_tick_fn(cfg)


def make_heal_fn(cfg: Config):
    """Single-device ring-engine overlay healing (None when -overlay-heal
    is off, keeping the traced window untouched): once per poll window,
    condemn dead friends (scenario.detect_dead), replace them via the
    phase-1 makeup draw and deposit the infected healers' re-sends into
    the delay ring like any broadcast (scenario.heal_and_wave)."""
    if not cfg.overlay_heal_resolved:
        return None
    detect = cfg.heal_detect_ms
    d = ring_depth(cfg)

    def heal_fn(st: SimState, base_key: jax.Array) -> SimState:
        n, k = st.friends.shape
        ids = jnp.arange(n, dtype=I32)
        detected = _scen.detect_dead(st.crashed, st.down_since, st.tick,
                                     detect)
        healer_ok = ~st.crashed
        sender_inf = st.received & ~st.crashed & ~st.removed
        bits = _scen.heal_peer_bits(detected, sender_inf)
        friends, resend, pull, delay, clear, rep, blk = _scen.heal_and_wave(
            cfg, st.friends, st.friend_cnt, bits, healer_ok, sender_inf,
            _scen.rejoined_mask(st.down_since), ids, st.tick, base_key)
        if cfg.effective_time_mode == "rounds":
            dslot = jnp.broadcast_to((st.tick + 1) % d, (n,)).astype(I32)
        else:
            dslot = ((st.tick + delay) % d).astype(I32)
        slots = jnp.broadcast_to(dslot[:, None], (n, k)).reshape(-1)
        dst = jnp.where(resend, friends, -1).reshape(-1)
        pending = deposit_local(st.pending, dst, slots, resend.reshape(-1),
                                n=n)
        # Rejoin pull responses deliver to the puller's OWN row.
        pdst = jnp.broadcast_to(ids[:, None], (n, k)).reshape(-1)
        pending = deposit_local(pending, pdst, slots, pull.reshape(-1), n=n)
        return st._replace(
            friends=friends, pending=pending,
            down_since=jnp.where(clear, -1, st.down_since),
            heal_repaired=st.heal_repaired + rep,
            part_dropped=st.part_dropped + blk)

    return heal_fn


def make_window_fn(cfg: Config, window: int):
    """`window` consecutive steps as one device call (one progress window).
    The state is donated: the pending ring mutates in place instead of
    costing a fresh HBM allocation + copy per window (essential at 100M,
    where two ring copies would not fit).  With -overlay-heal on, the
    healing pass runs once at the end of every window -- the same cadence
    (and tick keys) the fast-path loop heals at, so both paths walk one
    trajectory."""
    step = make_step_fn(cfg)
    heal = make_heal_fn(cfg)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def window_fn(st: SimState, base_key: jax.Array) -> SimState:
        st = jax.lax.fori_loop(0, window, lambda _, s: step(s, base_key), st)
        if heal is not None:
            st = heal(st, base_key)
        return st

    return window_fn


def run_call_budget(cfg: Config) -> int:
    """Ticks per run_to_coverage device call.  One giant while_loop call can
    run for minutes at large n, so the host loop re-enters a bounded call
    until done -- same compiled executable, same trajectory (keys depend
    only on tick); the bound is how long one call holds the device before
    the host can check progress, exhaustion and signals.  The 1024 cap bounds how long a dead wave can spin before the host-side
    exhaustion check sees it (the single-device event engine also exits on
    its device-side in-flight term; the ring and sharded engines rely on
    this granularity).

    Push-pull budgets by LANES, not ticks: one anti-entropy round touches
    n * 2f peer draws (every node pushes f and pulls f, no wavefront to
    compact down to), so a round at 5e7 x fanout 26 is ~6 s of device
    work by itself and the SI-shaped 3.3e9/n budget (66 rounds) would hold
    the device for minutes per call.  1.5e9 lanes/call keeps calls in the
    2-6 s band across the measured sizes; the sizing is left to a measured
    PR."""
    if cfg.protocol == "pushpull":
        return max(1, min(cfg.max_rounds, 1024,
                          int(1.5e9 // max(1, 2 * cfg.fanout * cfg.n))))
    return max(64, min(cfg.max_rounds, 1024, int(3.3e9 // max(cfg.n, 1))))


def make_run_to_coverage_fn(cfg: Config, telemetry: bool = False):
    """Device-side while_loop toward the coverage target: zero host syncs in
    the hot loop (the reference's 10 ms polling becomes one device-side
    predicate, simulator.go:243-251).  Runs until target/max_rounds/`until`
    ticks; callers loop over bounded calls (run_call_budget).

    With `telemetry` the loop additionally carries a device-resident
    per-window History (utils/telemetry.py) and records one counters row
    after every poll window -- the trajectory the windowed driver loop
    observes, without its per-window host round-trip; the signature becomes
    `run_fn(st, key, target, until, hist) -> (st, hist)`."""
    step = make_step_fn(cfg)
    heal = make_heal_fn(cfg)
    window = 1 if cfg.effective_time_mode == "rounds" else 10
    max_steps = cfg.max_rounds
    # Push-pull draws fresh random peers each round -- there is no ring
    # occupancy to test, and the wave never "dies in flight".  Healing can
    # REVIVE an empty ring (a pending dead-friend detection re-sends from
    # an already-infected healer), so heal-on runs drop the early-death
    # exit and run to target/max_rounds (same gate in the host exhaustion
    # checks -- backends set `exhausted` only with healing off).
    check_in_flight = (cfg.protocol != "pushpull"
                       and not cfg.overlay_heal_resolved)
    multi = cfg.multi_rumor
    rumors = cfg.rumors

    def cond_live(s: SimState, target_count, until):
        with jax.named_scope(POLL):
            if multi:
                # Every rumor must hit the target; lanes >= R are padding.
                recv = jnp.min(s.rumor_recv[:rumors])
            else:
                recv = s.total_received
            live = ((recv < target_count)
                    & (s.tick < max_steps) & (s.tick < until))
            if check_in_flight:
                # In-flight term (an O(d*n) emptiness test per window, not
                # per tick): exit the device loop the moment the wave dies
                # instead of spinning empty windows until the bounded-call
                # budget lets the host notice -- parity with the event
                # engine's cond (event.make_run_to_coverage_fn).
                live = live & (in_flight(s) > 0)
            return live

    def run_window(s: SimState, base_key):
        # One window per iteration keeps the predicate check off the
        # per-tick critical path.
        s = jax.lax.fori_loop(0, window, lambda _, x: step(x, base_key), s)
        if heal is not None:
            s = heal(s, base_key)
        return s

    if telemetry:
        from gossip_simulator_tpu.utils import telemetry as telem

        sir = cfg.protocol == "sir"
        spatial = telem.spatial_spec(cfg)

        @functools.partial(jax.jit, donate_argnums=(0, 4))
        def run_fn_t(st: SimState, base_key: jax.Array,
                     target_count: jax.Array, until: jax.Array,
                     hist: telem.History):
            def cond(carry):
                s, _ = carry
                return cond_live(s, target_count, until)

            def body(carry):
                s, h = carry
                s = run_window(s, base_key)
                with jax.named_scope(POLL):
                    row = telem.gossip_probe(
                        s, sir, rumors=rumors if multi else 0)
                    return s, telem.record_window(h, row, st=s,
                                                  spec=spatial)

            return jax.lax.while_loop(cond, body, (st, hist))

        return run_fn_t

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_fn(st: SimState, base_key: jax.Array, target_count: jax.Array,
               until: jax.Array) -> SimState:
        def cond(s: SimState):
            return cond_live(s, target_count, until)

        return jax.lax.while_loop(cond, lambda s: run_window(s, base_key), st)

    return run_fn
