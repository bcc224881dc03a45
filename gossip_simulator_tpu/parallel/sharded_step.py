"""SPMD step functions over the node mesh (shard_map + all_to_all + psum).

Each device owns a contiguous slice of the node axis; the per-tick physics is
the SAME `tick_core` the single-chip backend uses, the outgoing wave is
routed with one all_to_all (parallel/exchange.py), and the global counters /
termination predicate are psums -- the TPU-native equivalent of the
reference's shared `GlobalView` + atomics (simulator.go:24-31).

Layout (S shards, n = S * n_local):
    received/crashed/removed/friend_cnt: [n]      -> P("nodes")
    friends:                             [n, k]   -> P("nodes", None)
    pending:                  [S * d * n_local]   -> P("nodes")
    rebroadcast:                         [d, n]   -> P(None, "nodes")
    tick / totals:                       scalars  -> replicated
Global node id of local row r on shard s: s * n_local + r.  Each shard's
slice of `pending` is its own flat delay ring (epidemic.ring_row: slot t
at [t * n_local, (t + 1) * n_local)), so the global array is the shards'
rings side by side, not a [d, n] ring; snapshots convert
(ShardedStepper.state_pytree).

Named scopes (metadata only, as in the single-device engine): COMBINE,
EMIT, DELIVER and POLL as epidemic.py has them, and ROUTE around each
all_to_all exchange with its pack and unpack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import epidemic, graphs, overlay
from gossip_simulator_tpu.models import state as state_mod
from gossip_simulator_tpu.models.state import (OverlayState, SimState,
                                               msg64_add)
from gossip_simulator_tpu.ops import select
from gossip_simulator_tpu.ops.mailbox import deliver
from gossip_simulator_tpu.parallel import exchange
from gossip_simulator_tpu.parallel.mesh import AXIS, shard_size
from gossip_simulator_tpu.utils import rng as _rng

I32 = jnp.int32


def sim_state_specs(cfg: Config) -> SimState:
    # down_since is node-sharded only when the fault machinery allocates
    # the full axis (Config.faults_enabled); the fault-free 1-element
    # placeholder is replicated.
    return SimState(
        received=P(AXIS), crashed=P(AXIS), removed=P(AXIS),
        friends=P(AXIS, None), friend_cnt=P(AXIS),
        pending=P(AXIS), rebroadcast=P(None, AXIS),
        tick=P(), total_message=P(), total_received=P(), total_crashed=P(),
        exchange_overflow=P(),
        down_since=P(AXIS) if cfg.faults_enabled else P(),
        scen_crashed=P(), scen_recovered=P(), part_dropped=P(),
        heal_repaired=P(),
        # Multi-rumor rides the event engine only on meshes (config
        # rejects ring+multi at S > 1), so these stay the 1-element
        # replicated placeholders.
        pending_rumors=P(), rumor_words=P(), rumor_recv=P(),
        rumor_done=P(),
        # Per-shard exchange counters stack to (S, S+2); the 1x1
        # off-path placeholder splits the same way to (S, 1).
        exch_counts=P(AXIS, None),
    )


def overlay_state_specs() -> OverlayState:
    # Spill buffers are per-shard (each shard spills only what ITS routed
    # delivery overflowed; the hook path never fills them -- overlay.py's
    # pass-through keeps them empty, so the axis-sharded spec just splits
    # constant -1 arrays).
    return OverlayState(
        friends=P(AXIS, None), friend_cnt=P(AXIS),
        mk_dst=P(None, AXIS), bk_dst=P(None, AXIS), boot_dst=P(AXIS),
        mk_spill=P(None, None), bk_spill=P(None, None),
        round=P(), makeups=P(), breakups=P(),
        win_makeups=P(), win_breakups=P(), mailbox_dropped=P(),
    )


def _shard_map(mesh, fn, in_specs, out_specs):
    from gossip_simulator_tpu.parallel.mesh import shard_map

    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


# --------------------------------------------------------------------------
# Epidemic phase
# --------------------------------------------------------------------------

def _deposit_routed(cfg: Config, n_local: int, n_shards: int, pending,
                    dst_global, slots, valid, cap: int, exch=None):
    """Route (dst, ring-slot) messages to their owning shards and scatter
    into the local pending ring.  Returns (pending, local overflow).
    `cap` is the per-destination-shard buffer size (exchange.epidemic_cap of
    the wave's row count x row width).  `exch` non-None (the spatial
    panels' exch_counts leaf) accumulates the route's traffic and a 3rd
    value returns the updated leaf."""
    d = epidemic.ring_depth(cfg)
    with jax.named_scope(epidemic.ROUTE):
        dest_shard = jnp.where(valid, dst_global // n_local, n_shards)
        dst_local = jnp.where(valid, dst_global % n_local, 0)
        packed = jnp.where(valid,
                           exchange.pack_dst_slot(dst_local, slots, d), -1)
        out = exchange.route_one(packed, dest_shard, valid, n_shards, cap,
                                 traffic=exch)
        (recv, overflow), exch = out[:2], (out[2] if exch is not None
                                           else None)
        rvalid = recv >= 0
        rdst, rslot = exchange.unpack_dst_slot(jnp.maximum(recv, 0), d)
    with jax.named_scope(epidemic.DELIVER):
        pending = epidemic.deposit_local(pending, rdst, rslot, rvalid,
                                         n=n_local,
                                         kernel=cfg.deliver_kernel_resolved)
    if exch is None:
        return pending, overflow
    return pending, overflow, exch


def _route_stage_si(cfg: Config, n_local: int, n_shards: int, dst_global,
                    slots, valid, cap: int, pstage, exch=None):
    """Pipelined twin of _deposit_routed's route half (-exchange-pipeline
    double): the same pack/route/unpack, but the deposit arguments come
    back as the next staged drain instead of being scattered -- the
    caller deposits the barrier-threaded PREVIOUS stage while this
    chunk's all_to_all is in flight.  Deferring the deposit is
    trivially bit-identical here: nothing in the chunk loop reads
    `pending` (the chunk's rows come from the tick's select.chunk_plan),
    and deposits replay in the serial FIFO order.  Returns
    (stage_new, overflow, pstage_threaded[, exch])."""
    d = epidemic.ring_depth(cfg)
    with jax.named_scope(epidemic.ROUTE):
        dest_shard = jnp.where(valid, dst_global // n_local, n_shards)
        dst_local = jnp.where(valid, dst_global % n_local, 0)
        packed = jnp.where(valid,
                           exchange.pack_dst_slot(dst_local, slots, d), -1)
        out = exchange.route_multi_pipelined(
            (packed,), dest_shard, valid, n_shards, cap, pstage,
            traffic=exch)
        ((recv,), overflow, pstage), exch = out[:3], (
            out[3] if exch is not None else None)
        rvalid = recv >= 0
        rdst, rslot = exchange.unpack_dst_slot(jnp.maximum(recv, 0), d)
    if exch is None:
        return (rdst, rslot, rvalid), overflow, pstage
    return (rdst, rslot, rvalid), overflow, pstage, exch


def _flush_deposit(cfg: Config, n_local: int, pending, stage):
    """Apply a staged deposit (the deferred half of _route_stage_si)."""
    rdst, rslot, rvalid = stage
    with jax.named_scope(epidemic.DELIVER):
        return epidemic.deposit_local(pending, rdst, rslot, rvalid,
                                      n=n_local,
                                      kernel=cfg.deliver_kernel_resolved)


def _empty_deposit_stage(n_lanes: int):
    """All-invalid staged deposit: scattering it is a no-op, seeds the
    pipeline's prologue."""
    z = jnp.zeros((n_lanes,), I32)
    return (z, z, jnp.zeros((n_lanes,), bool))


def make_sharded_tick(cfg: Config, mesh):
    """Per-tick transition as a shard_map body (composable into loops)."""
    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)

    track_part = cfg.scenario_resolved.has_partitions
    # Exchange pipelining (-exchange-pipeline double): the compact chunk
    # loop defers each chunk's pending-ring deposit one chunk behind its
    # all_to_all (see _route_stage_si); the dense path's single route
    # per tick has no loop to pipeline and stays serial.
    pipe = exchange.pipeline_enabled(cfg, s)
    # Spatial panels: the exch_counts leaf rides the ovf carry position
    # as a pair (exchange.ovf_split) through the chunk loops.
    spatial = cfg.telemetry_spatial_enabled and s > 1

    def tick_shard(st: SimState, base_key: jax.Array) -> SimState:
        shard = jax.lax.axis_index(AXIS)
        gid0 = shard * n_local
        with jax.named_scope(epidemic.COMBINE):
            # Scenario faults draw on (tick, GLOBAL-id) keys -- shard-count
            # invariant, unlike the shard-folded step keys below -- so a
            # scenario trajectory's crash/recovery schedule is identical on
            # any mesh (and across a reshard-resume).
            st, dsc, dsr = epidemic.apply_fault_window(
                cfg, st, gid0 + jnp.arange(n_local, dtype=I32), base_key)
            keys = epidemic.tick_keys(base_key, st.tick, shard)
            stp, senders, dslot, (dm, dr, dc) = epidemic.tick_core(
                cfg, st, keys)
        width = stp.friends.shape[1]
        zblk = jnp.zeros((), I32)
        ovf0 = exchange.ovf_join(jnp.zeros((), I32),
                                 st.exch_counts if spatial else None)

        if cfg.compact_resolved:
            # The chunk loop is DELIVER, with EMIT (the gathers) and ROUTE
            # (the exchanges) inside it, as in epidemic.make_tick_fn.
            # Compacted wave: only sender rows reach the RNG/sort/all_to_all.
            # Chunk count is agreed across shards (pmax) so every shard
            # executes the same number of collectives.
            ccap = epidemic.compact_chunk_cap(cfg, n_local)
            # Per-chunk route cap: destination-uniform graphs size the
            # wire from the per-pair high-water mark (round 6 --
            # exchange.chernoff_cap, same gate as the event engine's
            # wire_cap; counted overflow, never silent); others keep the
            # round-1 rule -- never below the dense path's cap (so any
            # wave dense delivers losslessly, compact does too, skew
            # included), bounded above by a chunk's absolute max emission.
            if cfg.graph in ("kout", "erdos"):
                rcap = exchange.chernoff_cap(ccap * width, s)
            else:
                rcap = min(exchange.epidemic_cap(n_local, width, s),
                           ccap * width)
            with jax.named_scope(epidemic.EMIT):
                plan = select.chunk_plan(senders, ccap)
            with jax.named_scope(epidemic.DELIVER):
                count = jax.lax.pmax(plan.count, AXIS)
                chunks = (count + ccap - 1) // ccap

            def gather(j, cursor, **kw):
                with jax.named_scope(epidemic.EMIT):
                    idx, cursor = select.plan_chunk(plan, j, cursor)
                    return (*epidemic.compact_gather(
                        cfg, stp.friends, stp.friend_cnt, dslot,
                        keys["delay"], keys["drop"], st.tick, idx, **kw),
                        cursor)

            if pipe:
                # Pipelined chunk loop (-exchange-pipeline double): chunk
                # j's deposit flushes behind chunk j+1's in-flight
                # collective (_route_stage_si's identity note); the last
                # stage flushes after the loop.
                def body_pipe(j, carry):
                    pending, cursor, ovf, blk, pend = carry
                    oacc, exch = exchange.ovf_split(ovf)
                    (dstg, slots, valid, b2, cursor) = gather(
                        j, cursor, **(dict(gid0=gid0) if track_part else {}))
                    out = _route_stage_si(
                        cfg, n_local, s, dstg, slots, valid, rcap, pend,
                        exch=exch)
                    (nstage, o, pthr), exch = out[:3], (
                        out[3] if exch is not None else None)
                    pending = _flush_deposit(cfg, n_local, pending, pthr)
                    return (pending, cursor,
                            exchange.ovf_join(oacc + o, exch),
                            blk + (b2 if track_part else 0), nstage)

                with jax.named_scope(epidemic.DELIVER):
                    pending, _, ovf, blk, pend = jax.lax.fori_loop(
                        0, chunks, body_pipe,
                        (stp.pending, zblk, ovf0, zblk,
                         _empty_deposit_stage(s * rcap)))
                pending = _flush_deposit(cfg, n_local, pending, pend)
            elif track_part:
                def body_p(j, carry):
                    pending, cursor, ovf, blk = carry
                    oacc, exch = exchange.ovf_split(ovf)
                    (dstg, slots, valid, b2, cursor) = gather(
                        j, cursor, gid0=gid0)
                    out = _deposit_routed(cfg, n_local, s, pending,
                                          dstg, slots, valid, rcap,
                                          exch=exch)
                    (pending, o), exch = out[:2], (
                        out[2] if exch is not None else None)
                    return (pending, cursor,
                            exchange.ovf_join(oacc + o, exch), blk + b2)

                with jax.named_scope(epidemic.DELIVER):
                    pending, _, ovf, blk = jax.lax.fori_loop(
                        0, chunks, body_p,
                        (stp.pending, zblk, ovf0, zblk))
            else:
                def body(j, carry):
                    pending, cursor, ovf = carry
                    oacc, exch = exchange.ovf_split(ovf)
                    (dstg, slots, valid, _b, cursor) = gather(j, cursor)
                    out = _deposit_routed(cfg, n_local, s, pending,
                                          dstg, slots, valid, rcap,
                                          exch=exch)
                    (pending, o), exch = out[:2], (
                        out[2] if exch is not None else None)
                    return (pending, cursor,
                            exchange.ovf_join(oacc + o, exch))

                with jax.named_scope(epidemic.DELIVER):
                    pending, _, ovf = jax.lax.fori_loop(
                        0, chunks, body,
                        (stp.pending, zblk, ovf0))
                blk = zblk
        else:
            with jax.named_scope(epidemic.EMIT):
                dst, slots, valid, blk = epidemic.edges_from_senders(
                    cfg, stp.friends, stp.friend_cnt, senders, dslot,
                    keys["drop"], tick=st.tick, gid0=gid0)
            exch = st.exch_counts if spatial else None
            out = _deposit_routed(
                cfg, n_local, s, stp.pending, dst, slots, valid,
                exchange.epidemic_cap(n_local, width, s), exch=exch)
            (pending, ovf), exch = out[:2], (
                out[2] if exch is not None else None)
            ovf = exchange.ovf_join(ovf, exch)
        with jax.named_scope(epidemic.COMBINE):
            # Traffic rows are per-shard gauges: split them off BEFORE the
            # psum.
            ovf, exch = exchange.ovf_split(ovf)
            dm, dr, dc, ovf = jax.lax.psum((dm, dr, dc, ovf), AXIS)
            # NOTE: no lax.cond empty-slot skip here -- see the NOTE in
            # epidemic.make_tick_fn (cond + dynamic fori).
            # The psum'd per-tick delta stays int32 (bounded by the
            # delay-ring capacity); the carry into the 64-bit pair is
            # replicated per shard.
            stp = stp._replace(
                pending=pending,
                total_message=msg64_add(stp.total_message, dm),
                total_received=stp.total_received + dr,
                total_crashed=stp.total_crashed + dc,
                exchange_overflow=stp.exchange_overflow + ovf)
            if exch is not None:
                stp = stp._replace(exch_counts=exch)
            if cfg.scenario_resolved.active:
                dsc, dsr, blk = jax.lax.psum(
                    (jnp.asarray(dsc, I32), jnp.asarray(dsr, I32),
                     jnp.asarray(blk, I32)), AXIS)
                stp = stp._replace(
                    scen_crashed=stp.scen_crashed + dsc,
                    scen_recovered=stp.scen_recovered + dsr,
                    part_dropped=stp.part_dropped + blk)
        return stp

    return tick_shard


def make_sharded_pushpull(cfg: Config, mesh):
    """Push-pull anti-entropy round per shard: push deliveries and pull
    request/response both ride the same all_to_all routing."""
    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    f = cfg.fanout
    drop_p = epidemic.p_eff(cfg, cfg.droprate)
    crash_p = epidemic.p_eff(cfg, cfg.crashrate)
    cap = exchange.epidemic_cap(n_local, f, s)
    spatial = cfg.telemetry_spatial_enabled and s > 1

    def round_shard(st: SimState, base_key: jax.Array) -> SimState:
        shard = jax.lax.axis_index(AXIS)
        skey = jax.random.fold_in(base_key, shard)
        k1 = _rng.tick_key(skey, st.tick, _rng.OP_BOOTSTRAP)
        k2 = _rng.tick_key(skey, st.tick, _rng.OP_PULL)
        kd1 = _rng.tick_key(skey, st.tick, _rng.OP_DROP)
        kd2 = _rng.tick_key(skey, st.tick, _rng.OP_DELAY)
        kc = _rng.tick_key(skey, st.tick, _rng.OP_CRASH)

        live = ~st.crashed
        inf = st.received & live
        sus = ~st.received & live
        gids = shard * n_local + jnp.arange(n_local, dtype=I32)

        # --- push ---------------------------------------------------------
        peers = jax.random.randint(k1, (n_local, f), 0, cfg.n, dtype=I32)
        kept = ~_rng.bernoulli(kd1, drop_p, (n_local, f))
        edge = (inf[:, None] & kept).reshape(-1)
        dstg = peers.reshape(-1)
        exch = st.exch_counts if spatial else None
        out = exchange.route_one(
            jnp.where(edge, dstg % n_local, -1),
            jnp.where(edge, dstg // n_local, s), edge, s, cap,
            traffic=exch)
        (recv, ovf1), exch = out[:2], (
            out[2] if exch is not None else None)
        rvalid = recv >= 0
        arriving = jnp.zeros((n_local,), I32).at[
            jnp.where(rvalid, recv, n_local)].add(1, mode="drop")
        counted = jnp.where(live, arriving, 0)
        dm = counted.sum(dtype=I32)
        if crash_p > 0.0:
            pc = 1.0 - jnp.power(1.0 - crash_p, counted.astype(jnp.float32))
            new_crash = (jax.random.uniform(kc, (n_local,)) < pc) & (counted > 0)
        else:
            new_crash = jnp.zeros((n_local,), bool)
        crashed = st.crashed | new_crash
        dc = new_crash.sum(dtype=I32)
        newly_push = (counted > 0) & ~crashed & ~st.received

        # --- pull: request (target, requester) then response (hits) --------
        peers2 = jax.random.randint(k2, (n_local, f), 0, cfg.n, dtype=I32)
        kept2 = ~_rng.bernoulli(kd2, drop_p, (n_local, f))
        req = (sus[:, None] & kept2 & ~crashed[:, None]).reshape(-1)
        tgt = peers2.reshape(-1)
        dest = jnp.where(req, tgt // n_local, s)
        # Target row and requester id share one sort + one all_to_all.
        out = exchange.route_multi(
            (jnp.where(req, tgt % n_local, -1),
             jnp.where(req, jnp.broadcast_to(
                 gids[:, None], (n_local, f)).reshape(-1), -1)),
            dest, req, s, cap, traffic=exch)
        ((rtgt, rreq), ovf2), exch = out[:2], (
            out[2] if exch is not None else None)
        tvalid = rtgt >= 0
        tgt_idx = jnp.where(tvalid, rtgt, 0)
        # A live peer answers any request (counted); an infected live peer's
        # answer infects.  One packed gather answers both (pre-round crashed,
        # like the single-device round; see epidemic.packed_peer_state).
        peer_state = epidemic.packed_peer_state(st.received,
                                                st.crashed)[tgt_idx]
        answered = tvalid & (peer_state < 2)
        dm = dm + answered.sum(dtype=I32)
        hit = answered & (peer_state == 1)
        out = exchange.route_one(
            jnp.where(hit, rreq % n_local, -1),
            jnp.where(hit, rreq // n_local, s), hit, s, cap,
            traffic=exch)
        (back, ovf4), exch = out[:2], (
            out[2] if exch is not None else None)
        bvalid = back >= 0
        pull_hit = jnp.zeros((n_local,), bool).at[
            jnp.where(bvalid, back, n_local)].max(bvalid, mode="drop")

        newly = (newly_push | pull_hit) & ~crashed & ~st.received
        received = st.received | newly
        dr = newly.sum(dtype=I32)
        dm, dr, dc = jax.lax.psum((dm, dr, dc), AXIS)
        ovf = jax.lax.psum(ovf1 + ovf2 + ovf4, AXIS)
        stp = st._replace(
            received=received, crashed=crashed, tick=st.tick + 1,
            total_message=msg64_add(st.total_message, dm),
            total_received=st.total_received + dr,
            total_crashed=st.total_crashed + dc,
            exchange_overflow=st.exchange_overflow + ovf)
        if exch is not None:
            stp = stp._replace(exch_counts=exch)
        return stp

    return round_shard


def make_sharded_step(cfg: Config, mesh):
    if cfg.protocol == "pushpull":
        return make_sharded_pushpull(cfg, mesh)
    return make_sharded_tick(cfg, mesh)


def make_sharded_heal(cfg: Config, mesh):
    """Sharded ring-engine overlay healing (shard_map body; None when
    -overlay-heal is off).  The failure detector's verdicts are per-shard
    (crash clock and crashed bits live with the rows); ONE bool-per-node
    all_gather publishes them so every shard can condemn its remote
    friends, then the repaired-edge re-sends ride the normal all_to_all
    route.  Heal draws are (tick, GLOBAL-id)-keyed (scenario.heal_and_
    wave), so the repair schedule matches the single-device engine
    bit-for-bit."""
    if not cfg.overlay_heal_resolved:
        return None
    from gossip_simulator_tpu import scenario as _scen

    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    detect = cfg.heal_detect_ms
    d = epidemic.ring_depth(cfg)
    spatial = cfg.telemetry_spatial_enabled and s > 1

    def heal_shard(st: SimState, base_key: jax.Array) -> SimState:
        shard = jax.lax.axis_index(AXIS)
        gids = shard * n_local + jnp.arange(n_local, dtype=I32)
        rows = jnp.arange(n_local, dtype=I32)
        k = st.friends.shape[1]
        detected = _scen.detect_dead(st.crashed, st.down_since, st.tick,
                                     detect)
        healer_ok = ~st.crashed
        sender_inf = st.received & ~st.crashed & ~st.removed
        bits_global = jax.lax.all_gather(
            _scen.heal_peer_bits(detected, sender_inf), AXIS, tiled=True)
        friends, resend, pull, delay, clear, rep, blk = _scen.heal_and_wave(
            cfg, st.friends, st.friend_cnt, bits_global, healer_ok,
            sender_inf, _scen.rejoined_mask(st.down_since), gids, st.tick,
            base_key)
        if cfg.effective_time_mode == "rounds":
            dslot = jnp.broadcast_to((st.tick + 1) % d,
                                     (n_local,)).astype(I32)
        else:
            dslot = ((st.tick + delay) % d).astype(I32)
        dst = jnp.where(resend, friends, -1).reshape(-1)
        slots = jnp.broadcast_to(dslot[:, None], (n_local, k)).reshape(-1)
        exch = st.exch_counts if spatial else None
        out = _deposit_routed(
            cfg, n_local, s, st.pending, dst, slots, resend.reshape(-1),
            exchange.epidemic_cap(n_local, k, s), exch=exch)
        (pending, ovf), exch = out[:2], (
            out[2] if exch is not None else None)
        # Rejoin pull responses deliver to the puller's OWN row -- always
        # shard-local, so they skip the route.
        pdst = jnp.broadcast_to(rows[:, None], (n_local, k)).reshape(-1)
        pending = epidemic.deposit_local(pending, pdst, slots,
                                         pull.reshape(-1), n=n_local,
                                         kernel=cfg.deliver_kernel_resolved)
        rep, blk, ovf = jax.lax.psum(
            (rep, jnp.asarray(blk, I32), ovf), AXIS)
        stp = st._replace(
            friends=friends, pending=pending,
            down_since=jnp.where(clear, -1, st.down_since),
            heal_repaired=st.heal_repaired + rep,
            part_dropped=st.part_dropped + blk,
            exchange_overflow=st.exchange_overflow + ovf)
        if exch is not None:
            stp = stp._replace(exch_counts=exch)
        return stp

    return heal_shard


def make_sharded_seed(cfg: Config, mesh):
    """Uniform-random global sender; its broadcast is routed like any wave."""
    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    spatial = cfg.telemetry_spatial_enabled and s > 1

    def seed_shard(st: SimState, base_key: jax.Array) -> SimState:
        shard = jax.lax.axis_index(AXIS)
        ks = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_SEED_NODE)
        kd = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_DELAY)
        kp = _rng.tick_key(jax.random.fold_in(base_key, shard),
                           epidemic.SEED_TICK, _rng.OP_DROP)
        sender = jax.random.randint(ks, (), 0, cfg.n, dtype=I32)
        gids = shard * n_local + jnp.arange(n_local, dtype=I32)
        is_sender = gids == sender
        received, total_received = st.received, st.total_received
        if cfg.protocol == "pushpull" or not cfg.compat_reference:
            received = received | is_sender
            total_received = total_received + 1  # replicated: +1 everywhere
        if cfg.protocol == "pushpull":
            return st._replace(received=received,
                               total_received=total_received)
        dslot = epidemic.row_slot(cfg, kd, st.tick,
                                  jnp.arange(n_local, dtype=I32))
        # Only the sender's row emits: gather it (row-keyed draws, so the
        # same edges as a dense pass over every row) and route its k
        # lanes.  A dense [n_local, k] wave did not fit a chip's HBM at
        # 25M rows per shard.  Shards without the sender gather fill row
        # n_local, whose edges are invalid.
        k = st.friends.shape[1]
        row = sender - shard * n_local
        row = jnp.where((row >= 0) & (row < n_local), row, n_local)
        dst, slots, valid, blk = epidemic.compact_gather(
            cfg, st.friends, st.friend_cnt, dslot, kd, kp, st.tick,
            row[None], gid0=shard * n_local)
        if cfg.scenario_resolved.has_partitions:
            st = st._replace(part_dropped=st.part_dropped
                             + jax.lax.psum(blk, AXIS))
        exch = st.exch_counts if spatial else None
        out = _deposit_routed(cfg, n_local, s, st.pending, dst, slots, valid,
                              k, exch=exch)
        (pending, ovf), exch = out[:2], (
            out[2] if exch is not None else None)
        rb = st.rebroadcast
        if cfg.protocol == "sir":
            kr = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_REMOVE)
            keep = ~_rng.bernoulli(kr, epidemic.p_eff(cfg, cfg.removal_rate),
                                   ())
            rb = rb.at[dslot, jnp.arange(n_local, dtype=I32)].max(
                is_sender & keep)
        ovf = jax.lax.psum(ovf, AXIS)
        stp = st._replace(received=received, total_received=total_received,
                          pending=pending, rebroadcast=rb,
                          exchange_overflow=st.exchange_overflow + ovf)
        if exch is not None:
            stp = stp._replace(exch_counts=exch)
        return stp

    return seed_shard


def make_sharded_init(cfg: Config, mesh):
    """Build the sharded SimState for a static graph directly on the mesh
    (each shard generates its own row slice; the row-keyed generators make
    this bit-identical to slicing a single-device generation)."""
    n_local = shard_size(cfg.n, mesh)
    n_shards = mesh.shape[AXIS]

    def init_shard():
        shard = jax.lax.axis_index(AXIS)
        key = graphs.graph_key(cfg)
        friends, cnt = graphs.generate(cfg, key, row0=shard * n_local,
                                       rows=n_local)
        return epidemic.init_state(cfg, friends, cnt, n_local=n_local,
                                   n_shards=n_shards)

    specs = sim_state_specs(cfg)
    fn = _shard_map(mesh, init_shard, in_specs=(), out_specs=specs)
    return jax.jit(fn)


# --------------------------------------------------------------------------
# Overlay phase (dynamic graph), sharded
# --------------------------------------------------------------------------

def make_sharded_overlay_round(cfg: Config, mesh):
    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    cap = cfg.mailbox_cap_for(n_local)
    # Membership messages per node per round <= em/eb; same capacity logic as
    # the epidemic wave.
    route_cap = exchange.epidemic_cap(n_local, cap + 2, s)

    def routed_deliver(src, dst, valid, mbox_cap):
        """Route (src payload) to dst's shard, then local mailbox deliver.
        One route_multi call: src and the local-destination payload share
        the sort and the all_to_all."""
        dest = jnp.where(valid, dst // n_local, s)
        dstl = jnp.where(valid, dst % n_local, 0)
        (rsrc, rdst), ovf = exchange.route_multi(
            (jnp.where(valid, src, -1), jnp.where(valid, dstl, -1)),
            dest, valid, s, route_cap)
        rvalid = rsrc >= 0
        mbox, _, dropped = deliver(rsrc, jnp.where(rvalid, rdst, 0), rvalid,
                                   n_local, mbox_cap,
                                   kernel=cfg.deliver_kernel_resolved)
        return mbox, dropped + ovf

    def ids_fn():
        shard = jax.lax.axis_index(AXIS)
        return shard * n_local + jnp.arange(n_local, dtype=I32)

    def sum_fn(x):
        return jax.lax.psum(x, AXIS)

    body = overlay.make_round_fn(cfg, deliver_fn=routed_deliver,
                                 ids_fn=ids_fn, sum_fn=sum_fn,
                                 n_rows=n_local)

    def round_shard(st: OverlayState, base_key: jax.Array) -> OverlayState:
        # Decorrelate per-shard draws inside the round body by folding the
        # shard id into the key stream.
        shard = jax.lax.axis_index(AXIS)
        return body(st, jax.random.fold_in(base_key, shard))

    return round_shard


def make_sharded_overlay_init(cfg: Config, mesh):
    n_local = shard_size(cfg.n, mesh)

    def init_shard():
        return overlay.init_state(cfg, n_local=n_local)

    return jax.jit(_shard_map(mesh, init_shard, in_specs=(),
                              out_specs=overlay_state_specs()))


# --------------------------------------------------------------------------
# Jitted drivers (loops live inside one shard_map region)
# --------------------------------------------------------------------------

def make_window_fn(cfg: Config, mesh, window: int):
    step = make_sharded_step(cfg, mesh)
    heal = make_sharded_heal(cfg, mesh)
    specs = sim_state_specs(cfg)

    def window_shard(st: SimState, base_key: jax.Array) -> SimState:
        st = jax.lax.fori_loop(0, window, lambda _, x: step(x, base_key), st)
        if heal is not None:
            st = heal(st, base_key)
        return st

    return jax.jit(_shard_map(mesh, window_shard, in_specs=(specs, P()),
                              out_specs=specs), donate_argnums=(0,))


def make_seed_fn(cfg: Config, mesh):
    specs = sim_state_specs(cfg)
    return jax.jit(_shard_map(mesh, make_sharded_seed(cfg, mesh),
                              in_specs=(specs, P()), out_specs=specs))


def make_overlay_round_fn(cfg: Config, mesh):
    specs = overlay_state_specs()
    return jax.jit(_shard_map(mesh, make_sharded_overlay_round(cfg, mesh),
                              in_specs=(specs, P()), out_specs=specs))


def make_run_to_coverage_fn(cfg: Config, mesh, telemetry: bool = False):
    """Bounded device-side while_loop (see epidemic.run_call_budget): the
    host re-enters until target/max_rounds/exhaustion.  With `telemetry`
    the loop carries the per-window History (utils/telemetry.py) inside
    shard_map with replicated specs -- the recorded totals are already
    psum-replicated by the step; the per-shard occupancy/removed probes
    reduce across shards so every shard writes identical rows."""
    step = make_sharded_step(cfg, mesh)
    heal = make_sharded_heal(cfg, mesh)
    specs = sim_state_specs(cfg)
    window = 1 if cfg.effective_time_mode == "rounds" else 10
    max_steps = cfg.max_rounds
    # Heal-on runs drop the early-death exit: healing can revive an empty
    # ring (see epidemic.make_run_to_coverage_fn).
    check_in_flight = (cfg.protocol != "pushpull"
                       and not cfg.overlay_heal_resolved)

    def cond_live(s, target_count, until):
        with jax.named_scope(epidemic.POLL):
            live = ((s.total_received < target_count)
                    & (s.tick < max_steps) & (s.tick < until))
            if check_in_flight:
                # psum of each shard's ring-occupied indicator
                # (replicated, so every shard agrees): exit at wave
                # death instead of spinning to the bounded-call budget
                # -- same term the sharded event engine's cond has
                # (event_sharded.make_run_to_coverage_fn).
                live = live & (jax.lax.psum(state_mod.in_flight(s),
                                            AXIS) > 0)
            return live

    def advance(s, base_key):
        s = jax.lax.fori_loop(0, window, lambda _, x: step(x, base_key), s)
        if heal is not None:
            s = heal(s, base_key)
        return s

    if telemetry:
        from gossip_simulator_tpu.utils import telemetry as telem

        sir = cfg.protocol == "sir"
        ihwm = exchange.inflight_hwm(cfg, mesh.shape[AXIS])
        spatial = telem.spatial_spec(cfg, int(mesh.shape[AXIS]))
        hspecs = telem.bundle_specs(spatial, P)

        @functools.partial(jax.jit, donate_argnums=(0, 4))
        def run_t(st: SimState, base_key, target_count, until, hist):
            def run_shard(st, base_key, target_count, until, hist):
                def cond(carry):
                    s, _ = carry
                    return cond_live(s, target_count, until)

                def body(carry):
                    s, h = carry
                    s = advance(s, base_key)
                    with jax.named_scope(epidemic.POLL):
                        row = telem.gossip_probe(
                            s, sir, psum=lambda x: jax.lax.psum(x, AXIS),
                            pmax=lambda x: jax.lax.pmax(x, AXIS),
                            inflight_hwm=ihwm)
                        return s, telem.record_window(
                            h, row, st=s, spec=spatial,
                            shard_index=jax.lax.axis_index(AXIS),
                            gather=lambda x: jax.lax.all_gather(x, AXIS),
                            psum=lambda x: jax.lax.psum(x, AXIS))

                return jax.lax.while_loop(cond, body, (st, hist))

            return _shard_map(
                mesh, run_shard,
                in_specs=(specs, P(), P(), P(), hspecs),
                out_specs=(specs, hspecs))(st, base_key, target_count,
                                           until, hist)

        return run_t

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(st: SimState, base_key: jax.Array, target_count: jax.Array,
            until: jax.Array) -> SimState:
        def run_shard(st, base_key, target_count, until):
            return jax.lax.while_loop(
                lambda s: cond_live(s, target_count, until),
                lambda s: advance(s, base_key), st)

        return _shard_map(mesh, run_shard, in_specs=(specs, P(), P(), P()),
                          out_specs=specs)(st, base_key, target_count, until)

    return run
