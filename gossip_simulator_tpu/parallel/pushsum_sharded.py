"""Mesh-sharded PushSum engine: numeric mass gossip across shards.

Same scaffolding as the sharded event engine (parallel/event_sharded.py):
each shard drains its own slot of the mail ring locally with the SUM
combine, and the emission routes (value, weight) mass shares to their
destination's owner shard over `lax.all_to_all` -- the mass limbs ride
exchange.route_multi as extra int32 columns next to the packed wire word,
exactly the multi-rumor word-column path.

Shard invariance is STRONGER here than for SI: the event engine shard-
folds its crash/drop/delay streams (trajectories differ by shard count,
distributionally matched), but pushsum draws only (tick, GLOBAL id)-keyed
delays off the UNFOLDED base key (models/pushsum.emit_shares) and its
deposits are integer adds, which commute -- so S=1 and S=8 produce
BIT-IDENTICAL mass states, the property tests/test_pushsum.py pins and
the reshard-resume acceptance criterion rides on.

Collective agreement: drain chunk counts are pmax-agreed; the
convergence count psums and the max relative error pmaxes, so the
replicated scalars (total_received, relerr_ppb, eps_tick) match every
shard.  Zero-loss accounting: route overflow -> exchange_overflow, slot
overflow -> mail_dropped, both psum'd -- either being nonzero means
destroyed mass, and the conservation tests assert both stay 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gossip_simulator_tpu import scenario as _scen
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import event, graphs, pushsum
from gossip_simulator_tpu.models.pushsum import LIMBS, PushSumState
from gossip_simulator_tpu.models.state import in_flight, msg64_add
from gossip_simulator_tpu.parallel import event_sharded, exchange
from gossip_simulator_tpu.parallel.mesh import AXIS, shard_size

I32 = jnp.int32


def pushsum_state_specs(cfg: Config) -> PushSumState:
    return PushSumState(
        flags=P(AXIS),
        friends=P(AXIS, None), friend_cnt=P(AXIS),
        mass=P(AXIS, None),
        mail_ids=P(AXIS), mail_mass=P(AXIS, None),
        mail_cnt=P(AXIS, None), sup_cnt=P(AXIS, None),
        tick=P(), total_message=P(), total_received=P(), total_crashed=P(),
        mail_dropped=P(), exchange_overflow=P(),
        down_since=P(AXIS) if cfg.faults_enabled else P(),
        scen_crashed=P(), scen_recovered=P(), part_dropped=P(),
        heal_repaired=P(),
        relerr_ppb=P(), eps_tick=P(),
        # Per-shard exchange counters stack to (S, S+2); the 1x1
        # off-path placeholder splits the same way to (S, 1).
        exch_counts=P(AXIS, None),
    )


def _shard_map(mesh, fn, in_specs, out_specs):
    from gossip_simulator_tpu.parallel.mesh import shard_map

    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def make_sharded_pushsum_init(cfg: Config, mesh):
    """Per-shard graph slice + pushsum state; the row-keyed graph
    generators and the gid-keyed mass hash make this bit-identical to
    slicing a single-device init."""
    n_local = shard_size(cfg.n, mesh)
    n_shards = mesh.shape[AXIS]

    def init_shard():
        shard = jax.lax.axis_index(AXIS)
        key = graphs.graph_key(cfg)
        friends, cnt = graphs.generate(cfg, key, row0=shard * n_local,
                                       rows=n_local)
        return pushsum.init_state(cfg, friends, cnt, gid0=shard * n_local,
                                  n_shards=n_shards)

    return jax.jit(_shard_map(mesh, init_shard, in_specs=(),
                              out_specs=pushsum_state_specs(cfg)))


def _mass_append(cfg: Config, n_local: int, mail, mailm, cnt, dropped,
                 payload, rows, wslot, valid):
    """Local ring append of packed entries + their mass rows (pushsum's
    slot geometry, not the event engine's)."""
    from gossip_simulator_tpu.ops.mailbox import ring_append

    dw = pushsum.ring_windows(cfg)
    cap = (mail.shape[0] - pushsum.ring_tail(cfg, n_local)) // dw
    (mail, mailm), cnt, dropped = ring_append(
        (mail, mailm), cnt, dropped, (payload, rows), wslot, valid, dw,
        cap, kernel=cfg.deliver_kernel_resolved)
    return mail, mailm, cnt, dropped


def _route_append_mass(cfg: Config, s: int, n_local: int, mail, mailm,
                       cnt, dropped, xovf, dst_global, wslot, off, valid,
                       rcap, share):
    """Route mass shares to their owner shards and append.  The 1-device
    mesh appends directly (the route is the identity there -- same
    DIRECT_SELF_APPEND argument as the event engine, and what makes the
    S=1 sharded ring bit-identical to the single-device one)."""
    b = pushsum.batch_ticks(cfg)
    dw = pushsum.ring_windows(cfg)
    if s == 1 and event_sharded.DIRECT_SELF_APPEND:
        mail, mailm, cnt, dropped = _mass_append(
            cfg, n_local, mail, mailm, cnt, dropped,
            dst_global * b + off, share, wslot, valid)
        return mail, mailm, cnt, dropped, xovf
    xo, exch = exchange.ovf_split(xovf)
    dest = jnp.where(valid, dst_global // n_local, s)
    wire = jnp.where(
        valid, (dst_global % n_local) * (dw * b) + wslot * b + off, -1)
    payloads = (wire,) + tuple(share[:, i] for i in range(share.shape[1]))
    out = exchange.route_multi(payloads, dest, valid, s, rcap,
                               traffic=exch)
    (recvs, ovf), exch = out[:2], (out[2] if exch is not None else None)
    recv = recvs[0]
    rvalid = recv >= 0
    r = jnp.maximum(recv, 0)
    rdstl = r // (dw * b)
    rw = (r // b) % dw
    roff = r % b
    # Empty wire slots carry the -1 fill in every column; gate their
    # garbage mass out (a stray add would CREATE mass).
    rrows = jnp.where(rvalid[:, None], jnp.stack(recvs[1:], axis=1), 0)
    mail, mailm, cnt, dropped = _mass_append(
        cfg, n_local, mail, mailm, cnt, dropped, rdstl * b + roff, rrows,
        rw, rvalid)
    return mail, mailm, cnt, dropped, exchange.ovf_join(xo + ovf, exch)


def make_sharded_pushsum_step(cfg: Config, mesh):
    """One B-tick window transition per shard (shard_map body)."""
    from gossip_simulator_tpu.ops.mailbox import deposit_sum

    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    b = pushsum.batch_ticks(cfg)
    dw = pushsum.ring_windows(cfg)
    cap = pushsum.slot_cap(cfg, n_local)
    ccap = pushsum.drain_chunk(cfg, n_local)
    dim = cfg.pushsum_dim
    C = pushsum.mass_cols(cfg)
    eps = float(cfg.pushsum_eps)
    tgt = pushsum.eps_target(cfg)
    dkern = cfg.deliver_kernel_resolved
    scen = cfg.scenario_resolved
    k = cfg.graph_width
    if n_local * dw * b >= 2 ** 31:
        raise ValueError(
            f"wire packing overflow: n_local ({n_local}) * dw ({dw}) * B "
            f"({b}) must stay below 2^31; use more shards")
    # Every live node emits <= k lanes per window; the per-pair route
    # buffer uses the event-heal zero-loss-leaning bound (overflow is
    # counted, and the conservation tests assert it stays 0).
    rcap = min(exchange.epidemic_cap(n_local, k, s), n_local * k)
    spatial = cfg.telemetry_spatial_enabled and s > 1

    def step_shard(st: PushSumState, base_key: jax.Array) -> PushSumState:
        shard = jax.lax.axis_index(AXIS)
        gids = shard * n_local + jnp.arange(n_local, dtype=I32)
        # Scenario faults: (window, GLOBAL-id)-keyed off the UNFOLDED
        # base key -- identical schedule at any shard count.
        flags, down, dsc, dsr = event.apply_fault_window_flags(
            cfg, st.flags, st.down_since, st.tick, gids, base_key, b)
        slot = (st.tick // b) % dw
        m = st.mail_cnt[0, slot]
        # pmax-agreed chunk count: every shard runs the same loop trip
        # count (shards with fewer entries deposit masked no-ops).
        chunks = (jax.lax.pmax(m, AXIS) + ccap - 1) // ccap

        def body(j, acc):
            off0 = slot * cap + j * ccap
            ent = jax.lax.dynamic_slice(st.mail_ids, (off0,), (ccap,))
            rows = jax.lax.dynamic_slice(
                st.mail_mass, (off0, 0), (ccap, C))
            ok = j * ccap + jnp.arange(ccap, dtype=I32) < m
            return deposit_sum(acc, ent // b, rows, ok, kernel=dkern)

        mass = jax.lax.fori_loop(0, chunks, body, st.mass)
        m3 = pushsum._normalize(mass.reshape(n_local, dim + 1, LIMBS))
        crashed = (flags & event.CRASHED) > 0
        rel, rep = pushsum.metric_rel(cfg, m3, crashed)
        conv = rel <= jnp.float32(eps)
        flags = jnp.where(conv, flags | event.RECEIVED,
                          flags & ~event.RECEIVED)
        total_received = jax.lax.psum(conv.sum(dtype=I32), AXIS)
        maxrel = jax.lax.pmax(rep.max(), AXIS)
        new_tick = st.tick + b
        # Eps-band population criterion, same as the single-device step
        # (see the model docstring: the global max need never enter the
        # band on a kout overlay, the coverage target is the contract).
        eps_tick = jnp.where(
            (st.eps_tick < 0) & (total_received >= tgt),
            new_tick, st.eps_tick)
        new_m3, share, dst, wslot, off, lane_valid, blk = \
            pushsum.emit_shares(cfg, m3, crashed, st.friends,
                                st.friend_cnt, st.tick, gids, base_key)
        ddrop = jnp.zeros((), I32)
        xv0 = exchange.ovf_join(jnp.zeros((), I32),
                                st.exch_counts if spatial else None)
        mail, mailm, cnt, ddrop, dxovf = _route_append_mass(
            cfg, s, n_local, st.mail_ids, st.mail_mass, st.mail_cnt,
            ddrop, xv0, dst, wslot, off, lane_valid, rcap,
            share)
        dxovf, exch_new = exchange.ovf_split(dxovf)
        cnt = cnt.at[0, slot].set(0)
        dm = lane_valid.sum(dtype=I32)
        if scen.has_faults:
            dm, ddrop, dxovf, blk, dsc, dsr = jax.lax.psum(
                (dm, ddrop, dxovf, blk, dsc, dsr), AXIS)
        else:
            dm, ddrop, dxovf, blk = jax.lax.psum(
                (dm, ddrop, dxovf, blk), AXIS)
        if exch_new is not None:
            st = st._replace(exch_counts=exch_new)
        return st._replace(
            flags=flags, down_since=down,
            mass=new_m3.reshape(n_local, C),
            mail_ids=mail, mail_mass=mailm, mail_cnt=cnt,
            mail_dropped=st.mail_dropped + ddrop,
            exchange_overflow=st.exchange_overflow + dxovf,
            tick=new_tick,
            total_message=msg64_add(st.total_message, dm),
            total_received=total_received,
            scen_crashed=st.scen_crashed + dsc,
            scen_recovered=st.scen_recovered + dsr,
            part_dropped=st.part_dropped + blk,
            relerr_ppb=(maxrel * jnp.float32(1e9)).astype(I32),
            eps_tick=eps_tick)

    return step_shard


def make_sharded_pushsum_heal(cfg: Config, mesh):
    """Per-shard rejoin bookkeeping (None when off).  Deliberately no
    edge repair and no waves -- see models/pushsum.make_heal_fn for why
    rewiring strands rebooted nodes' estimates; the shard-local marker
    clear needs no collective, so S=1..S=8 trajectories stay identical
    by construction."""
    if not cfg.overlay_heal_resolved:
        return None

    def heal_shard(st: PushSumState, base_key: jax.Array) -> PushSumState:
        clear = _scen.rejoined_mask(st.down_since)
        return st._replace(down_since=jnp.where(clear, -1, st.down_since))

    return heal_shard


def make_window_fn(cfg: Config, mesh, window: int):
    step = make_sharded_pushsum_step(cfg, mesh)
    heal = make_sharded_pushsum_heal(cfg, mesh)
    steps = max(1, -(-window // pushsum.batch_ticks(cfg)))
    specs = pushsum_state_specs(cfg)

    def window_shard(st: PushSumState, base_key: jax.Array) -> PushSumState:
        st = jax.lax.fori_loop(0, steps, lambda _, x: step(x, base_key), st)
        if heal is not None:
            st = heal(st, base_key)
        return st

    return jax.jit(_shard_map(mesh, window_shard, in_specs=(specs, P()),
                              out_specs=specs), donate_argnums=(0,))


def make_seed_fn(cfg: Config, mesh):
    """No-op (mass exists from init), but still a shard_map identity so
    the stepper's seed call leaves the sharded layout untouched."""
    specs = pushsum_state_specs(cfg)

    def seed_shard(st: PushSumState, base_key: jax.Array) -> PushSumState:
        return st

    return jax.jit(_shard_map(mesh, seed_shard, in_specs=(specs, P()),
                              out_specs=specs), donate_argnums=(0,))


def make_run_to_coverage_fn(cfg: Config, mesh, telemetry: bool = False):
    step = make_sharded_pushsum_step(cfg, mesh)
    heal = make_sharded_pushsum_heal(cfg, mesh)
    specs = pushsum_state_specs(cfg)
    max_steps = cfg.max_rounds
    steps = event.poll_window_steps(cfg)
    b = pushsum.batch_ticks(cfg)
    check_in_flight = not cfg.overlay_heal_resolved

    def cond_live(s, target_count, until):
        live = ((s.total_received < target_count)
                & (s.tick < max_steps) & (s.tick < until))
        if check_in_flight:
            # The ring is empty BEFORE the first emission (seed is a
            # no-op), so the aliveness term only applies past window 0.
            alive = jax.lax.psum(in_flight(s), AXIS) > 0
            live = live & (alive | (s.tick < b))
        return live

    def advance(s, base_key):
        s = jax.lax.fori_loop(0, steps, lambda _, x: step(x, base_key), s)
        if heal is not None:
            s = heal(s, base_key)
        return s

    if telemetry:
        from gossip_simulator_tpu.utils import telemetry as telem

        ihwm = exchange.inflight_hwm(cfg, mesh.shape[AXIS])
        spatial = telem.spatial_spec(cfg, int(mesh.shape[AXIS]))
        hspecs = telem.bundle_specs(spatial, P)

        @functools.partial(jax.jit, donate_argnums=(0, 4))
        def run_t(st: PushSumState, base_key, target_count, until, hist):
            def run_shard(st, base_key, target_count, until, hist):
                def cond(carry):
                    s, _ = carry
                    return cond_live(s, target_count, until)

                def body(carry):
                    s, h = carry
                    s = advance(s, base_key)
                    row = telem.gossip_probe(
                        s, False, psum=lambda x: jax.lax.psum(x, AXIS),
                        pmax=lambda x: jax.lax.pmax(x, AXIS),
                        inflight_hwm=ihwm, relerr=s.relerr_ppb)
                    return s, telem.record_window(
                        h, row, st=s, spec=spatial,
                        shard_index=jax.lax.axis_index(AXIS),
                        gather=lambda x: jax.lax.all_gather(x, AXIS),
                        psum=lambda x: jax.lax.psum(x, AXIS),
                        relerr=s.relerr_ppb)

                return jax.lax.while_loop(cond, body, (st, hist))

            return _shard_map(
                mesh, run_shard,
                in_specs=(specs, P(), P(), P(), hspecs),
                out_specs=(specs, hspecs))(st, base_key, target_count,
                                           until, hist)

        return run_t

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(st: PushSumState, base_key: jax.Array, target_count: jax.Array,
            until: jax.Array) -> PushSumState:
        def run_shard(st, base_key, target_count, until):
            return jax.lax.while_loop(
                lambda s: cond_live(s, target_count, until),
                lambda s: advance(s, base_key), st)

        return _shard_map(mesh, run_shard, in_specs=(specs, P(), P(), P()),
                          out_specs=specs)(st, base_key, target_count, until)

    return run
