"""Mesh-sharded event-list engine: O(arrivals) ticks across shards.

Same design as the single-device event engine (models/event.py) with the
node axis split over the 1-D "nodes" mesh: every shard drains its own packed
mail ring locally, and the emission step routes each message to its
destination's owner shard with `lax.all_to_all` (parallel/exchange.py) --
the ICI replacement for the reference's shared `GlobalView[id].ch <- msg`
sends (simulator.go:145).  Collective counts are pmax-agreed at BOTH
levels so every shard executes the same number: drain chunks per window,
and -- when sender compaction engages (event.sender_compaction_cap) --
emission batches per chunk scheduled by the shared
event.narrow_tail_trips rule on pmax(senders): full scap-wide batches
plus, for small remainders, 1-2 narrow scap/8-wide tail batches, each
batch routing one all_to_all with a zero-loss width*kwidth per-pair
buffer (degree <= 2 configs emit one full-width all_to_all per chunk as
before).

Wire format: one int32 per message, `dst_local * (dw*B) + wslot * B + off`
(destination's local row, arrival window slot, tick offset).  Requires
n_local * dw * B < 2^31 -- 7.1M rows/shard at the default dw=3, B=10; the
mesh spreads larger n.  Drain-side packing is the same `dst_local * B + off`
the single-device engine uses.

Round-6 routed-append rework (the 61.6 -> <=51 ns/msg overhead round; every
piece bit-identical in the zero-overflow regime, see _route_and_append):
* bucketing is sort-free (exchange.route_multi's one-hot cumsum ranks);
* duplicate suppression runs PRE-exchange for locally-owned destinations
  -- at S=1 that is every edge, so suppressed traffic never touches the
  bucketing path -- with the receiving-side filter kept for routed
  arrivals;
* a 1-device mesh appends surviving edges directly (DIRECT_SELF_APPEND):
  the stable bucket pack + tiled self-all_to_all + unpack is the identity
  on entry order there, so the whole route is a provable no-op;
* destination-uniform graphs size the all_to_all payload from the actual
  per-pair high-water mark (exchange.chernoff_cap) instead of the
  zero-loss worst case width*kwidth, shrinking wire bytes and the
  receive-side unpack/filter/append width ~S-fold at S > 1.

Divergences from the single-device event engine: per-shard key folding (the
same scheme the sharded ring engine uses) decorrelates shards' crash/drop/
delay streams, so trajectories differ from the single-device run but match
it distributionally (tested).  Route-buffer overflow is counted in
`exchange_overflow`; slot-capacity overflow in `mail_dropped` -- never
silent.

SIR: re-broadcast triggers are tagged SELF-messages and therefore always
shard-local -- they append directly into the local ring
(_append_local_triggers) and never touch the all_to_all; removal draws are
shard-folded + row-keyed like delay/drop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gossip_simulator_tpu import scenario as _scen
from gossip_simulator_tpu import tuning as _tuning
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import epidemic, event, graphs
from gossip_simulator_tpu.models.event import EventState
from gossip_simulator_tpu.models.state import msg64_add
from gossip_simulator_tpu.parallel import exchange
from gossip_simulator_tpu.parallel.mesh import AXIS, shard_size
from gossip_simulator_tpu.utils import rng as _rng

I32 = jnp.int32

# Round-6 routed-append switches, monkeypatchable by the A/B parity tests
# (tests/test_sharded.py pins that flipping either reproduces the same
# trajectory bit-for-bit); production always runs both True.
PRE_EXCHANGE_SUPPRESS = True   # filter local-dest duplicates before routing
DIRECT_SELF_APPEND = True      # S=1: skip the route (it is the identity)


def event_state_specs(cfg: Config) -> EventState:
    # down_since: see sharded_step.sim_state_specs -- node-sharded only
    # when the fault machinery allocates the full axis.  The rumor leaves
    # follow the same convention: the mail-ring words and per-node bitmask
    # shard with their primary arrays under Config.multi_rumor; the
    # 1-element placeholders (and the psum-replicated per-rumor counters)
    # are replicated.
    multi = cfg.multi_rumor
    return EventState(
        flags=P(AXIS),
        friends=P(AXIS, None), friend_cnt=P(AXIS),
        mail_ids=P(AXIS), mail_cnt=P(AXIS, None), sup_cnt=P(AXIS, None),
        tick=P(), total_message=P(), total_received=P(), total_crashed=P(),
        mail_dropped=P(), exchange_overflow=P(),
        down_since=P(AXIS) if cfg.faults_enabled else P(),
        scen_crashed=P(), scen_recovered=P(), part_dropped=P(),
        heal_repaired=P(),
        mail_words=P(AXIS, None) if multi else P(),
        rumor_words=P(AXIS, None) if multi else P(),
        rumor_recv=P(), rumor_done=P(),
        # Per-shard exchange counters stack to (S, S+2) like mail_cnt
        # (the 1x1 off-path placeholder splits the same way to (S, 1)).
        exch_counts=P(AXIS, None),
    )


def _shard_map(mesh, fn, in_specs, out_specs):
    from gossip_simulator_tpu.parallel.mesh import shard_map

    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def make_sharded_event_init(cfg: Config, mesh):
    """Per-shard graph slice + event state (row-keyed generators make this
    bit-identical to slicing a single-device generation)."""
    n_local = shard_size(cfg.n, mesh)
    n_shards = mesh.shape[AXIS]

    def init_shard():
        shard = jax.lax.axis_index(AXIS)
        key = graphs.graph_key(cfg)
        friends, cnt = graphs.generate(cfg, key, row0=shard * n_local,
                                       rows=n_local)
        return event.init_state(cfg, friends, cnt, n_shards=n_shards)

    return jax.jit(_shard_map(mesh, init_shard, in_specs=(),
                              out_specs=event_state_specs(cfg)))


def _ring_append(cfg: Config, n_local: int, mail, cnt, dropped, payload,
                 wslot, valid, words=None, mail_words=None):
    """Append one packed entry per True in `valid` into its `wslot` slot of
    the local mail ring: rank within each slot via a one-hot cumsum
    (emission order), bounds-checked against the slot capacity with
    overflow counted in `dropped`, out-of-capacity writes diverted to the
    dw*cap trash cell.  The single reservation path for both routed data
    messages and shard-local SIR triggers.  With `words`/`mail_words`
    (multi-rumor) the per-entry payload words land at the SAME flat
    positions and a 4th value returns the updated word ring."""
    from gossip_simulator_tpu.ops.mailbox import ring_append

    dw = event.ring_windows(cfg)
    cap = (mail.shape[0] - event.ring_tail(cfg, n_local)) // dw
    dkern = cfg.deliver_kernel_resolved
    if words is not None:
        (mail, mail_words), cnt, dropped = ring_append(
            (mail, mail_words), cnt, dropped, (payload, words), wslot,
            valid, dw, cap, kernel=dkern)
        return mail, cnt, dropped, mail_words
    (mail,), cnt, dropped = ring_append(
        (mail,), cnt, dropped, (payload,), wslot, valid, dw, cap,
        kernel=dkern)
    return mail, cnt, dropped


def _route_and_append(cfg: Config, n_shards: int, n_local: int, mail, cnt,
                      dropped, xovf, dst_global, wslot, off, valid, rcap,
                      flags=None, words=None, mail_words=None):
    """Route (global dst, window slot, tick offset) messages to their owner
    shards and append into the local mail ring.

    `wslot`/`off` are per-message arrays the same shape as `dst_global`.
    `flags` non-None enables guaranteed-duplicate suppression.  Since
    round 6 the filter is split around the exchange: locally-owned
    destinations (whose received bits live right here -- EVERY destination
    at S=1, the 1/S local fraction otherwise) are filtered PRE-exchange,
    so their suppressed edges never enter the bucketing path; routed
    arrivals keep the RECEIVING-side filter (remote destinations' flags
    live on their owner shard -- a sender-side check is impossible for
    them).  Nothing writes flags between route and append, so both halves
    see the same flags snapshot and together suppress exactly the edges
    the old post-exchange-only filter did, on the same shard (a local dup's
    sender IS its receiver) and in the same arrival window -- pinned by
    tests/test_sharded.py::test_pre_vs_post_exchange_suppression.
    Suppressed edges are returned as per-arrival-window counts
    `sup_adds[dw]` the caller banks in sup_cnt and credits to the psum'd
    total_message when that window drains -- the same deferred-credit
    scheme as the single-device append_messages, so per-window observables
    stay bit-identical.  Retained entries keep their relative emission
    order, so at crash_p == 0 (the Config.dup_suppress_resolved gate) the
    trajectory is bit-identical.

    One-device meshes (DIRECT_SELF_APPEND) skip the route entirely:
    bucketing stably prefix-packs survivors and the tiled 1-device
    all_to_all is the identity, so appending the surviving edges in
    emission order lands the bit-identical ring -- and exchange_overflow
    stays structurally 0, which the zero-loss caps already guaranteed
    there (pinned by test_direct_local_matches_routed and the
    single-device bit-identity test).  Returns
    (mail, cnt, dropped, xovf, sup_adds).

    Multi-rumor (`words` (M, W) uint32 + `mail_words`): each message's
    payload words ride the SAME all_to_all as extra bitcast-int32 columns
    (exchange.route_multi slot-aligns them with the wire word), and the
    receive-side append lands them at the same flat ring positions
    (_ring_append's words path).  The -1 wire sentinel gates validity on
    the PRIMARY payload only, so word values with bit 31 set (rumor
    indices = 31 mod 32) route unharmed.  `flags` (duplicate suppression)
    is mutually exclusive with `words` -- config.validate rejects
    -dup-suppress on under a rumor axis.  A 6th return value carries the
    updated word ring."""
    b = event.batch_ticks(cfg)
    dw = event.ring_windows(cfg)
    sup_adds = jnp.zeros((dw,), I32)
    direct = n_shards == 1 and DIRECT_SELF_APPEND
    if flags is not None and (PRE_EXCHANGE_SUPPRESS or direct):
        # Pre-exchange filter on locally-owned destinations.  One-hot
        # reduction over the tiny dw axis (fuses; a dw-bin scatter-add
        # would serialize -- see append_messages' oh note).
        if n_shards == 1:
            local, dstl = valid, dst_global
        else:
            shard = jax.lax.axis_index(AXIS)
            local = valid & (dst_global // n_local == shard)
            dstl = dst_global % n_local
        dup = local & ((flags.at[jnp.where(local, dstl, 0)].get()
                        & event.RECEIVED) > 0)
        sup_adds = ((wslot[:, None] == jnp.arange(dw, dtype=I32)[None, :])
                    & dup[:, None]).sum(axis=0, dtype=I32)
        valid = valid & ~dup
    if direct:
        if words is not None:
            mail, cnt, dropped, mail_words = _ring_append(
                cfg, n_local, mail, cnt, dropped, dst_global * b + off,
                wslot, valid, words=words, mail_words=mail_words)
            return mail, cnt, dropped, xovf, sup_adds, mail_words
        mail, cnt, dropped = _ring_append(
            cfg, n_local, mail, cnt, dropped, dst_global * b + off, wslot,
            valid)
        return mail, cnt, dropped, xovf, sup_adds
    # xovf may be the (scalar, exch_counts) pair the spatial panels
    # thread through the emission carries (exchange.ovf_split).
    xo, exch = exchange.ovf_split(xovf)
    dest = jnp.where(valid, dst_global // n_local, n_shards)
    wire = jnp.where(
        valid,
        (dst_global % n_local) * (dw * b) + wslot * b + off, -1)
    if words is not None:
        payloads = (wire,) + tuple(
            jax.lax.bitcast_convert_type(words[:, i], I32)
            for i in range(words.shape[1]))
        out = exchange.route_multi(payloads, dest, valid, n_shards,
                                   rcap, traffic=exch)
        (recvs, ovf), exch = out[:2], out[2] if exch is not None else None
        recv = recvs[0]
    else:
        out = exchange.route_one(wire, dest, valid, n_shards, rcap,
                                 traffic=exch)
        (recv, ovf), exch = out[:2], out[2] if exch is not None else None
    rvalid = recv >= 0
    r = jnp.maximum(recv, 0)
    rdstl = r // (dw * b)
    rw = (r // b) % dw
    roff = r % b
    if flags is not None:
        # Receiving-side filter for routed arrivals; locally-destined
        # duplicates were already gone before the route when the
        # pre-exchange pass ran (re-checking survivors is a no-op).
        dup = rvalid & ((flags.at[rdstl].get() & event.RECEIVED) > 0)
        sup_adds = sup_adds + (
            (rw[:, None] == jnp.arange(dw, dtype=I32)[None, :])
            & dup[:, None]).sum(axis=0, dtype=I32)
        rvalid = rvalid & ~dup
    if words is not None:
        rwords = jnp.stack(
            [jax.lax.bitcast_convert_type(c, jnp.uint32)
             for c in recvs[1:]], axis=1)
        # Empty wire slots carry the -1 fill in every column; the rvalid
        # gate keeps their garbage words out of the ring.
        rwords = jnp.where(rvalid[:, None], rwords, jnp.uint32(0))
        mail, cnt, dropped, mail_words = _ring_append(
            cfg, n_local, mail, cnt, dropped, rdstl * b + roff, rw,
            rvalid, words=rwords, mail_words=mail_words)
        return (mail, cnt, dropped, exchange.ovf_join(xo + ovf, exch),
                sup_adds, mail_words)
    mail, cnt, dropped = _ring_append(
        cfg, n_local, mail, cnt, dropped, rdstl * b + roff, rw, rvalid)
    return (mail, cnt, dropped, exchange.ovf_join(xo + ovf, exch),
            sup_adds)


def _route_stage(cfg: Config, n_shards: int, n_local: int, xovf,
                 dst_global, wslot, off, valid, rcap, pstage, flags=None,
                 words=None):
    """Pipelined twin of _route_and_append's route half (-exchange-pipeline
    double): the same pre-exchange filter, wire pack, collective and
    receiving-side filter -- op for op, so verdicts and sup_adds are
    bit-identical -- but the ring-append arguments come back as the next
    staged drain instead of being applied.  The caller flushes the
    returned barrier-threaded PREVIOUS stage while this batch's
    all_to_all is in flight (exchange.route_multi_pipelined's ordering
    note).  Only the append is deferred: the duplicate verdict still
    reads flags at the serial program point, and nothing between a
    batch's route and its deferred append writes flags (appends are
    flag-blind; SIR removal precedes the route), so deferring moves no
    observable.  Callers guarantee n_shards > 1 (the S=1 direct path has
    no collective to overlap).  Returns (xovf, sup_adds, stage_new,
    pstage_threaded)."""
    b = event.batch_ticks(cfg)
    dw = event.ring_windows(cfg)
    sup_adds = jnp.zeros((dw,), I32)
    if flags is not None and PRE_EXCHANGE_SUPPRESS:
        shard = jax.lax.axis_index(AXIS)
        local = valid & (dst_global // n_local == shard)
        dstl = dst_global % n_local
        dup = local & ((flags.at[jnp.where(local, dstl, 0)].get()
                        & event.RECEIVED) > 0)
        sup_adds = ((wslot[:, None] == jnp.arange(dw, dtype=I32)[None, :])
                    & dup[:, None]).sum(axis=0, dtype=I32)
        valid = valid & ~dup
    # xovf may be the (scalar, exch_counts) pair (exchange.ovf_split).
    xo, exch = exchange.ovf_split(xovf)
    dest = jnp.where(valid, dst_global // n_local, n_shards)
    wire = jnp.where(
        valid,
        (dst_global % n_local) * (dw * b) + wslot * b + off, -1)
    if words is not None:
        payloads = (wire,) + tuple(
            jax.lax.bitcast_convert_type(words[:, i], I32)
            for i in range(words.shape[1]))
    else:
        payloads = (wire,)
    out = exchange.route_multi_pipelined(
        payloads, dest, valid, n_shards, rcap, pstage, traffic=exch)
    (recvs, ovf, pstage), exch = out[:3], (out[3] if exch is not None
                                           else None)
    recv = recvs[0]
    rvalid = recv >= 0
    r = jnp.maximum(recv, 0)
    rdstl = r // (dw * b)
    rw = (r // b) % dw
    roff = r % b
    if flags is not None:
        dup = rvalid & ((flags.at[rdstl].get() & event.RECEIVED) > 0)
        sup_adds = sup_adds + (
            (rw[:, None] == jnp.arange(dw, dtype=I32)[None, :])
            & dup[:, None]).sum(axis=0, dtype=I32)
        rvalid = rvalid & ~dup
    stage = (rdstl * b + roff, rw, rvalid)
    if words is not None:
        rwords = jnp.stack(
            [jax.lax.bitcast_convert_type(c, jnp.uint32)
             for c in recvs[1:]], axis=1)
        rwords = jnp.where(rvalid[:, None], rwords, jnp.uint32(0))
        stage = stage + (rwords,)
    return exchange.ovf_join(xo + ovf, exch), sup_adds, stage, pstage


def _flush_stage(cfg: Config, n_local: int, mail, cnt, dropped, stage,
                 sir=False, mail_words=None):
    """Apply a staged drain: the deferred ring_append of a batch's routed
    arrivals, then (SIR) the batch's local re-broadcast triggers -- the
    exact serial order _route_and_append + _append_local_triggers
    produce, one batch late.  Appends execute in the same FIFO order as
    the serial loop (stage j-1 always flushes before stage j), so ring
    layout, cnt trajectory and drop counts are bit-identical.  Returns
    (mail, cnt, dropped[, mail_words])."""
    payload, rw, rvalid = stage[:3]
    i = 3
    if mail_words is not None:
        rwords = stage[i]
        i += 1
        mail, cnt, dropped, mail_words = _ring_append(
            cfg, n_local, mail, cnt, dropped, payload, rw, rvalid,
            words=rwords, mail_words=mail_words)
    else:
        mail, cnt, dropped = _ring_append(
            cfg, n_local, mail, cnt, dropped, payload, rw, rvalid)
    if sir:
        rows, keep, wslot, off = stage[i:i + 4]
        mail, cnt, dropped = _append_local_triggers(
            cfg, n_local, mail, cnt, dropped, rows, keep, wslot, off)
    return (mail, cnt, dropped, mail_words) if mail_words is not None \
        else (mail, cnt, dropped)


def _empty_stage(n_lanes: int, trig_lanes: int = 0, words_w: int = 0):
    """An all-invalid staged drain (every valid lane False): flushing it
    reserves nothing and leaves the ring untouched, so it seeds the
    pipeline's prologue -- the one extra no-op append a pipelined loop
    pays per window/segment."""
    z = jnp.zeros((n_lanes,), I32)
    stage = (z, z, jnp.zeros((n_lanes,), bool))
    if words_w:
        stage = stage + (jnp.zeros((n_lanes, words_w), jnp.uint32),)
    if trig_lanes:
        zt = jnp.zeros((trig_lanes,), I32)
        stage = stage + (zt, jnp.zeros((trig_lanes,), bool), zt, zt)
    return stage


def _append_local_triggers(cfg: Config, n_local: int, mail, cnt, dropped,
                           rows, strig, wslot, off):
    """Append SIR re-broadcast triggers (tagged self-messages,
    trigger_base + row*b + off) into the LOCAL mail ring -- triggers never
    cross shards, so they skip the all_to_all entirely.  One entry per
    True in `strig`; reservations are per-trigger (not per-sender), so an
    all-False mask leaves the ring untouched."""
    b = event.batch_ticks(cfg)
    tb = event.trigger_base(n_local, b)
    return _ring_append(cfg, n_local, mail, cnt, dropped,
                        tb + rows * b + off, wslot, strig)


def make_sharded_event_step(cfg: Config, mesh):
    """One B-tick window transition per shard (shard_map body)."""
    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    b = event.batch_ticks(cfg)
    dw = event.ring_windows(cfg)
    ccap = event.drain_chunk(cfg, n_local)
    tail = event.ring_tail(cfg, n_local)
    crash_p = epidemic.p_eff(cfg, cfg.crashrate)
    drop_p = epidemic.p_eff(cfg, cfg.droprate)
    sir = cfg.protocol == "sir"
    removal_p = epidemic.p_eff(cfg, cfg.removal_rate) if sir else 0.0
    if n_local * dw * b >= 2**31:
        raise ValueError(
            f"wire packing overflow: n_local ({n_local}) * dw ({dw}) * B "
            f"({b}) must stay below 2^31; use more shards")
    if sir and (2 * n_local + 3) * b >= 2**31:
        raise ValueError(
            f"SIR trigger packing overflow: (2*n_local+3) ({2*n_local+3}) "
            f"* B ({b}) must stay below 2^31; use more shards")
    # Same degree-gated sender-compaction width as the single-device step.
    scap = event.sender_compaction_cap(cfg, ccap)
    # Split pre/post-exchange duplicate suppression (_route_and_append
    # docstring); the resolved gate implies crash_p == 0.
    suppress = cfg.dup_suppress_resolved
    # Destination-uniform graphs size each batch's per-pair wire buffer
    # from the actual high-water mark (mean/S + Chernoff pad; overflow
    # counted, never silent) instead of the zero-loss worst case -- the
    # all_to_all payload and the receive-side unpack/filter/append width
    # shrink ~S-fold at S > 1.  Ring lattices and overlay graphs can
    # concentrate a batch on one pair (exchange.chernoff_cap's soundness
    # note), so they keep the zero-loss bound; S = 1 is returned
    # unchanged (and DIRECT_SELF_APPEND skips the wire there anyway).
    uniform_dest = cfg.graph in ("kout", "erdos")

    def wire_cap(m_edges: int) -> int:
        return exchange.chernoff_cap(m_edges, s) if uniform_dest else m_edges

    # Exchange pipelining (-exchange-pipeline, ROADMAP item 1): defer
    # each batch's ring-append drain one batch behind its all_to_all so
    # the next dispatch overlaps the drain (_route_stage/_flush_stage).
    pipe = exchange.pipeline_enabled(cfg, s)
    if pipe and scap:
        # Per-buffer staged-batch width cap on the EMISSION batches only
        # (contract-neutral: batch-boundary placement cannot change the
        # trajectory in the zero-overflow regime -- narrow_tail_cap's
        # envelope).  The drain chunk ccap is untouched: its width CAN
        # move the trajectory (event.drain_chunk_floor's gated note).
        pc = _tuning.value("exchange.pipeline_chunk", cfg)
        if pc:
            scap = min(scap, int(pc))
    scen = cfg.scenario_resolved
    faults = cfg.faults_enabled
    track_crashed = faults or scen.has_faults
    track_down = faults and crash_p > 0.0
    track_part = scen.has_partitions
    # Multi-rumor (static): entry payload words ride the wire/carry
    # alongside mail_ids; injection replaces the seed (owner-gated --
    # injection_batch's source draws are shard-count invariant).  Off =>
    # every gate below is Python-False and the traced program is the
    # single-rumor one.
    multi = cfg.multi_rumor

    def step_shard(st: EventState, base_key: jax.Array) -> EventState:
        shard = jax.lax.axis_index(AXIS)
        skey = jax.random.fold_in(base_key, shard)
        gid0 = shard * n_local
        # Scenario faults: (window, GLOBAL-id)-keyed draws -- the one
        # stream in this engine NOT shard-folded, so the crash/recovery
        # schedule is identical at any shard count (reshard-resume safe).
        flags1, down1, dsc, dsr = event.apply_fault_window_flags(
            cfg, st.flags, st.down_since, st.tick,
            gid0 + jnp.arange(n_local, dtype=I32), base_key, b)
        st = st._replace(flags=flags1, down_since=down1)
        inj_drop = jnp.zeros((), I32)
        if multi:
            # Streaming/oneshot injection BEFORE the slot count is read,
            # so a rumor due this window drains -- and its source starts
            # forwarding -- this window (the single-device step's order).
            # Only the source's owner shard appends (valid is owner-gated,
            # payload row localized); drops accumulate into the psum'd
            # per-window delta, not the replicated mail_dropped directly.
            ipay, iwords, iwslot, ivalid = event.injection_batch(
                cfg, st.tick, base_key, b, dw, n_local=n_local,
                shard=shard)
            from gossip_simulator_tpu.ops.mailbox import ring_append

            icap = (st.mail_ids.shape[0] - tail) // dw
            (mi, mw), icnt, inj_drop = ring_append(
                (st.mail_ids, st.mail_words), st.mail_cnt, inj_drop,
                (ipay, iwords), iwslot, ivalid, dw, icap)
            st = st._replace(mail_ids=mi, mail_words=mw, mail_cnt=icnt)
        w = st.tick // b
        slot = w % dw
        m = st.mail_cnt[0, slot]
        dm0 = st.sup_cnt[0, slot]
        mail0 = st.mail_ids
        cap0 = (mail0.shape[0] - tail) // dw
        if suppress:
            # Pre-drain compaction on the local slot (local flags; see
            # event.predrain_compact) in the endgame regime only
            # (event.PREDRAIN_MIN_RECV_FRAC; total_received is replicated,
            # so every shard agrees).  Chunk count is pmax-agreed on the
            # POST-filter occupancy below.
            go = st.total_received >= I32(
                int(event.PREDRAIN_MIN_RECV_FRAC * cfg.n))
            mail0, kept, fdat = event.predrain_compact(
                b, n_local, dw, cap0, ccap, sir, st.flags, mail0, slot,
                jnp.where(go, m, 0))
            m = jnp.where(go, kept, m)
            dm0 = dm0 + fdat
        chunks = (jax.lax.pmax(m, AXIS) + ccap - 1) // ccap
        ckey = _rng.tick_key(skey, w, _rng.OP_CRASH)
        kwidth = st.friends.shape[1]
        # Dense-path per-pair buffer: the Chernoff high-water cap on
        # uniform graphs, the round-5 lossless-leaning bound otherwise
        # (a batch cannot emit more than ccap * kwidth edges).
        rcap = (wire_cap(ccap * kwidth) if uniform_dest
                else min(exchange.epidemic_cap(n_local, kwidth, s),
                         ccap * kwidth))
        # Compacted batches carry at most `width` senders, so
        # width * kwidth is their zero-loss bound; wire_cap tightens it
        # to the per-pair high-water mark on uniform graphs (computed per
        # batch width in make_abody -- full scap and narrow scap/8).
        cap = cap0

        def emit(flags, mail, cnt, dropped, xovf, sids, svalid, sticks,
                 width, ecap, sw=None, mwords=None, pstage=None):
            """Route one batch of senders' broadcasts (delay/drop draws,
            SIR removal + local triggers, all_to_all + ring append) at a
            static `width`.  Keys are shard-folded + (tick, local-row)
            keyed, so the draws do not depend on the batch width.
            Returns a trailing partition-block count (Python 0 without
            partitions); under multi (`sw` = per-sender delta words
            (width, W), `mwords` = word ring) a further trailing value
            returns the updated word ring.  `pstage` non-None runs the
            PIPELINED schedule (_route_stage/_flush_stage): this batch's
            append is returned as one more trailing value (the new
            stage) and the previous batch's stage is flushed behind this
            batch's in-flight all_to_all instead."""
            if s == 1 and DIRECT_SELF_APPEND and not sir:
                # One-device SI mesh: the emission IS the single-device
                # append -- append_messages draws the identical
                # (tick, row)-keyed delay/drop streams off the folded
                # shard key and reserves per sender in the same order the
                # per-entry path appends (the _route_and_append identity
                # argument), so the whole decode/rank/append pass below
                # collapses into the engine the jax backend runs; this is
                # what lets the S=1 bench twin's ns/msg sit on top of the
                # single-device row.  SIR keeps the generic path: its
                # routed form appends batch triggers AFTER batch data,
                # while append_messages interleaves each sender's trigger
                # with its edges -- a different (established, pre-round-6)
                # ring order this rework must not shift.  The partition
                # mask applies inside append_messages (gid0 globalizes).
                if multi:
                    mail, cnt, dropped, sa, blk, mwords = \
                        event.append_messages(
                            cfg, mail, cnt, dropped, sids, svalid, sticks,
                            st.friends, st.friend_cnt, skey, gid0=gid0,
                            swords=sw, mail_words=mwords)
                    return flags, mail, cnt, dropped, xovf, sa, blk, mwords
                mail, cnt, dropped, sa, blk = event.append_messages(
                    cfg, mail, cnt, dropped, sids, svalid, sticks,
                    st.friends, st.friend_cnt, skey,
                    flags=flags if suppress else None, gid0=gid0)
                return flags, mail, cnt, dropped, xovf, sa, blk
            rows = jnp.where(svalid, sids, n_local)
            sidx = jnp.where(svalid, sids, 0)
            sf = st.friends.at[sidx].get()
            # No friend_cnt gather: rows are prefix-compact, (sf >= 0) is
            # the edge mask (see append_messages).
            dk = event._sender_keys(skey, _rng.OP_DELAY, sticks, rows)
            pk = event._sender_keys(skey, _rng.OP_DROP, sticks, rows)
            delay = jnp.maximum(jax.vmap(
                lambda kk: jax.random.randint(
                    kk, (), cfg.delaylow, cfg.delayhigh, dtype=I32))(dk), 1)
            if drop_p <= 0.0:
                drop = jnp.zeros((width, kwidth), bool)
            elif drop_p >= 1.0:
                drop = jnp.ones((width, kwidth), bool)
            else:
                drop = jax.vmap(
                    lambda kk: jax.random.bernoulli(kk, drop_p,
                                                    (kwidth,)))(pk)
            arrive = sticks + delay
            wslot2 = (arrive // b) % dw
            off2 = arrive % b
            rem = None
            if sir:
                # Removal draw per sender at its send tick (same ordering
                # as the single-device step); surviving senders schedule
                # their next trigger locally -- triggers never cross
                # shards, so no collective is involved.
                rk = event._sender_keys(skey, _rng.OP_REMOVE, sticks, rows)
                rem = jax.vmap(lambda kk: jax.random.bernoulli(
                    kk, removal_p))(rk) & svalid if removal_p > 0.0 \
                    else jnp.zeros(svalid.shape, bool)
                flags = flags.at[jnp.where(rem, sids, n_local)].add(
                    event.REMOVED, mode="drop")
            edge = svalid[:, None] & ~drop & (sf >= 0)
            blk = 0
            if track_part:
                # Send-time partition mask on global (src, dst) ids --
                # before the route AND before the duplicate filter, so a
                # blocked edge is never credited as a delivered duplicate.
                blocked = _scen.partition_blocked(
                    scen, cfg.n, sticks[:, None], (gid0 + rows)[:, None],
                    sf) & edge
                blk = blocked.sum(dtype=I32)
                edge = edge & ~blocked
            dstg = jnp.where(edge, sf, 0).reshape(-1)
            if multi:
                # Every edge of a sender carries the sender's NEW bits.
                ewords = jnp.broadcast_to(
                    sw[:, None, :], (width, kwidth, sw.shape[1])
                ).reshape(-1, sw.shape[1])
                if pstage is not None:
                    xovf, nsup, nstage, pthr = _route_stage(
                        cfg, s, n_local, xovf, dstg,
                        jnp.broadcast_to(wslot2[:, None],
                                         (width, kwidth)).reshape(-1),
                        jnp.broadcast_to(off2[:, None],
                                         (width, kwidth)).reshape(-1),
                        edge.reshape(-1), ecap, pstage, words=ewords)
                    mail, cnt, dropped, mwords = _flush_stage(
                        cfg, n_local, mail, cnt, dropped, pthr,
                        mail_words=mwords)
                    return (flags, mail, cnt, dropped, xovf, nsup, blk,
                            mwords, nstage)
                mail, cnt, dropped, xovf, nsup, mwords = _route_and_append(
                    cfg, s, n_local, mail, cnt, dropped, xovf, dstg,
                    jnp.broadcast_to(wslot2[:, None],
                                     (width, kwidth)).reshape(-1),
                    jnp.broadcast_to(off2[:, None],
                                     (width, kwidth)).reshape(-1),
                    edge.reshape(-1), ecap, words=ewords,
                    mail_words=mwords)
                return (flags, mail, cnt, dropped, xovf, nsup, blk,
                        mwords)
            if pstage is not None:
                xovf, nsup, nstage, pthr = _route_stage(
                    cfg, s, n_local, xovf, dstg,
                    jnp.broadcast_to(wslot2[:, None],
                                     (width, kwidth)).reshape(-1),
                    jnp.broadcast_to(off2[:, None],
                                     (width, kwidth)).reshape(-1),
                    edge.reshape(-1), ecap,
                    pstage, flags=flags if suppress else None)
                if sir:
                    # The batch's triggers defer WITH its data so the
                    # flush replays the serial append order exactly.
                    nstage = nstage + (rows, svalid & ~rem, wslot2, off2)
                mail, cnt, dropped = _flush_stage(
                    cfg, n_local, mail, cnt, dropped, pthr, sir=sir)
                return flags, mail, cnt, dropped, xovf, nsup, blk, nstage
            mail, cnt, dropped, xovf, nsup = _route_and_append(
                cfg, s, n_local, mail, cnt, dropped, xovf, dstg,
                jnp.broadcast_to(wslot2[:, None],
                                 (width, kwidth)).reshape(-1),
                jnp.broadcast_to(off2[:, None],
                                 (width, kwidth)).reshape(-1),
                edge.reshape(-1), ecap, flags=flags if suppress else None)
            if sir:
                mail, cnt, dropped = _append_local_triggers(
                    cfg, n_local, mail, cnt, dropped, rows, svalid & ~rem,
                    wslot2, off2)
            return flags, mail, cnt, dropped, xovf, nsup, blk

        # Conditional loop-carry tail, mirroring the single-device step:
        # crash clock only when reception crashes stamp it, partition
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
            core, i = c[:9], 9
            down = part = None
            if track_down:
                down, i = c[i], i + 1
            if track_part:
                part, i = c[i], i + 1
            return core, down, part, c[i:]

        # Dense-path pipelining threads the staged drain through the
        # WHOLE chunk fori (one emit per chunk, homogeneous shapes):
        # chunk j's drain flushes behind chunk j+1's in-flight
        # collective, the final stage flushes after the loop.  The
        # compacted path pipelines inside each chunk's full-width batch
        # loop instead (make_abody/run_narrow_tail below).
        pipe_dense = pipe and not scap

        def body(j, carry):
            (flags, mail, cnt, sup, dm, dr, dc, dropped,
             xovf), down, part, mt = unpack(carry)
            mail_words = rumor_words = rrecv = delta_w = None
            pend = None
            if multi:
                mail_words, rumor_words, rrecv = mt[:3]
                if pipe_dense:
                    pend = mt[3]
            elif pipe_dense:
                pend = mt[0]
            off0 = j * ccap
            entry_pos = off0 + jnp.arange(ccap, dtype=I32)
            evalid = entry_pos < m
            packed = jax.lax.dynamic_slice(mail, (slot * cap + off0,),
                                           (ccap,))
            if multi:
                wchunk = jax.lax.dynamic_slice(
                    mail_words, (slot * cap + off0, 0),
                    (ccap, mail_words.shape[1]))
                (flags, cdm, cdr, cdc, ids_s, toff_s, senders, down,
                 rumor_words, delta_w, drecv) = event.drain_chunk_core(
                    crash_p, b, n_local, flags, packed, evalid,
                    entry_pos, ckey, sir=sir,
                    track_crashed=track_crashed, down_since=down,
                    win_tick=st.tick, words=wchunk,
                    rumor_words=rumor_words)
                rrecv = rrecv + drecv
            else:
                flags, cdm, cdr, cdc, ids_s, toff_s, senders, down = \
                    event.drain_chunk_core(crash_p, b, n_local, flags,
                                           packed, evalid, entry_pos,
                                           ckey, sir=sir,
                                           track_crashed=track_crashed,
                                           down_since=down,
                                           win_tick=st.tick)
            dm, dr, dc = dm + cdm, dr + cdr, dc + cdc
            if scap:
                # Sender compaction (see the single-device step's
                # rationale -- the emission's gathers/route inputs are
                # element-bound, and only ~1/(0.9 deg) of entries are
                # senders).  The batch count is pmax-agreed so every
                # shard runs the same number of all_to_alls; receiving
                # slots see arrivals in batch order, a (deterministic)
                # reshuffle of the dense path's per-chunk order, so
                # per-shard trajectories shift within the usual
                # sharded-vs-single distributional envelope.
                srank = jnp.cumsum(senders.astype(I32)) - 1
                scnt = senders.sum(dtype=I32)
                spacked = ids_s * b + toff_s
                smax = jax.lax.pmax(scnt, AXIS)

                def make_abody(width, lo_of):
                    # width * kwidth: zero-loss per-pair receive buffer
                    # at this batch width (see the step-level comment).
                    # Only the homogeneous full-width batches pipeline
                    # (the staged carry must keep one shape across the
                    # fori); the 1-2 narrow tail batches stay serial --
                    # run_narrow_tail's `between` hook flushes the last
                    # full batch's stage before they run, so FIFO append
                    # order is preserved.
                    stagewise = pipe and width == scap

                    def abody(jb, acarry):
                        acarry = list(acarry)
                        apend = acarry.pop() if stagewise else None
                        awords = acarry.pop() if multi else None
                        if track_part:
                            (aflags, amail, acnt, asup, adropped, axovf,
                             apart) = acarry
                        else:
                            (aflags, amail, acnt, asup, adropped,
                             axovf) = acarry
                            apart = None
                        if multi:
                            bids, btoff, bvalid, bufw = event.sender_batch(
                                senders, srank, scnt, spacked, b, width,
                                jb, lo=lo_of(jb), sdelta=delta_w)
                            if stagewise:
                                (aflags, amail, acnt, adropped, axovf, sa,
                                 ablk, awords, apend) = emit(
                                    aflags, amail, acnt, adropped, axovf,
                                    bids, bvalid, w * b + btoff, width,
                                    wire_cap(width * kwidth), sw=bufw,
                                    mwords=awords, pstage=apend)
                            else:
                                (aflags, amail, acnt, adropped, axovf, sa,
                                 ablk, awords) = emit(
                                    aflags, amail, acnt, adropped, axovf,
                                    bids, bvalid, w * b + btoff, width,
                                    wire_cap(width * kwidth), sw=bufw,
                                    mwords=awords)
                        else:
                            bids, btoff, bvalid = event.sender_batch(
                                senders, srank, scnt, spacked, b, width,
                                jb, lo=lo_of(jb))
                            if stagewise:
                                (aflags, amail, acnt, adropped, axovf, sa,
                                 ablk, apend) = emit(
                                    aflags, amail, acnt, adropped, axovf,
                                    bids, bvalid, w * b + btoff, width,
                                    wire_cap(width * kwidth),
                                    pstage=apend)
                            else:
                                (aflags, amail, acnt, adropped, axovf, sa,
                                 ablk) = emit(aflags, amail, acnt,
                                              adropped, axovf, bids,
                                              bvalid, w * b + btoff,
                                              width,
                                              wire_cap(width * kwidth))
                        out = (aflags, amail, acnt, asup + sa[None, :],
                               adropped, axovf)
                        if track_part:
                            out = out + (apart + ablk,)
                        if multi:
                            out = out + (awords,)
                        if stagewise:
                            out = out + (apend,)
                        return out
                    return abody

                # Shared schedule + driver (event.run_narrow_tail) on the
                # pmax-agreed smax, so every shard still runs the same
                # number of all_to_alls.
                acarry0 = (flags, mail, cnt, sup, dropped, xovf)
                if track_part:
                    acarry0 = acarry0 + (part,)
                if multi:
                    acarry0 = acarry0 + (mail_words,)
                between = None
                if pipe:
                    acarry0 = acarry0 + (_empty_stage(
                        s * wire_cap(scap * kwidth),
                        trig_lanes=0 if multi else (scap if sir else 0),
                        words_w=(mail_words.shape[1] if multi else 0)),)

                    def between(c):
                        # Flush the last full-width batch's stage and
                        # strip it from the carry before the (serial,
                        # differently-shaped) narrow tail runs.
                        c = list(c)
                        apend = c.pop()
                        mw = c.pop() if multi else None
                        if multi:
                            c[1], c[2], c[4], mw = _flush_stage(
                                cfg, n_local, c[1], c[2], c[4], apend,
                                mail_words=mw)
                            c.append(mw)
                        else:
                            c[1], c[2], c[4] = _flush_stage(
                                cfg, n_local, c[1], c[2], c[4], apend,
                                sir=sir)
                        return tuple(c)

                out = event.run_narrow_tail(make_abody, acarry0, smax,
                                            scap, between=between)
                (flags, mail, cnt, sup, dropped, xovf) = out[:6]
                if multi:
                    mail_words = out[-1]
                if track_part:
                    part = out[6]
            else:
                if multi:
                    if pipe_dense:
                        (flags, mail, cnt, dropped, xovf, sa, blk,
                         mail_words, pend) = emit(
                            flags, mail, cnt, dropped, xovf, ids_s,
                            senders, w * b + toff_s, ccap, rcap,
                            sw=delta_w, mwords=mail_words, pstage=pend)
                    else:
                        (flags, mail, cnt, dropped, xovf, sa, blk,
                         mail_words) = emit(
                            flags, mail, cnt, dropped, xovf, ids_s,
                            senders, w * b + toff_s, ccap, rcap,
                            sw=delta_w, mwords=mail_words)
                elif pipe_dense:
                    flags, mail, cnt, dropped, xovf, sa, blk, pend = emit(
                        flags, mail, cnt, dropped, xovf, ids_s, senders,
                        w * b + toff_s, ccap, rcap, pstage=pend)
                else:
                    flags, mail, cnt, dropped, xovf, sa, blk = emit(
                        flags, mail, cnt, dropped, xovf, ids_s, senders,
                        w * b + toff_s, ccap, rcap)
                sup = sup + sa[None, :]
                if track_part:
                    part = part + blk
            mt_out = (mail_words, rumor_words, rrecv) if multi else ()
            if pipe_dense:
                mt_out = mt_out + (pend,)
            return pack((flags, mail, cnt, sup, dm, dr, dc, dropped,
                         xovf), down, part, mt_out)

        z = jnp.zeros((), I32)
        # dm starts at this shard's deferred duplicate credits for the
        # draining window (banked by _route_and_append; appends during
        # this drain only target later windows), zeroed with mail_cnt.
        # Under multi the dropped carry is seeded with the injection
        # drops so they reach the per-window psum below.
        mt0 = ((st.mail_words, st.rumor_words,
                jnp.zeros_like(st.rumor_recv)) if multi else ())
        if pipe_dense:
            # Prologue: the pipeline starts with an all-invalid stage
            # (chunk 0 flushes a no-op), and the last chunk's stage
            # flushes in the epilogue below -- before the drained slot's
            # counters reset (the appends target later windows anyway).
            mt0 = mt0 + (_empty_stage(
                s * rcap,
                trig_lanes=0 if multi else (ccap if sir else 0),
                words_w=(st.mail_words.shape[1] if multi else 0)),)
        # Spatial panels (S > 1): the exch_counts leaf rides the xovf
        # carry position as a pair (exchange.ovf_split) so every route
        # inside the chunk loop accumulates into it without widening any
        # emission signature.
        xv0 = ((z, st.exch_counts)
               if cfg.telemetry_spatial_enabled and s > 1 else z)
        out = jax.lax.fori_loop(
            0, chunks, body,
            pack((st.flags, mail0, st.mail_cnt, st.sup_cnt,
                  dm0, z, z, inj_drop, xv0), st.down_since, z, mt0))
        (flags, mail, cnt, sup, dm, dr, dc, ddrop,
         dxovf), down, part, mt = unpack(out)
        dxovf, exch_new = exchange.ovf_split(dxovf)
        if exch_new is not None:
            st = st._replace(exch_counts=exch_new)
        if pipe_dense:
            if multi:
                mw, rwd, rrc = mt[:3]
                mail, cnt, ddrop, mw = _flush_stage(
                    cfg, n_local, mail, cnt, ddrop, mt[3], mail_words=mw)
                mt = (mw, rwd, rrc)
            else:
                mail, cnt, ddrop = _flush_stage(
                    cfg, n_local, mail, cnt, ddrop, mt[0], sir=sir)
                mt = ()
        cnt = cnt.at[0, slot].set(0)
        sup = sup.at[0, slot].set(0)
        dm, dr, dc, ddrop, dxovf = jax.lax.psum((dm, dr, dc, ddrop, dxovf),
                                                AXIS)
        st = st._replace(
            flags=flags, mail_ids=mail, mail_cnt=cnt, sup_cnt=sup,
            tick=st.tick + b,
            total_message=msg64_add(st.total_message, dm),
            total_received=st.total_received + dr,
            total_crashed=st.total_crashed + dc,
            mail_dropped=st.mail_dropped + ddrop,
            exchange_overflow=st.exchange_overflow + dxovf)
        if multi:
            # Per-shard receive deltas fold into the replicated global
            # per-rumor counters; done ticks stamp off the advanced tick
            # (the same convention as the single-device step).
            mail_words, rumor_words, rrecv = mt
            rumor_recv = st.rumor_recv + jax.lax.psum(rrecv, AXIS)
            rumor_done = event.stamp_rumor_done(cfg, rumor_recv,
                                                st.rumor_done, st.tick)
            st = st._replace(mail_words=mail_words,
                             rumor_words=rumor_words,
                             rumor_recv=rumor_recv, rumor_done=rumor_done)
        if track_down:
            st = st._replace(down_since=down)
        if scen.active:
            psc, psr = jax.lax.psum(
                (jnp.asarray(dsc, I32), jnp.asarray(dsr, I32)), AXIS)
            st = st._replace(scen_crashed=st.scen_crashed + psc,
                             scen_recovered=st.scen_recovered + psr)
        if track_part:
            st = st._replace(
                part_dropped=st.part_dropped + jax.lax.psum(part, AXIS))
        return st

    return step_shard


def make_sharded_event_seed(cfg: Config, mesh):
    """Uniform-random global sender; every shard draws the same sender (same
    global key), only the owner emits, and the messages ride the normal
    route+append path."""
    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    b = event.batch_ticks(cfg)
    dw = event.ring_windows(cfg)

    if cfg.multi_rumor:
        # Multi-rumor sources come from the injection schedule inside the
        # window step (owner-gated, OP_INJECT-keyed); the classic seed
        # would double-infect rumor 0's source.
        def seed_noop(st: EventState, base_key: jax.Array) -> EventState:
            return st

        return seed_noop

    def seed_shard(st: EventState, base_key: jax.Array) -> EventState:
        shard = jax.lax.axis_index(AXIS)
        ks = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_SEED_NODE)
        kd = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_DELAY)
        kp = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_DROP)
        sender = jax.random.randint(ks, (), 0, cfg.n, dtype=I32)
        own = (sender // n_local) == shard
        srow = jnp.where(own, sender % n_local, 0)
        kwidth = st.friends.shape[1]
        sf = st.friends[srow]
        scnt = jnp.where(own, st.friend_cnt[srow], 0)
        delay = jnp.maximum(
            jax.random.randint(jax.random.fold_in(kd, sender), (),
                               cfg.delaylow, cfg.delayhigh, dtype=I32), 1)
        drop = _rng.bernoulli(jax.random.fold_in(kp, sender),
                              epidemic.p_eff(cfg, cfg.droprate), (kwidth,))
        arrive = st.tick + delay
        edge = (jnp.arange(kwidth, dtype=I32) < scnt) & ~drop & (sf >= 0) \
            & own
        scen = cfg.scenario_resolved
        if scen.has_partitions:
            blocked = _scen.partition_blocked(
                scen, cfg.n, st.tick, sender, sf) & edge
            st = st._replace(
                part_dropped=st.part_dropped
                + jax.lax.psum(blocked.sum(dtype=I32), AXIS))
            edge = edge & ~blocked
        flags, total_received = st.flags, st.total_received
        if cfg.protocol == "sir" or not cfg.compat_reference:
            # SIR always marks the seed: trigger firing needs the received
            # bit, and the reference has no SIR compat surface (same rule
            # as the single-device engines).
            flags = flags | jnp.where(
                (jnp.arange(n_local, dtype=I32) == srow) & own,
                event.RECEIVED, jnp.uint8(0))
            total_received = total_received + 1  # replicated
        # The seed emits at most kwidth messages total; a wave-sized route
        # buffer here would allocate epidemic_cap (~GBs at 1e8) for nothing.
        rcap = min(exchange.epidemic_cap(n_local, kwidth, s), kwidth)
        # No suppression at seed time (flags=None): the only set received
        # bit is the seed's own and no generator produces self-edges.
        xv0 = ((jnp.zeros((), I32), st.exch_counts)
               if cfg.telemetry_spatial_enabled and s > 1
               else jnp.zeros((), I32))
        mail, cnt, dropped, xovf, _ = _route_and_append(
            cfg, s, n_local, st.mail_ids, st.mail_cnt, jnp.zeros((), I32),
            xv0, jnp.where(edge, sf, 0),
            jnp.broadcast_to((arrive // b) % dw, (kwidth,)),
            jnp.broadcast_to(arrive % b, (kwidth,)), edge, rcap)
        xovf, exch_new = exchange.ovf_split(xovf)
        if exch_new is not None:
            st = st._replace(exch_counts=exch_new)
        if cfg.protocol == "sir":
            # The seed's removal draw decides its re-broadcast trigger
            # (replicated key; only the owner shard appends).
            kr = _rng.tick_key(base_key, epidemic.SEED_TICK, _rng.OP_REMOVE)
            keep = ~_rng.bernoulli(kr, epidemic.p_eff(cfg, cfg.removal_rate),
                                   ())
            mail, cnt, dropped = _append_local_triggers(
                cfg, n_local, mail, cnt, dropped, srow[None],
                (own & keep)[None], ((arrive // b) % dw)[None],
                (arrive % b)[None])
        dropped, xovf = jax.lax.psum((dropped, xovf), AXIS)
        return st._replace(flags=flags, total_received=total_received,
                           mail_ids=mail, mail_cnt=cnt,
                           mail_dropped=st.mail_dropped + dropped,
                           exchange_overflow=st.exchange_overflow + xovf)

    return seed_shard


def make_sharded_event_heal(cfg: Config, mesh):
    """Sharded event-engine overlay healing (shard_map body; None when
    off): per-shard detector verdicts are all_gathered (one bool per
    node), condemned friends are replaced via the GLOBAL-id-keyed makeup
    draw, and infected healers' re-sends ride the normal all_to_all
    route+append.  See sharded_step.make_sharded_heal for the ring
    twin."""
    if not cfg.overlay_heal_resolved:
        return None
    s = mesh.shape[AXIS]
    n_local = shard_size(cfg.n, mesh)
    b = event.batch_ticks(cfg)
    dw = event.ring_windows(cfg)
    detect = cfg.heal_detect_ms

    def heal_shard(st: EventState, base_key: jax.Array) -> EventState:
        shard = jax.lax.axis_index(AXIS)
        gids = shard * n_local + jnp.arange(n_local, dtype=I32)
        rows = jnp.arange(n_local, dtype=I32)
        k = st.friends.shape[1]
        crashed = (st.flags & event.CRASHED) > 0
        detected = _scen.detect_dead(crashed, st.down_since, st.tick,
                                     detect)
        healer_ok = ~crashed
        sender_inf = ((st.flags & event.RECEIVED) > 0) & ~crashed \
            & ~((st.flags & event.REMOVED) > 0)
        bits_global = jax.lax.all_gather(
            _scen.heal_peer_bits(detected, sender_inf), AXIS, tiled=True)
        friends, resend, pull, delay, clear, rep, blk = _scen.heal_and_wave(
            cfg, st.friends, st.friend_cnt, bits_global, healer_ok,
            sender_inf, _scen.rejoined_mask(st.down_since), gids, st.tick,
            base_key)
        arrive = st.tick + delay
        wslot = jnp.broadcast_to(((arrive // b) % dw)[:, None],
                                 (n_local, k)).reshape(-1)
        off = jnp.broadcast_to((arrive % b)[:, None],
                               (n_local, k)).reshape(-1)
        rcap = min(exchange.epidemic_cap(n_local, k, s), n_local * k)
        xv0 = ((jnp.zeros((), I32), st.exch_counts)
               if cfg.telemetry_spatial_enabled and s > 1
               else jnp.zeros((), I32))
        if cfg.multi_rumor:
            wc = st.rumor_words.shape[1]
            # Resends carry the healer's FULL rumor set (cross-shard via
            # the word-column route); rejoin pulls copy the friend's
            # global word row -- one all_gather of the (n_local, W)
            # uint32 leaf serves both the pull gather below and keeps
            # the resend path local.
            rw = jnp.broadcast_to(st.rumor_words[:, None, :],
                                  (n_local, k, wc)).reshape(-1, wc)
            mail, cnt, dropped, xovf, _, mailw = _route_and_append(
                cfg, s, n_local, st.mail_ids, st.mail_cnt,
                jnp.zeros((), I32), xv0,
                jnp.where(resend, friends, 0).reshape(-1),
                wslot, off, resend.reshape(-1), rcap, words=rw,
                mail_words=st.mail_words)
            ppay = jnp.broadcast_to(rows[:, None] * b,
                                    (n_local, k)).reshape(-1) + off
            global_words = jax.lax.all_gather(st.rumor_words, AXIS,
                                              tiled=True)
            fw = global_words[jnp.where(friends >= 0, friends,
                                        0)].reshape(-1, wc)
            mail, cnt, dropped, mailw = _ring_append(
                cfg, n_local, mail, cnt, dropped, ppay, wslot,
                pull.reshape(-1), words=fw, mail_words=mailw)
            st = st._replace(mail_words=mailw)
        else:
            mail, cnt, dropped, xovf, _ = _route_and_append(
                cfg, s, n_local, st.mail_ids, st.mail_cnt,
                jnp.zeros((), I32), xv0,
                jnp.where(resend, friends, 0).reshape(-1),
                wslot, off, resend.reshape(-1), rcap)
            # Rejoin pull responses deliver to the puller's OWN row --
            # always shard-local, so they append directly.
            ppay = jnp.broadcast_to(rows[:, None] * b,
                                    (n_local, k)).reshape(-1) + off
            mail, cnt, dropped = _ring_append(
                cfg, n_local, mail, cnt, dropped, ppay, wslot,
                pull.reshape(-1))
        xovf, exch_new = exchange.ovf_split(xovf)
        if exch_new is not None:
            st = st._replace(exch_counts=exch_new)
        rep, blk, dropped, xovf = jax.lax.psum(
            (rep, jnp.asarray(blk, I32), dropped, xovf), AXIS)
        return st._replace(
            friends=friends, mail_ids=mail, mail_cnt=cnt,
            mail_dropped=st.mail_dropped + dropped,
            exchange_overflow=st.exchange_overflow + xovf,
            down_since=jnp.where(clear, -1, st.down_since),
            heal_repaired=st.heal_repaired + rep,
            part_dropped=st.part_dropped + blk)

    return heal_shard


def make_window_fn(cfg: Config, mesh, window: int):
    """Advance ~`window` simulated ms as one device call."""
    step = make_sharded_event_step(cfg, mesh)
    heal = make_sharded_event_heal(cfg, mesh)
    steps = max(1, -(-window // event.batch_ticks(cfg)))
    specs = event_state_specs(cfg)

    def window_shard(st: EventState, base_key: jax.Array) -> EventState:
        st = jax.lax.fori_loop(0, steps, lambda _, x: step(x, base_key), st)
        if heal is not None:
            st = heal(st, base_key)
        return st

    return jax.jit(_shard_map(mesh, window_shard, in_specs=(specs, P()),
                              out_specs=specs), donate_argnums=(0,))


def make_seed_fn(cfg: Config, mesh):
    specs = event_state_specs(cfg)
    return jax.jit(_shard_map(mesh, make_sharded_event_seed(cfg, mesh),
                              in_specs=(specs, P()), out_specs=specs))


def make_run_to_coverage_fn(cfg: Config, mesh, telemetry: bool = False):
    """Bounded device-side while_loop (base.run_bounded_to_target).  With
    `telemetry`, carries the per-window History inside shard_map with
    replicated specs (see sharded_step.make_run_to_coverage_fn)."""
    step = make_sharded_event_step(cfg, mesh)
    heal = make_sharded_event_heal(cfg, mesh)
    specs = event_state_specs(cfg)
    max_steps = cfg.max_rounds
    # One while iteration = one full 10 ms poll window, the cadence the
    # windowed driver path observes at (see event.poll_window_steps).
    steps = event.poll_window_steps(cfg)
    # Heal-on runs drop the early-death exit (see event.make_run_to_
    # coverage_fn).
    check_in_flight = not cfg.overlay_heal_resolved
    multi = cfg.multi_rumor
    rumors = cfg.rumors
    stream = cfg.traffic == "stream"
    last_inj = cfg.last_inject_tick

    def cond_live(s, target_count, until):
        # The in-flight term (psum of each shard's ring-occupied
        # indicator -- replicated, so every shard agrees) stops the
        # loop the moment the wave dies instead of spinning empty
        # windows until the host-side bounded-call check notices,
        # matching the single-device cond
        # (event.make_run_to_coverage_fn).  Indicator, not count:
        # a cross-shard sum of entry counts could wrap int32 near
        # ring occupancy.
        if multi:
            # Every rumor must hit the target (rumor_recv is
            # replicated; lanes >= R are padding, always 0).
            recv = jnp.min(s.rumor_recv[:rumors])
        else:
            recv = s.total_received
        live = ((recv < target_count)
                & (s.tick < max_steps) & (s.tick < until))
        if check_in_flight:
            alive = jax.lax.psum(event.in_flight(s), AXIS) > 0
            if multi:
                # An empty ring is not death while the injection
                # schedule still has rumors to start -- including tick 0
                # of a oneshot run (last_inj = 0): seeding happens INSIDE
                # the first window step, not before the loop.
                alive = alive | (s.tick <= last_inj)
            live = live & alive
        return live

    def advance(s, base_key):
        s = jax.lax.fori_loop(0, steps, lambda _, x: step(x, base_key), s)
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
        def run_t(st: EventState, base_key, target_count, until, hist):
            def run_shard(st, base_key, target_count, until, hist):
                def cond(carry):
                    s, _ = carry
                    return cond_live(s, target_count, until)

                def body(carry):
                    s, h = carry
                    s = advance(s, base_key)
                    row = telem.gossip_probe(
                        s, sir, psum=lambda x: jax.lax.psum(x, AXIS),
                        pmax=lambda x: jax.lax.pmax(x, AXIS),
                        rumors=rumors if multi else 0,
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
    def run(st: EventState, base_key: jax.Array, target_count: jax.Array,
            until: jax.Array) -> EventState:
        def run_shard(st, base_key, target_count, until):
            return jax.lax.while_loop(
                lambda s: cond_live(s, target_count, until),
                lambda s: advance(s, base_key), st)

        return _shard_map(mesh, run_shard, in_specs=(specs, P(), P(), P()),
                          out_specs=specs)(st, base_key, target_count, until)

    return run
