"""PushSum numeric gossip: mass averaging as a second model family.

SI infection spreads one bit; this model spreads MASS (Kempe-Dobra-Gehrke
PushSum).  Each node carries a value vector x (length ``-pushsum-dim``)
plus a scalar PushSum weight w (init 1).  Every poll window a live node
keeps ~half its (x, w) mass and pushes the other half, split evenly over
its eligible friend edges, through the SAME mail ring / sharded
all_to_all the SI family uses; delivery folds an associative SUM combine
(ops/mailbox.deposit_sum) instead of first-touch-wins OR.  x_i / w_i
then converges to the network mean of the initial values -- churn-
tolerant averaging, the actor-learner-architectures claim (PAPERS.md).

Fixed-point limb representation -- the load-bearing design choice:
  The repo runs with x64 disabled, and float scatter-adds are not
  associative, so float mass would make trajectories depend on delivery
  order (= shard count).  Mass is therefore 64-bit fixed point
  (FRAC_BITS fractional bits) stored as LIMBS x 16-bit limbs in int32
  columns: integer scatter-adds commute, so S=1 and S=8 produce
  BIT-IDENTICAL states and window sums conserve Sigma x, Sigma w exactly
  (the mass-conservation invariant tests/test_pushsum.py pins).  Limbs
  are kept normalized (< 2^16) between windows; deposits may carry each
  limb up to ~2^16 per arrival, so _normalize's fixed carry sweep is
  safe below ~2^15 arrivals per node per window (slot caps sit far
  under that).

Conservation contract: config.validate rejects -droprate/-crashrate for
pushsum (both destroy mass silently).  Scenario faults are fine: a
crashed node PARKS mass -- it still receives deposits, it just stops
emitting -- and partition-blocked edges are excluded from the share
divisor BEFORE the split, so blocked mass simply stays with the sender.

Convergence metric: per-node relative error |x/w - mean| / |mean|
(max over dims), computed in f32 from the limbs -- identical per node
on every shard layout, and max-reduced, so it is order-independent.
relerr_ppb (clamped at 2e9) rides telemetry as the live max over rows
that can still be averaged (crashed and weight-starved rows excluded --
see metric_rel); eps_tick stamps the first window whose eps-band
population reaches the coverage target (the ticks-to-epsilon
Stats/JSONL surface).  A kout overlay carries an ~e^-k tail of
in-degree-0 nodes that no protocol can average -- nothing ever pushes
to them, their own halving drains their weight to dust -- so a strict
global max would pin at that tail's O(1) error forever, the same reason
SI runs use coverage_target < 1.

Shard invariance of emissions: every random draw is (tick, GLOBAL
id)-keyed off the UNFOLDED base key (rng.OP_PUSHSUM), the same
convention as scenario fault draws -- a shard's rows draw exactly what
the single-device run draws.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gossip_simulator_tpu import scenario as _scen
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import event
from gossip_simulator_tpu.models.state import (in_flight, init_exch_counts,
                                               msg64_add, msg64_zero)
from gossip_simulator_tpu.utils import rng as _rng

I32 = jnp.int32
U32 = jnp.uint32

LIMBS = 4  # 16-bit limbs per fixed-point scalar: 64-bit range
LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1
FRAC_BITS = 24  # weight 1.0 == 2**24
VALUE_BITS = 20  # init values are 20-bit hashes (integer part)


class PushSumState(NamedTuple):
    """Numeric-gossip phase-2 state.  Mirrors EventState's mail-ring and
    scenario leaves (the steppers, checkpointing and in_flight duck-type
    on those names); the SI rumor leaves are replaced by the mass columns
    and the convergence scalars."""

    flags: jnp.ndarray  # uint8[n]   CRASHED bit; RECEIVED mirrors "converged"
    friends: jnp.ndarray  # int32[n, k]
    friend_cnt: jnp.ndarray  # int32[n]
    mass: jnp.ndarray  # int32[n, C]  C = (dim+1)*LIMBS, weight block last
    mail_ids: jnp.ndarray  # int32[ring] packed dst*B + tick-offset
    mail_mass: jnp.ndarray  # int32[ring, C] mass companion rows
    mail_cnt: jnp.ndarray  # int32[1, dw]
    sup_cnt: jnp.ndarray  # int32[1, dw] always 0 (in_flight duck-compat)
    tick: jnp.ndarray  # int32[]
    total_message: jnp.ndarray  # uint32[2] msg64
    total_received: jnp.ndarray  # int32[]  count(converged | crashed)
    total_crashed: jnp.ndarray  # int32[]  always 0 (crashrate rejected)
    mail_dropped: jnp.ndarray  # int32[]  must stay 0 (mass loss otherwise)
    exchange_overflow: jnp.ndarray  # int32[]
    down_since: jnp.ndarray  # int32[n or 1] crash clock (scenario)
    scen_crashed: jnp.ndarray  # int32[]
    scen_recovered: jnp.ndarray  # int32[]
    part_dropped: jnp.ndarray  # int32[]
    heal_repaired: jnp.ndarray  # int32[]
    relerr_ppb: jnp.ndarray  # int32[]  last window's live max rel-err, ppb
    eps_tick: jnp.ndarray  # int32[]  first tick with eps-band count >= target; -1
    # Spatial-telemetry routed-exchange counters (state.init_exch_counts;
    # 1x1 placeholder unless the panels record under S > 1 shards).
    exch_counts: jnp.ndarray  # int32[1, S+2 | 1x1]


# --- geometry ----------------------------------------------------------------
# Window cadence and ring slot layout are the event engine's (B-tick
# windows, dw slots); only the per-slot capacity differs -- pushsum must
# not drop entries (dropped mail is destroyed mass), so the cap is sized
# for the emission volume, not the SI duplicate volume.

batch_ticks = event.batch_ticks
ring_windows = event.ring_windows


def mass_cols(cfg: Config) -> int:
    """int32 columns per node: dim value blocks + 1 weight block."""
    return (cfg.pushsum_dim + 1) * LIMBS


def _src_windows(cfg: Config) -> int:
    """How many distinct emission windows can land in one ring slot: the
    delay span [max(1, delaylow), delayhigh) mapped to window indices."""
    b = batch_ticks(cfg)
    dlow = max(1, cfg.delaylow)
    return max(1, (cfg.delayhigh - 1) // b - dlow // b + 1)


def slot_cap(cfg: Config, n_local: int | None = None) -> int:
    """Per-slot mail capacity.  Every window each live node emits <= k
    lanes, so `n*k*src_windows` is the adversarial zero-loss bound; it is
    clamped to 2*n*k (~8x the uniform-delay expectation n*k/src_windows)
    because the worst case needs every delay draw to agree -- mail_dropped
    stays the audited safety valve (tests assert it is 0)."""
    n = int(n_local) if n_local is not None else cfg.n
    k = cfg.graph_width
    dw = ring_windows(cfg, n_local)
    if cfg.event_slot_cap > 0:
        cap = int(cfg.event_slot_cap)
    else:
        worst = n * k * _src_windows(cfg)
        cap = max(4096, min(worst, 2 * n * k))
    # Flat int32 indexing bound: dw*cap + tail must stay addressable.
    lim = (2 ** 31 - 1 - event._chunk_want(cfg, n_local)) // max(dw, 1)
    return max(256, min(cap, lim))


def drain_chunk(cfg: Config, n_local: int | None = None) -> int:
    return min(slot_cap(cfg, n_local), event._chunk_want(cfg, n_local))


def ring_tail(cfg: Config, n_local: int | None = None) -> int:
    """Slack past the last slot: covers the drain's final dynamic_slice
    window and the append trash cell at flat index dw*cap."""
    return drain_chunk(cfg, n_local)


def ring_len(cfg: Config, n_local: int | None = None) -> int:
    return (ring_windows(cfg, n_local) * slot_cap(cfg, n_local)
            + ring_tail(cfg, n_local))


# --- fixed-point limb arithmetic --------------------------------------------

def _normalize(m3):
    """Carry sweep on (..., LIMBS) int32 limbs.  LIMBS passes reduce any
    post-deposit accumulation (each limb < 2^31) back below 2^16; the top
    limb's carry-out is unreachable by the headroom argument in the module
    docstring."""
    for _ in range(LIMBS):
        carry = m3 >> LIMB_BITS
        m3 = (m3 & LIMB_MASK) + jnp.concatenate(
            [jnp.zeros_like(carry[..., :1]), carry[..., :-1]], axis=-1)
    return m3


def _halve(m3):
    """floor(v/2) on normalized limbs; returns (half, odd) with
    half + half + odd == v (odd is the dropped low bit, shape (...,))."""
    up = jnp.concatenate(
        [m3[..., 1:] & 1, jnp.zeros_like(m3[..., :1])], axis=-1)
    half = (m3 >> 1) | (up << (LIMB_BITS - 1))
    return half, m3[..., 0] & 1


def _div_rows(m3, m):
    """Long division of normalized limbs (n, G, LIMBS) by per-row divisor
    m (n,), high limb first: returns (quotient limbs, remainder (n, G))
    with q*m + r == v exactly.  Safe for m <= 32767 (r*2^16 + limb fits
    int32); graph widths sit far below that."""
    mm = m[:, None]
    r = jnp.zeros(m3.shape[:-1], I32)
    qs = []
    for i in range(LIMBS - 1, -1, -1):
        cur = r * (LIMB_MASK + 1) + m3[..., i]
        q = cur // mm
        r = cur - q * mm
        qs.append(q)
    qs.reverse()
    return jnp.stack(qs, axis=-1), r


_SCALE = tuple(float(2.0 ** (LIMB_BITS * i - FRAC_BITS))
               for i in range(LIMBS))


def _to_float(m3):
    """f32 value of (..., LIMBS) limbs.  Same fixed 4-term reduction on
    every shard layout, so the metric is shard-invariant."""
    return (m3.astype(jnp.float32)
            * jnp.asarray(_SCALE, jnp.float32)).sum(axis=-1)


# --- init values -------------------------------------------------------------
# Per-(seed, gid, dim) 20-bit hash values, implemented twice with
# identical uint32 wraparound semantics: jnp for device init (shard rows
# draw their slice), numpy for the host-side exact true mean.

def _mix32_np(x):
    x = x.astype(np.uint32).copy()
    x ^= x >> np.uint32(16)
    x = x * np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x = x * np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def _mix32_jnp(x):
    x = x ^ (x >> U32(16))
    x = x * U32(0x7FEB352D)
    x = x ^ (x >> U32(15))
    x = x * U32(0x846CA68B)
    return x ^ (x >> U32(16))


def _values_q_host(seed: int, n: int, dim: int) -> np.ndarray:
    """(n, dim) int64 of 20-bit init values q (fixed-point x = q * 2^24)."""
    gid = np.arange(n, dtype=np.uint32)[:, None]
    d = np.arange(dim, dtype=np.uint32)[None, :]
    h = _mix32_np(np.uint32(seed) ^ (gid * np.uint32(0x9E3779B9)))
    h = _mix32_np(h ^ ((d + np.uint32(1)) * np.uint32(0x85EBCA6B)))
    return (h >> np.uint32(32 - VALUE_BITS)).astype(np.int64)


def _values_q_jnp(seed: int, gid, dim: int):
    """(rows, dim) int32 of the same q values for global ids `gid`."""
    g = gid.astype(U32)[:, None]
    d = jnp.arange(dim, dtype=U32)[None, :]
    h = _mix32_jnp(U32(seed) ^ (g * U32(0x9E3779B9)))
    h = _mix32_jnp(h ^ ((d + U32(1)) * U32(0x85EBCA6B)))
    return (h >> U32(32 - VALUE_BITS)).astype(I32)


@functools.lru_cache(maxsize=None)
def _true_means(n: int, dim: int, seed: int) -> tuple:
    sums = _values_q_host(seed, n, dim).sum(axis=0)  # exact int64
    return tuple(float(s) / float(n) for s in sums)


def true_means(cfg: Config) -> tuple:
    """Exact network means of the init values (integer q units) -- baked
    into the metric as compile-time constants."""
    return _true_means(cfg.n, cfg.pushsum_dim, cfg.seed)


def eps_target(cfg: Config) -> int:
    """Eps-band node count at which eps_tick stamps -- the same formula
    the driver's run loop converges on (backends/base.py)."""
    return int(np.ceil(cfg.coverage_target * cfg.n))


def init_mass(cfg: Config, gid0, rows: int):
    """(rows, C) int32 initial mass for global ids [gid0, gid0+rows):
    value blocks q*2^24, weight block 1.0 = 2^24."""
    gid = jnp.asarray(gid0, I32) + jnp.arange(rows, dtype=I32)
    q = _values_q_jnp(cfg.seed, gid, cfg.pushsum_dim)  # (rows, D)
    # q * 2^24 in 16-bit limbs: bits 24..43 -> limb1 low byte + limb2.
    vl = jnp.stack([jnp.zeros_like(q), (q & 0xFF) << 8,
                    (q >> 8) & LIMB_MASK, jnp.zeros_like(q)], axis=-1)
    wl = jnp.zeros((rows, 1, LIMBS), I32).at[:, :, 1].set(1 << (FRAC_BITS
                                                                - LIMB_BITS))
    return jnp.concatenate([vl, wl], axis=1).reshape(rows, mass_cols(cfg))


# --- state -------------------------------------------------------------------

def init_state(cfg: Config, friends: jnp.ndarray, friend_cnt: jnp.ndarray,
               gid0=0, n_shards: int = 1) -> PushSumState:
    n = friends.shape[0]  # local rows: the shard slice under sharded
    z = lambda: jnp.zeros((), I32)  # noqa: E731
    dw = ring_windows(cfg, n)
    return PushSumState(
        flags=jnp.zeros((n,), jnp.uint8),
        friends=friends,
        friend_cnt=friend_cnt,
        mass=init_mass(cfg, gid0, n),
        mail_ids=jnp.zeros((ring_len(cfg, n),), I32),
        mail_mass=jnp.zeros((ring_len(cfg, n), mass_cols(cfg)), I32),
        mail_cnt=jnp.zeros((1, dw), I32),
        sup_cnt=jnp.zeros((1, dw), I32),
        tick=z(), total_message=msg64_zero(), total_received=z(),
        total_crashed=z(), mail_dropped=z(), exchange_overflow=z(),
        down_since=_scen.init_down_since(cfg.faults_enabled, n),
        scen_crashed=z(), scen_recovered=z(), part_dropped=z(),
        heal_repaired=z(),
        relerr_ppb=jnp.full((), 2_000_000_000, I32),
        eps_tick=jnp.full((), -1, I32),
        exch_counts=init_exch_counts(cfg, n_shards),
    )


# --- shared cores (single-device step and the sharded engine both call) -----

STARVE_BITS = 10  # weight < 2^-10: the node is cut off from the mix


def metric_rel(cfg: Config, m3, crashed):
    """Per-node relative error vs the true mean, f32, max over dims,
    clamped to 2.0; crashed rows report 0 (parked mass is 'done' -- the
    convergence count and the live max both want them excluded).

    Returns ``(rel, rep)``.  ``rel`` drives the converged count: a
    weight-STARVED row (an in-degree-0 node, or one walled off by a
    partition, halves its own weight every window with nothing coming
    back) keeps its honest O(1) error and never counts converged.
    ``rep`` is ``rel`` with starved rows zeroed -- the telemetry max
    tracks the population that CAN still be averaged, so relerr_ppb
    actually descends into the eps band instead of pinning at the
    unreachable tail's error (the same reason SI runs use
    coverage_target < 1 on a kout overlay)."""
    dim = cfg.pushsum_dim
    vals = _to_float(m3[:, :dim, :])  # (n, D)
    w_raw = _to_float(m3[:, dim, :])
    w = jnp.maximum(w_raw, jnp.float32(2.0 ** -FRAC_BITS))
    means = jnp.maximum(jnp.abs(jnp.asarray(true_means(cfg), jnp.float32)),
                        jnp.float32(1e-6))
    est = vals / w[:, None]
    rel = (jnp.abs(est - jnp.asarray(true_means(cfg), jnp.float32)[None, :])
           / means[None, :]).max(axis=1)
    rel = jnp.where(crashed, jnp.float32(0.0),
                    jnp.minimum(rel, jnp.float32(2.0)))
    rep = jnp.where(w_raw < jnp.float32(2.0 ** -STARVE_BITS),
                    jnp.float32(0.0), rel)
    return rel, rep


def emit_shares(cfg: Config, m3, crashed, friends, friend_cnt, tick, gids,
                base_key):
    """The PushSum emission: halve, split over eligible edges, return the
    lanes for the engine glue to deliver (append locally or route).

    Eligible edge = in-range, non-padding, sender live, not partition-
    blocked AT SEND TIME -- blocked/dead lanes are excluded BEFORE the
    divisor so their mass share never leaves the sender.  Crashed
    DESTINATIONS still receive (parked mass).  Returns
    (new_m3, share_lanes (n*k, C), dst (n*k,), wslot (n*k,),
    off (n*k,), lane_valid (n*k,), blocked_count)."""
    n, k = friends.shape
    b = batch_ticks(cfg)
    dw = ring_windows(cfg)
    scen = cfg.scenario_resolved
    in_range = jnp.arange(k, dtype=I32)[None, :] < friend_cnt[:, None]
    edge = in_range & (friends >= 0) & ~crashed[:, None]
    blk = jnp.zeros((), I32)
    if scen.has_partitions:
        blocked = _scen.partition_blocked(
            scen, cfg.n, tick, gids[:, None], friends) & edge
        blk = blocked.sum(dtype=I32)
        edge = edge & ~blocked
    mdeg = edge.sum(axis=1, dtype=I32)
    emit = ~crashed & (mdeg > 0)
    half, odd = _halve(m3)
    share, rem = _div_rows(half, jnp.maximum(mdeg, 1))
    # kept = ceil(v/2) + division remainder: v == kept + mdeg*share exactly.
    kept = half.at[..., 0].add(odd + rem)
    new_m3 = jnp.where(emit[:, None, None], kept, m3)
    C = m3.shape[1] * LIMBS
    share_lanes = jnp.broadcast_to(
        jnp.where(emit[:, None, None], share, 0).reshape(n, 1, C),
        (n, k, C)).reshape(n * k, C)
    # One shared delay per sender, (tick, GLOBAL id)-keyed off the
    # UNFOLDED base key: shard-count invariant.  batch_ticks guarantees
    # b <= max(1, delaylow), so arrival always lands in a LATER window
    # than the emitting one (its slot is already drained this window).
    tk = _rng.tick_key(base_key, tick, _rng.OP_PUSHSUM)
    delay = _rng.row_uniform_delay(tk, cfg.delaylow, cfg.delayhigh, gids)
    arrive = tick + delay
    wslot = jnp.broadcast_to(((arrive // b) % dw)[:, None], (n, k))
    off = jnp.broadcast_to((arrive % b)[:, None], (n, k))
    lane_valid = (edge & emit[:, None]).reshape(-1)
    dst = jnp.where(edge, friends, 0).reshape(-1)
    return (new_m3, share_lanes, dst, wslot.reshape(-1), off.reshape(-1),
            lane_valid, blk)


# --- single-device engine ----------------------------------------------------

def make_window_step_fn(cfg: Config, n_local: int | None = None):
    """One B-tick window: scenario faults -> drain this window's slot with
    the SUM combine -> normalize -> convergence metric -> emission."""
    from gossip_simulator_tpu.ops.mailbox import deposit_sum, ring_append

    b = batch_ticks(cfg)
    dw = ring_windows(cfg)
    cap = slot_cap(cfg, n_local)
    ccap = drain_chunk(cfg, n_local)
    dim = cfg.pushsum_dim
    C = mass_cols(cfg)
    eps = float(cfg.pushsum_eps)
    tgt = eps_target(cfg)
    dkern = cfg.deliver_kernel_resolved

    def step_fn(st: PushSumState, base_key: jax.Array) -> PushSumState:
        n, k = st.friends.shape
        gids = jnp.arange(n, dtype=I32)
        slot = (st.tick // b) % dw
        flags, down, dsc, dsr = event.apply_fault_window_flags(
            cfg, st.flags, st.down_since, st.tick, gids, base_key, b)
        # Drain: sum-deposit every entry due this window.  The packed
        # tick offset (ent % b) orders SI deliveries within the window;
        # sums commute, so only the destination row matters here.
        m = st.mail_cnt[0, slot]
        chunks = (m + ccap - 1) // ccap

        def body(j, acc):
            off0 = slot * cap + j * ccap
            ent = jax.lax.dynamic_slice(st.mail_ids, (off0,), (ccap,))
            rows = jax.lax.dynamic_slice(
                st.mail_mass, (off0, 0), (ccap, C))
            ok = j * ccap + jnp.arange(ccap, dtype=I32) < m
            return deposit_sum(acc, ent // b, rows, ok, kernel=dkern)

        mass = jax.lax.fori_loop(0, chunks, body, st.mass)
        m3 = _normalize(mass.reshape(n, dim + 1, LIMBS))
        crashed = (flags & event.CRASHED) > 0
        rel, rep = metric_rel(cfg, m3, crashed)
        conv = rel <= jnp.float32(eps)
        # RECEIVED mirrors "currently within eps" so the telemetry
        # received column and SI-shaped probes stay meaningful.
        flags = jnp.where(conv, flags | event.RECEIVED,
                          flags & ~event.RECEIVED)
        maxrel = rep.max()
        recv = conv.sum(dtype=I32)
        new_tick = st.tick + b
        eps_tick = jnp.where(
            (st.eps_tick < 0) & (recv >= tgt), new_tick, st.eps_tick)
        new_m3, share, dst, wslot, off, lane_valid, blk = emit_shares(
            cfg, m3, crashed, st.friends, st.friend_cnt, st.tick, gids,
            base_key)
        (mail, mailm), cnt, dropped = ring_append(
            (st.mail_ids, st.mail_mass), st.mail_cnt, st.mail_dropped,
            (dst * b + off, share), wslot, lane_valid, dw, cap,
            kernel=dkern)
        cnt = cnt.at[0, slot].set(0)
        return st._replace(
            flags=flags, down_since=down,
            mass=new_m3.reshape(n, C),
            mail_ids=mail, mail_mass=mailm, mail_cnt=cnt,
            mail_dropped=dropped, tick=new_tick,
            total_message=msg64_add(st.total_message,
                                    lane_valid.sum(dtype=I32)),
            total_received=recv,
            scen_crashed=st.scen_crashed + dsc,
            scen_recovered=st.scen_recovered + dsr,
            part_dropped=st.part_dropped + blk,
            relerr_ppb=(maxrel * jnp.float32(1e9)).astype(I32),
            eps_tick=eps_tick)

    return step_fn


def make_seed_fn(cfg: Config):
    """No-op: pushsum has no rumor injection -- every node's mass exists
    from init and the first window step starts the exchange."""

    def seed_fn(st: PushSumState, base_key: jax.Array) -> PushSumState:
        return st

    return seed_fn


def make_heal_fn(cfg: Config, n_local: int | None = None):
    """Rejoin bookkeeping only (None when heal is off).  The SI heal's
    three waves are ALL deliberately inert for pushsum:

    - edge REPAIR would rewire in-edges away from a temporarily-down node
      permanently: when it reboots nobody pushes to it any more, its own
      emissions halve its (value, weight) down to integer dust and its
      estimate strands at O(1) error even though conservation holds
      (observed as a growing plateau of never-converged nodes under the
      churn timeline).  Parked mass plus the UNCHANGED topology is the
      averaging model's own healing mechanism: mail keeps depositing into
      a crashed node, and on reboot the node pushes the parked mass back
      through the same edges.
    - RE-SEND/PULL waves would emit extra mass and break conservation.

    What remains is consuming the reboot markers apply_fault_window_flags
    leaves in down_since, so detect-dead clocks restart cleanly across
    repeated churn reboots."""
    if not cfg.overlay_heal_resolved:
        return None

    def heal_fn(st: PushSumState, base_key: jax.Array) -> PushSumState:
        clear = _scen.rejoined_mask(st.down_since)
        return st._replace(down_since=jnp.where(clear, -1, st.down_since))

    return heal_fn


def make_window_fn(cfg: Config, window: int):
    step = make_window_step_fn(cfg)
    heal = make_heal_fn(cfg)
    steps = max(1, -(-window // batch_ticks(cfg)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def window_fn(st: PushSumState, base_key: jax.Array) -> PushSumState:
        st = jax.lax.fori_loop(0, steps, lambda _, s: step(s, base_key), st)
        if heal is not None:
            st = heal(st, base_key)
        return st

    return window_fn


def make_run_to_coverage_fn(cfg: Config, telemetry: bool = False):
    """Bounded device-side while_loop to the convergence target, same
    contract as event.make_run_to_coverage_fn.  total_received counts
    converged-or-crashed nodes, so coverage_target means "fraction of
    nodes within eps"."""
    step = make_window_step_fn(cfg)
    heal = make_heal_fn(cfg)
    max_steps = cfg.max_rounds
    steps = event.poll_window_steps(cfg)
    b = batch_ticks(cfg)
    check_in_flight = not cfg.overlay_heal_resolved

    def cond_live(s: PushSumState, target_count, until):
        recv = s.total_received
        live = ((recv < target_count)
                & (s.tick < max_steps) & (s.tick < until))
        if check_in_flight:
            # The ring is empty BEFORE the first emission (seed is a
            # no-op), so the aliveness term only applies past window 0.
            live = live & ((in_flight(s) > 0) | (s.tick < b))
        return live

    def run_window(s: PushSumState, base_key):
        s = jax.lax.fori_loop(0, steps, lambda _, x: step(x, base_key), s)
        if heal is not None:
            s = heal(s, base_key)
        return s

    if telemetry:
        from gossip_simulator_tpu.utils import telemetry as telem

        spatial = telem.spatial_spec(cfg)

        @functools.partial(jax.jit, donate_argnums=(0, 4))
        def run_fn_t(st: PushSumState, base_key: jax.Array,
                     target_count: jax.Array, until: jax.Array,
                     hist: "telem.History"):
            def cond(carry):
                s, _ = carry
                return cond_live(s, target_count, until)

            def body(carry):
                s, h = carry
                s = run_window(s, base_key)
                row = telem.gossip_probe(s, False, relerr=s.relerr_ppb)
                return s, telem.record_window(h, row, st=s, spec=spatial,
                                              relerr=s.relerr_ppb)

            return jax.lax.while_loop(cond, body, (st, hist))

        return run_fn_t

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_fn(st: PushSumState, base_key: jax.Array,
               target_count: jax.Array, until: jax.Array) -> PushSumState:
        def cond(s: PushSumState):
            return cond_live(s, target_count, until)

        return jax.lax.while_loop(cond, lambda s: run_window(s, base_key),
                                  st)

    return run_fn


# --- host-side reporting -----------------------------------------------------

def report(stepper) -> dict:
    """The pushsum result-record payload (driver JSONL): whether the live
    max relative error reached eps, the tick it first did, and the final
    window's max error in ppb."""
    st = stepper.state
    rp, et = (int(v) for v in np.asarray(
        jax.device_get((st.relerr_ppb, st.eps_tick))))
    return {"converged_eps": et >= 0, "eps_ticks": et, "relerr_ppb": rp}
