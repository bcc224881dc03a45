"""Phase-1 speed-round parity pins (round 7).

Each ISSUE-4 gate gets an A/B bit-identity pin at CPU-feasible n by
lowering the production band constants (the house pattern of
test_column_delivery_band_small_n_golden / test_slotmajor_band_small_n):

* occupancy-adaptive hosted-chunk schedule (config.overlay_adaptive_chunks
  + ops.mailbox.make_hosted_column_delivery width ladder) -- trajectory-
  neutral by the compact_chunk contract, pinned on/off identical;
* dead-emission-row skip (config.overlay_dead_skip: emission counts
  recorded at write time, consumed as hosted row_totals + the scalar
  quiescence flag) -- trajectory-neutral, pinned on/off identical;
* one-shot static bootstrap (config.overlay_static_boot) -- a
  deterministic re-choice of the bootstrap schedule above the band
  (closer to the reference's no-delay needNewFriend re-arm); "off"
  reproduces the pre-round-7 trajectory exactly, "on" is golden-pinned
  here and bit-identical between the fused and split rounds;
* the ticks overlay's overflow spill (overlay_ticks.SPILL_CAP) --
  delayed-never-lost at the cap-8 band, mirroring the rounds spill suite
  (tests/test_mailbox.py::test_spill_makes_overflow_lossless);
* the prefix-dense drain delivery (overlay_ticks.PREFIX_DRAIN) --
  trajectory-neutral, pinned on/off identical.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import gossip_simulator_tpu.config as config_mod
import gossip_simulator_tpu.models.overlay as ov
import gossip_simulator_tpu.models.overlay_ticks as ot
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.driver import run_simulation
from gossip_simulator_tpu.utils.metrics import ProgressPrinter

ROUNDS = dict(n=3000, graph="overlay", overlay_mode="rounds", fanout=5,
              seed=9, backend="jax", progress=False, coverage_target=0.9)
TICKS = dict(n=2000, graph="overlay", overlay_mode="ticks", backend="jax",
             fanout=5, seed=9, progress=False, coverage_target=0.9)


def _run(**kw):
    return run_simulation(Config(**kw).validate(),
                          printer=ProgressPrinter(False))


def _same(a, b):
    assert a.stats == b.stats
    assert a.stabilize_ms == b.stabilize_ms
    assert a.overlay_windows == b.overlay_windows


# --- gate resolution / sizing pins ----------------------------------------

def test_gate_config_surface():
    c = Config(**ROUNDS).validate()
    assert c.overlay_adaptive_chunks_resolved
    assert c.overlay_dead_skip_resolved
    assert not c.static_boot_for(c.n)  # below the band
    assert Config(n=100_000_000).static_boot_for(100_000_000)
    assert Config(
        n=100_000_000,
        overlay_static_boot="off").static_boot_for(100_000_000) is False
    assert Config(n=3000, overlay_static_boot="on").static_boot_for(3000)
    with pytest.raises(ValueError, match="overlay_static_boot"):
        Config(overlay_static_boot="maybe").validate()
    with pytest.raises(ValueError, match="overlay_adaptive_chunks"):
        Config(overlay_adaptive_chunks="x").validate()


def test_hosted_chunk_ladder_shape():
    """Ladder: x4 rungs from the swept base to ADAPTIVE_CHUNK_MAX; 'off'
    pins the single pre-round-7 width (the A/B baseline)."""
    cfg = Config(n=100_000_000)
    widths = ov.hosted_chunk_widths(cfg, cfg.n)
    assert widths[0] == ov.delivery_chunk(cfg, cfg.n) == 781_250
    assert widths[-1] == ov.ADAPTIVE_CHUNK_MAX
    assert all(b == min(a * 4, ov.ADAPTIVE_CHUNK_MAX)
               for a, b in zip(widths, widths[1:]))
    off = Config(n=100_000_000, overlay_adaptive_chunks="off")
    assert ov.hosted_chunk_widths(off, off.n) == (781_250,)


def test_ticks_auto_band_raised_to_10m():
    """VERDICT r5 #3: -overlay-mode auto gives the true per-message clock
    up to 10M (the prefix-dense drain pays for the raise; README table)."""
    assert config_mod.OVERLAY_TICKS_AUTO_MAX == 10_000_000
    assert Config(n=10_000_000).overlay_mode_resolved == "ticks"
    assert Config(n=10_000_001).overlay_mode_resolved == "rounds"


def test_static_boot_requires_key():
    with pytest.raises(ValueError, match="base_key"):
        ov.init_state(Config(**ROUNDS, overlay_static_boot="on").validate())


# --- trajectory-neutral gates: on/off bit-identity ------------------------

def test_adaptive_chunks_bit_identical(monkeypatch):
    """Hosted split rounds with the multi-rung ladder == single fixed
    chunk (compact_chunk=256 at n=3000 gives a 3-rung ladder and genuine
    multi-chunk rows)."""
    monkeypatch.setattr(ov, "SPLIT_ROUND_MIN_ROWS", 0)
    kw = {**ROUNDS, "compact_chunk": 256}
    assert len(ov.hosted_chunk_widths(
        Config(**kw).validate(), 3000)) > 1
    on = _run(**kw, overlay_adaptive_chunks="on")
    off = _run(**kw, overlay_adaptive_chunks="off")
    _same(on, off)


def test_dead_skip_bit_identical(monkeypatch):
    """Split rounds with emission-count row skipping + scalar quiescence
    == the popcount/eager-reduction path, including the window count (the
    counts-quiescence must fire on exactly the same round)."""
    monkeypatch.setattr(ov, "SPLIT_ROUND_MIN_ROWS", 0)
    on = _run(**ROUNDS, overlay_dead_skip="on")
    off = _run(**ROUNDS, overlay_dead_skip="off")
    _same(on, off)


def test_split_round_identical_to_fused_all_gates(monkeypatch):
    """The round-7 split round (ladder + dead skip + static boot all ON)
    must still be bit-identical to the fused round with static boot on --
    the split/fused seam moved, the trajectory must not."""
    kw = {**ROUNDS, "overlay_static_boot": "on"}
    fused = _run(**kw)
    monkeypatch.setattr(ov, "SPLIT_ROUND_MIN_ROWS", 0)
    split = _run(**kw)
    _same(fused, split)


# --- static bootstrap: off == pre-PR, on == pinned band trajectory --------

def test_static_boot_off_matches_default_below_band():
    """'off' and the auto default below the band are the SAME pre-round-7
    staggered schedule (pinned totals match
    test_column_delivery_band_small_n_golden's re-pin lineage)."""
    off = _run(**ROUNDS, overlay_static_boot="off")
    default = _run(**ROUNDS)
    _same(off, default)
    assert default.stats.total_message == 8394
    assert default.stats.total_received == 2883


def test_static_boot_on_pinned_trajectory(monkeypatch):
    """The burst schedule's own golden: explicit 'on' == lowered auto
    band, quiesces with full degree bounds and zero drops, and every
    node starts AT fanout (the invariant that lets the round skip the
    bootstrap block exactly)."""
    on = _run(**ROUNDS, overlay_static_boot="on")
    assert on.overlay_windows == 16
    assert on.stabilize_ms == 240.0
    assert on.stats.total_received == 2873
    assert on.stats.total_message == 8172
    assert on.stats.mailbox_dropped == 0
    monkeypatch.setattr(config_mod, "OVERLAY_STATIC_BOOT_MIN_ROWS", 0)
    auto = _run(**ROUNDS)
    _same(on, auto)


def test_static_boot_init_state_invariants():
    """init_state's burst: cnt == fanout everywhere, friends[:, :f] the
    self-patched draws, the first f emission rows exactly the friends
    columns (the staged n*fanout burst), the rest empty."""
    from gossip_simulator_tpu.utils import rng as _rng

    cfg = Config(**ROUNDS, overlay_static_boot="on").validate()
    st = ov.init_state(cfg, base_key=_rng.base_key(cfg.seed))
    f = cfg.fanout
    cnt = np.asarray(st.friend_cnt)
    fr = np.asarray(st.friends)
    mk = np.asarray(st.mk_dst)
    assert (cnt == f).all()
    assert (fr[:, :f] >= 0).all() and (fr[:, :f] < cfg.n).all()
    assert (fr[:, :f] != np.arange(cfg.n)[:, None]).all()  # self-patched
    for j in range(f):
        np.testing.assert_array_equal(mk[j], fr[:, j])
    assert (mk[f:] == -1).all()
    assert (np.asarray(st.boot_dst) == -1).all()


def test_static_boot_burst_spill_lossless(monkeypatch):
    """The one-shot burst concentrates round-1 in-degree at
    Poisson(fanout) -- at the cap-8 band that is E[(X-8)+] ~ 0.12
    overflow messages PER NODE in one round (~12M at 1e8, vs the 257
    total the staggered schedule ever overflowed), so the band's spill
    is burst-sized (overlay.spill_cap_for).  The 100M acceptance shape,
    scaled: split path + forced cap 8 + static boot ends
    mailbox_dropped=0 with full degree bounds."""
    import jax

    from gossip_simulator_tpu.backends.jax_backend import JaxStepper

    monkeypatch.setattr(ov, "SPLIT_ROUND_MIN_ROWS", 0)
    monkeypatch.setattr(config_mod, "MAILBOX_CAP_MEMORY_BAND", 1000)
    cfg = Config(n=50_000, graph="overlay", overlay_mode="rounds",
                 backend="jax", seed=0, progress=False,
                 overlay_static_boot="on").validate()
    assert cfg.mailbox_cap_resolved == 8
    # Burst-sized: floor + 1.6 * n * E[(Poisson(fanout) - cap)+].
    assert ov.spill_cap_for(cfg, cfg.n) == 65_536 + int(
        1.6 * cfg.n * ov._poisson_excess(float(cfg.fanout), 8))
    s = JaxStepper(cfg)
    s.init()
    windows, q = s.overlay_run_to_quiescence(20_000)
    assert bool(q)
    assert s._mailbox_dropped == 0
    cnt = np.asarray(jax.device_get(s.state.friend_cnt))
    assert (cnt >= cfg.fanout).all()
    assert (cnt <= cfg.max_degree).all()


# --- ticks overlay: spill suite (mirrors the rounds spill suite) ----------

def _band_ticks(monkeypatch):
    monkeypatch.setattr(ot, "TICKS_SLOTMAJOR_MIN_ROWS", 1000)
    monkeypatch.setattr(config_mod, "MAILBOX_CAP_MEMORY_BAND", 1000)


def test_ticks_spill_makes_overflow_lossless(monkeypatch):
    """VERDICT r5 #4: mailbox overflow at the ticks overlay's cap-8 band
    spills (pay, key) pairs re-delivered next window -- delayed, never
    lost (simulator.go:51-54).  The SPILL_CAP=0 control proves the shape
    genuinely overflows (239 counted drops on this host); with the spill
    the same build ends with ZERO drops and a full overlay."""
    import jax

    from gossip_simulator_tpu.backends.jax_backend import JaxStepper

    _band_ticks(monkeypatch)
    cfg = Config(**TICKS).validate()
    # Control: spill disabled -> overflow falls through to counted drops.
    monkeypatch.setattr(ot, "SPILL_CAP", 0)
    ctl = JaxStepper(cfg)
    ctl.init()
    w_ctl, q_ctl = ctl.overlay_run_to_quiescence(20_000)
    assert bool(q_ctl) and ctl._mailbox_dropped > 0
    monkeypatch.setattr(ot, "SPILL_CAP", 65_536)
    s = JaxStepper(cfg)
    s.init()
    windows, q = s.overlay_run_to_quiescence(20_000)
    assert bool(q)
    assert s._mailbox_dropped == 0
    cnt = np.asarray(jax.device_get(s.state.friend_cnt))
    assert (cnt >= cfg.fanout).all()
    assert (cnt <= cfg.max_degree).all()


def test_ticks_spill_windowed_matches_fast_path(monkeypatch):
    """The spill rides the state, so the windowed host loop and the
    bounded device loop must agree through overflow exactly (the
    fast-path parity matrix of test_overlay_ticks, at the spill band)."""
    import jax

    from gossip_simulator_tpu.backends.jax_backend import JaxStepper

    _band_ticks(monkeypatch)
    cfg = Config(**TICKS).validate()

    def run(fast):
        s = JaxStepper(cfg)
        s.init()
        if fast:
            windows, q = s.overlay_run_to_quiescence(3000, budget=4)
        else:
            windows, q = 0, False
            for _ in range(3000):
                _, _, q = s.overlay_window()
                windows += 1
                if q:
                    break
        assert q
        return (windows, s.sim_time_ms(), s._mailbox_dropped,
                np.asarray(jax.device_get(s.state.friends)))

    wf, tf, df, ff = run(True)
    ww, tw, dw_, fw = run(False)
    assert (wf, tf, df) == (ww, tw, dw_)
    np.testing.assert_array_equal(ff, fw)


def test_ticks_spill_disabled_outside_band():
    """Full-cap configs keep the token spill (threading a live
    accumulator at cap 16 costs pure op floors -- overlay.spill_enabled's
    rationale); the default small-n state carries the (2, 1) token."""
    from gossip_simulator_tpu.utils import rng as _rng

    cfg = Config(**TICKS).validate()
    assert ot.ticks_spill_cap(cfg) == 0
    st = ot.init_state(cfg, _rng.base_key(cfg.seed))
    assert st.spill.shape == (2, 1)


def test_prefix_drain_identical(monkeypatch):
    """The prefix-dense drain delivery (no compaction scans) must be
    bit-identical to the masked chunked form: forcing a small
    compact_chunk engages the chunked path at test n, and the drained
    prefix contract (stable toff sort packs live entries first) makes
    the two index streams identical."""
    kw = {**TICKS, "compact_chunk": 512}
    monkeypatch.setattr(ot, "PREFIX_DRAIN", False)
    masked = _run(**kw)
    monkeypatch.setattr(ot, "PREFIX_DRAIN", True)
    prefix = _run(**kw)
    _same(masked, prefix)


def test_deliver_pair_prefix_and_spill_unit():
    """deliver_pair(prefix_len=...) == the masked chunked form == the
    single-pass form on a prefix-valid stream, and the spill return
    splits overflow exactly at the accumulator capacity."""
    from gossip_simulator_tpu.ops.mailbox import deliver_pair

    rng = np.random.default_rng(31)
    n, cap, m, live = 120, 2, 3000, 2201
    src = jnp.asarray(rng.integers(0, 4000, m).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    typ = jnp.asarray(rng.integers(0, 2, m).astype(np.int32))
    ev = jnp.asarray(np.arange(m) < live)
    ref = deliver_pair(src, dst, typ, ev, n, cap, flat=True)
    for chunk in (256, 4096):
        got = deliver_pair(src, dst, typ, ev, n, cap, compact_chunk=chunk,
                           flat=True, prefix_len=jnp.int32(live))
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Spill: mailbox cells identical, drops move into the pair list.
    scap = 64
    acc = (jnp.full((2, scap + 1), -1, jnp.int32),
           jnp.zeros((), jnp.int32))
    mbox, l0, l1, dropped, (pairs, cnt) = deliver_pair(
        src, dst, typ, ev, n, cap, compact_chunk=256, flat=True,
        prefix_len=jnp.int32(live), spill_in=None, spill=acc)
    np.testing.assert_array_equal(np.asarray(mbox), np.asarray(ref[0]))
    assert int(dropped) + int(cnt) == int(ref[3])
    assert int(cnt) == min(scap, int(ref[3]))


def test_hosted_row_totals_and_ladder_unit():
    """make_hosted_column_delivery: a multi-rung ladder with exact
    caller-supplied row totals == the fixed-width popcount form, across
    sparse / dense / empty rows (the dead-skip + adaptive-schedule unit
    seam)."""
    from gossip_simulator_tpu.ops.mailbox import (
        deliver_columns, make_hosted_column_delivery)

    rng = np.random.default_rng(41)
    n, cap, chunk = 700, 3, 64
    rows = [
        np.where(rng.random(n) < 0.3, rng.integers(0, n, n), -1),
        rng.integers(0, n, n),                                 # dense
        np.full(n, -1),                                        # empty
        np.where(rng.random(n) < 0.02, rng.integers(0, n, n), -1),
    ]
    mat = jnp.asarray(np.stack(rows).astype(np.int32))
    totals = [int((r >= 0).sum()) for r in rows]
    want_mbox, want_load, want_drop = deliver_columns(
        mat, n, cap, chunk, flat=True)
    run = make_hosted_column_delivery(n, cap, (chunk, 4 * chunk, n),
                                      per_call_chunks=2)
    got_mbox, got_load, got_drop = run((mat,), row_totals=totals)
    np.testing.assert_array_equal(np.asarray(got_mbox),
                                  np.asarray(want_mbox))
    assert int(got_load) == int(want_load)
    assert int(got_drop) == int(want_drop)


def test_ticks_spill_checkpoint_coercion():
    """prepare_overlay_restore_tree: pre-round-7 ticks snapshots (no
    spill field) coerce to the empty buffer; live pairs are rejected on
    a mesh (the sharded engine has no spill delivery)."""
    from gossip_simulator_tpu.utils import rng as _rng
    from gossip_simulator_tpu.utils.checkpoint import \
        prepare_overlay_restore_tree

    cfg = Config(**TICKS).validate()
    st = ot.init_state(cfg, _rng.base_key(cfg.seed))
    tree = {k: np.asarray(v) for k, v in st._asdict().items()}
    legacy = dict(tree)
    del legacy["spill"]
    fixed = prepare_overlay_restore_tree(legacy, cfg, n_shards=1)
    assert fixed["spill"].shape == (2, ot.ticks_spill_cap(cfg) + 1)
    assert (fixed["spill"] == -1).all()
    live = dict(tree)
    live["spill"] = np.asarray([[5], [7]], np.int32)  # one live pair
    with pytest.raises(ValueError, match="spill"):
        prepare_overlay_restore_tree(
            live, cfg.replace(backend="sharded"), n_shards=2)


# --------------------------------------------------------------------------
# Slot negotiation, bootstrap request and hosted delivery against
# per-node loops of the reference's rules
# --------------------------------------------------------------------------

def _random_state(n, k, seed):
    """A state with every row class: empty, under-fanin, at-fanout,
    over-fanout; src hits both present and absent friends, with dead
    mailbox lanes."""
    import jax

    I32 = jnp.int32
    kc, kf, ks, kk = jax.random.split(jax.random.PRNGKey(seed), 4)
    cnt = jax.random.randint(kc, (n,), 0, k + 1, dtype=I32)
    fr = jax.random.randint(kf, (n, k), 0, n, dtype=I32)
    fr = jnp.where(jnp.arange(k, dtype=I32)[None, :] < cnt[:, None],
                   fr, -1)
    # Half the live lanes name a present friend, so both the match and
    # the miss branches run.
    src = jax.random.randint(ks, (n,), -2, n, dtype=I32)
    pick = jax.random.randint(jax.random.fold_in(ks, 1), (n,), 0,
                              jnp.maximum(cnt, 1), dtype=I32)
    present = jnp.take_along_axis(fr, pick[:, None], axis=1)[:, 0]
    src = jnp.where((src >= 0) & (src % 2 == 0) & (present >= 0), present,
                    src)
    has = src >= 0
    src = jnp.where(has, src, 0)
    return fr, cnt, src, has, jnp.arange(n, dtype=I32), kk


# n=37 and n=1024: a ragged and a power-of-two node count.
@pytest.mark.parametrize("n", [37, 1024])
def test_breakup_slot_matches_reference(n):
    """process_breakup_slot against simulator.go:76-94 node by node: the
    first friend equal to the leaver is removed by swap-with-last when
    the node is over fanout, else replaced in place by a fresh peer (not
    self, not the leaver) who is sent a makeup."""
    from gossip_simulator_tpu.utils import rng as _rng

    k, fanout = 5, 3
    fr, cnt, src, has, ids, kk = _random_state(n, k, seed=7)
    xf, xc, xnf, xrp = ov.process_breakup_slot(n, fanout, fr, cnt, src,
                                               has, ids, kk)
    nf = np.asarray(_rng.randint_excluding(kk, n, (n,), src, ids))
    f, c, src, has = (np.array(a) for a in (fr, cnt, src, has))
    reply = np.full(n, -1)
    for r in range(n):
        if not has[r]:
            continue
        hit = [j for j in range(c[r]) if f[r, j] == src[r]]
        if not hit:
            continue
        pos = hit[0]
        if c[r] > fanout:
            f[r, pos] = f[r, c[r] - 1]
            f[r, c[r] - 1] = -1
            c[r] -= 1
        else:
            assert nf[r] != r and nf[r] != src[r]
            f[r, pos] = nf[r]
            reply[r] = nf[r]
    assert (reply >= 0).any() and (np.asarray(xc) < np.asarray(cnt)).any()
    np.testing.assert_array_equal(np.asarray(xf), f)
    np.testing.assert_array_equal(np.asarray(xc), c)
    np.testing.assert_array_equal(np.asarray(xrp), reply >= 0)
    np.testing.assert_array_equal(
        np.where(np.asarray(xrp), np.asarray(xnf), -1), reply)


@pytest.mark.parametrize("n", [37, 1024])
def test_makeup_slot_matches_reference(n):
    """process_makeup_slot against simulator.go:66-75 node by node: under
    fanin the requester is appended; otherwise a uniformly drawn friend
    is evicted (sent a breakup) and the requester takes its place."""
    import jax

    k, fanin = 5, 3
    fr, cnt, src, has, ids, kk = _random_state(n, k, seed=8)
    xf, xc, xv, xev = ov.process_makeup_slot(fanin, fr, cnt, src, has, kk)
    vpos = np.asarray(jax.random.randint(kk, cnt.shape, 0,
                                         jnp.maximum(cnt, 1),
                                         dtype=jnp.int32))
    f, c, src, has = (np.array(a) for a in (fr, cnt, src, has))
    victim = np.full(n, -1)
    for r in range(n):
        if not has[r]:
            continue
        if c[r] < fanin:
            f[r, min(c[r], k - 1)] = src[r]
            c[r] += 1
        else:
            victim[r] = f[r, vpos[r]]
            f[r, vpos[r]] = src[r]
    assert (victim >= 0).any() and (np.asarray(xc) > np.asarray(cnt)).any()
    np.testing.assert_array_equal(np.asarray(xf), f)
    np.testing.assert_array_equal(np.asarray(xc), c)
    np.testing.assert_array_equal(np.asarray(xev), victim >= 0)
    np.testing.assert_array_equal(
        np.where(np.asarray(xev), np.asarray(xv), -1), victim)


@pytest.mark.parametrize("n", [37, 1024])
def test_request_round_matches_reference(n):
    """The rounds engine's bootstrap block (needNewFriend,
    simulator.go:95-106): every node under fanout appends its drawn peer
    and sends it a makeup; the round counts the requests."""
    import jax

    from gossip_simulator_tpu.utils import rng as _rng

    I32 = jnp.int32
    k, fanout = 5, 3
    fr, cnt, _, _, ids, kk = _random_state(n, k, seed=9)
    w = jax.random.randint(jax.random.fold_in(kk, _rng.OP_BOOTSTRAP),
                           (n,), 0, n, dtype=I32)
    w = jnp.where(w == ids, (w + 1) % n, w)
    under = cnt < fanout
    xf = ov._masked_set(fr, ids, jnp.minimum(cnt, k - 1), w, under)
    xc = cnt + under.astype(I32)
    xem = jnp.where(under, w, -1)
    f, c, w = (np.array(a) for a in (fr, cnt, w))
    em = np.full(n, -1)
    for r in range(n):
        if c[r] < fanout:
            assert w[r] != r
            f[r, c[r]] = w[r]
            c[r] += 1
            em[r] = w[r]
    assert 0 < (em >= 0).sum() < n
    np.testing.assert_array_equal(np.asarray(xf), f)
    np.testing.assert_array_equal(np.asarray(xc), c)
    np.testing.assert_array_equal(np.asarray(xem), em)
    assert int(under.sum(dtype=I32)) == int((em >= 0).sum())


@pytest.mark.parametrize("m", [133, 2048])
def test_hosted_delivery_matches_reference(m):
    """make_hosted_column_delivery counting each row's live lanes itself
    (no caller totals) on a width ladder: sparse, empty and fully valid
    emission rows, filled into a NumPy rank-major mailbox in row then
    sender order, overflow past cap counted as dropped."""
    from gossip_simulator_tpu.ops.mailbox import make_hosted_column_delivery

    rng = np.random.default_rng(19)
    cap = 3
    rows = [np.where(rng.random(m) < 0.4, rng.integers(0, m, m), -1)
            for _ in range(4)]
    rows += [np.full(m, -1), rng.integers(0, m, m)]
    mat = np.stack(rows)
    run = make_hosted_column_delivery(m, cap, (32, 128, m),
                                      per_call_chunks=2)
    mbox, load, dropped = run((jnp.asarray(mat, jnp.int32),))
    want = np.full(m * cap, -1)
    cnt = np.zeros(m, np.int64)
    drops = 0
    for c in range(mat.shape[0]):
        for s in range(m):
            d = mat[c, s]
            if d < 0:
                continue
            if cnt[d] < cap:
                want[cnt[d] * m + d] = s
            else:
                drops += 1
            cnt[d] += 1
    assert drops > 0
    np.testing.assert_array_equal(np.asarray(mbox)[:m * cap], want)
    assert int(load) == min(int(cnt.max()), cap)
    assert int(dropped) == drops
