"""Checkpoint save/load (utils/checkpoint.py) and resume on the jax backend."""

import numpy as np
import pytest

from gossip_simulator_tpu.backends.jax_backend import JaxStepper
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.utils import checkpoint
from gossip_simulator_tpu.utils.metrics import Stats


def test_roundtrip(tmp_path):
    tree = {"a": np.arange(5), "b": np.ones((2, 3), bool)}
    path = checkpoint.save(str(tmp_path), 7, tree, Stats(n=5))
    assert checkpoint.latest(str(tmp_path)) == path
    loaded, meta = checkpoint.load(path)
    np.testing.assert_array_equal(loaded["a"], tree["a"])
    np.testing.assert_array_equal(loaded["b"], tree["b"])
    assert meta["window"] == 7


def test_atomic_save_leaves_no_tmp_and_latest_ignores_partials(tmp_path):
    """save() stages under .tmp names and os.replace's into place; a
    leftover partial from a crashed save must never shadow a real
    snapshot."""
    tree = {"a": np.arange(5)}
    path = checkpoint.save(str(tmp_path), 3, tree, Stats(n=5))
    import os

    assert sorted(os.listdir(tmp_path)) == [
        "state_00000003.npz", "state_00000003.npz.json"]
    # Simulate a crash mid-save: a stale tmp pair lying around.
    (tmp_path / "state_00000009.npz.tmp").write_bytes(b"partial")
    assert checkpoint.latest(str(tmp_path)) == path


def test_truncated_snapshot_rejected(tmp_path):
    """A crash/partial-copy truncation is caught by the content digest
    with a clear error instead of restoring garbage."""
    tree = {"a": np.arange(1000), "b": np.ones((50, 3))}
    path = checkpoint.save(str(tmp_path), 1, tree, Stats(n=5))
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: len(data) // 2])
    with pytest.raises(ValueError, match="corrupt"):
        checkpoint.load(path)


def test_torn_write_rejected(tmp_path):
    """Bytes flipped mid-file (torn write / bit rot): digest mismatch,
    rejected -- even though np.load might happily parse some of it."""
    tree = {"a": np.arange(1000)}
    path = checkpoint.save(str(tmp_path), 1, tree, Stats(n=5))
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))
    with pytest.raises(ValueError, match="corrupt"):
        checkpoint.load(path)


def test_pre_digest_snapshot_loads_without_check(tmp_path):
    """Legacy snapshots (no sha256 in the sidecar) still load."""
    import json as _json

    path = checkpoint.save(str(tmp_path), 1, {"a": np.arange(4)},
                           Stats(n=4))
    meta = _json.load(open(path + ".json"))
    meta.pop("sha256")
    _json.dump(meta, open(path + ".json", "w"))
    loaded, got = checkpoint.load(path)
    np.testing.assert_array_equal(loaded["a"], np.arange(4))
    assert "sha256" not in got


def test_jax_stepper_resume(tmp_path):
    # fanout 6: keeps the kout unreachable fraction (~e^{-5.4}) under 1%.
    cfg = Config(n=2000, backend="jax", graph="kout", fanout=6, seed=3,
                 crashrate=0.0, progress=False).validate()
    s = JaxStepper(cfg)
    s.init()
    s.seed()
    s.gossip_window()
    mid = s.stats()
    path = checkpoint.save(str(tmp_path), 1, s.state_pytree(), mid)

    s2 = JaxStepper(cfg)
    s2.init()
    tree, _ = checkpoint.load(path)
    s2.load_state_pytree(tree)
    assert s2.stats() == mid
    # Resumed run continues and converges.
    for _ in range(200):
        st = s2.gossip_window()
        if st.coverage >= 0.99:
            break
    assert st.coverage >= 0.99


def test_driver_writes_checkpoints(tmp_path):
    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    cfg = Config(n=1500, backend="native", seed=1, checkpoint_every=2,
                 checkpoint_dir=str(tmp_path), progress=False).validate()
    run_simulation(cfg, printer=ProgressPrinter(enabled=False))
    assert checkpoint.latest(str(tmp_path)) is not None


def test_driver_resume_flag(tmp_path):
    """Interrupted run -> -resume from the latest snapshot completes."""
    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    base = dict(n=2000, backend="jax", graph="kout", fanout=6, seed=3,
                crashrate=0.0, checkpoint_dir=str(tmp_path), progress=False)
    # "Interrupted": checkpoint every window, stop early via max_rounds.
    partial = run_simulation(
        Config(**base, checkpoint_every=1, max_rounds=30).validate(),
        printer=ProgressPrinter(enabled=False))
    assert not partial.converged
    assert checkpoint.latest(str(tmp_path)) is not None
    resumed = run_simulation(Config(**base, resume=True).validate(),
                             printer=ProgressPrinter(enabled=False))
    assert resumed.converged
    assert resumed.stats.total_received >= partial.stats.total_received


def _sharded(cfg):
    from gossip_simulator_tpu.backends.sharded import ShardedStepper

    s = ShardedStepper(cfg)
    s.init()
    return s


def test_sharded_event_resume_reproduces_trajectory(tmp_path):
    """Snapshot mid-run on the 8-device mesh, restore into a fresh stepper,
    and the per-window Stats match the uninterrupted run exactly (step keys
    depend only on (seed, tick, shard))."""
    cfg = Config(n=4000, backend="sharded", graph="kout", fanout=6, seed=3,
                 crashrate=0.01, coverage_target=0.99,
                 progress=False).validate()
    assert cfg.engine_resolved == "event"
    s = _sharded(cfg)
    s.seed()
    s.gossip_window()
    s.gossip_window()
    mid = s.stats()
    path = checkpoint.save(str(tmp_path), 2, s.state_pytree(), mid)
    reference = [s.gossip_window() for _ in range(8)]

    s2 = _sharded(cfg.replace(resume=True, checkpoint_dir=str(tmp_path)))
    tree, _ = checkpoint.load(path)
    s2.load_state_pytree(tree)
    assert s2.stats() == mid
    for want in reference:
        assert s2.gossip_window() == want


def test_sharded_ring_resume_reproduces_trajectory(tmp_path):
    """Same round-trip discipline on the ring engine (SIR resolves to it)."""
    # engine="ring" explicitly: auto-SIR resolves to the event engine
    # since round 5, and this test exists to cover the RING resume path.
    cfg = Config(n=4000, backend="sharded", graph="kout", fanout=6, seed=3,
                 protocol="sir", removal_rate=0.3, engine="ring",
                 progress=False).validate()
    assert cfg.engine_resolved == "ring"
    s = _sharded(cfg)
    s.seed()
    s.gossip_window()
    mid = s.stats()
    path = checkpoint.save(str(tmp_path), 1, s.state_pytree(), mid)
    reference = [s.gossip_window() for _ in range(5)]

    s2 = _sharded(cfg.replace(resume=True, checkpoint_dir=str(tmp_path)))
    tree, _ = checkpoint.load(path)
    s2.load_state_pytree(tree)
    assert s2.stats() == mid
    for want in reference:
        assert s2.gossip_window() == want


@pytest.mark.parametrize("first,shards", [
    pytest.param("jax", 1, id="jax"),
    pytest.param("sharded", 1, id="sharded"),
    pytest.param("sharded", 4, id="sharded4"),
])
def test_ring_snapshot_crosses_backends_mid_phase2(tmp_path, first, shards):
    """The ring engine keeps its delay ring flat, on one device and per
    shard, but snapshots it as the logical (d, n) ring.  A mid-phase-2
    snapshot of either backend restores into the other, whose own
    snapshot restores back, and the run finishes Stats-exact against the
    uninterrupted one.  At S = 4 the shards' flat rings, side by side in
    the sharded array, reorder into (d, n) and back."""
    from gossip_simulator_tpu.backends.sharded import ShardedStepper
    from gossip_simulator_tpu.models import epidemic

    n = 2000
    base = dict(n=n, graph="kout", fanout=6, seed=3, crashrate=0.0,
                engine="ring", progress=False)

    def stepper(backend, **kw):
        cfg = Config(**base, backend=backend, **kw).validate()
        s = (JaxStepper(cfg) if backend == "jax"
             else ShardedStepper(cfg, n_devices=shards))
        s.init()
        return s

    resume = dict(resume=True, checkpoint_dir=str(tmp_path))
    other = "sharded" if first == "jax" else "jax"
    s = stepper(first)
    d = epidemic.ring_depth(s.cfg)
    assert s.state.pending.shape == (d * n,)
    s.seed()
    s.gossip_window()
    s.gossip_window()
    mid = s.stats()
    tree, _ = checkpoint.load(
        checkpoint.save(str(tmp_path), 2, s.state_pytree(), mid))
    assert tree["pending"].shape == (d, n)
    assert tree["pending"].any()  # messages in flight
    # Slot t of node g sits in its owner shard's ring at t * n_local + r.
    flat = np.array(s.state.pending).reshape(shards, d, n // shards)
    np.testing.assert_array_equal(
        tree["pending"], np.concatenate(list(flat), axis=1))
    reference = []
    while not reference or reference[-1].coverage < 0.99:
        reference.append(s.gossip_window())

    t = stepper(other, **resume)
    t.load_state_pytree(dict(tree))
    assert t.stats() == mid
    back_tree = t.state_pytree()
    np.testing.assert_array_equal(back_tree["pending"], tree["pending"])
    back = stepper(first, **resume)
    back.load_state_pytree(back_tree)
    assert back.stats() == mid
    for want in reference:
        assert back.gossip_window() == want


def test_sharded_resume_repacks_mail_geometry(tmp_path):
    """A sharded snapshot written under one -event-chunk restores under a
    different one via the per-shard slot repack."""
    base = dict(n=4000, backend="sharded", graph="kout", fanout=6, seed=3,
                crashrate=0.0, progress=False)
    s = _sharded(Config(**base, event_chunk=512).validate())
    s.seed()
    s.gossip_window()
    tree = s.state_pytree()
    assert tree["mail_geom"].shape == (3,)
    s2 = _sharded(Config(**base, event_chunk=2048).validate())
    s2.load_state_pytree(tree)
    a = s.gossip_window()
    b = s2.gossip_window()
    assert a.total_received == b.total_received
    assert a.total_message == b.total_message


def _decode_entries(tree, cfg, s_ckpt):
    """Multiset of in-flight (global dst, slot, tick-offset) triples in an
    event snapshot -- the reshard conservation invariant."""
    from gossip_simulator_tpu.models import event

    b = event.batch_ticks(cfg)
    dw = event.ring_windows(cfg)
    geom = np.asarray(tree["mail_geom"])
    ocap = int(geom[0])
    mail = np.asarray(tree["mail_ids"])
    cnt = np.asarray(tree["mail_cnt"])
    per = mail.shape[0] // s_ckpt
    nlo = cfg.n // s_ckpt
    out = []
    for sh in range(s_ckpt):
        for slot in range(dw):
            c = int(cnt[sh, slot])
            seg = mail[sh * per + slot * ocap:
                       sh * per + slot * ocap + c].astype(np.int64)
            out += [(int(e // b) + sh * nlo, slot, int(e % b))
                    for e in seg]
    return sorted(out)


def test_sharded_resume_reshards_1_to_8_and_back(tmp_path):
    """VERDICT r4 #3: an S=1 snapshot restores onto an S=8 mesh (and
    back) via a host-side reshard of the per-shard mail rings.  Every
    in-flight message is conserved exactly (multiset of global
    (dst, slot, off) triples), restored counters equal the snapshot's,
    and the continued run converges.  Exact trajectory equality across
    shard counts is out of scope by design: the sharded engine folds the
    shard index into its RNG keys, so even a fresh S=8 run diverges from
    S=1 distributionally (test_event_sharded_converges pins that
    envelope)."""
    base = dict(n=4000, graph="kout", fanout=6, seed=3, crashrate=0.0,
                progress=False)
    sj = JaxStepper(Config(**base, backend="jax").validate())
    sj.init()
    sj.seed()
    for _ in range(3):
        sj.gossip_window()
    mid = sj.stats()
    tree1 = sj.state_pytree()
    want = _decode_entries(tree1, Config(**base, backend="jax").validate(),
                           1)
    assert want  # messages genuinely in flight mid-wave

    # 1 -> 8: restore the single-device snapshot on the fake 8-mesh.
    cfg8 = Config(**base, backend="sharded").validate()
    s8 = _sharded(cfg8)
    s8.load_state_pytree(dict(tree1))
    assert s8.stats() == mid
    tree8 = s8.state_pytree()
    assert np.asarray(tree8["mail_geom"])[2] == 8
    got = _decode_entries(tree8, cfg8, 8)
    assert got == want  # nothing lost or moved in the reshard
    while not s8.exhausted and s8.stats().coverage < 0.99:
        s8.gossip_window()
    assert s8.stats().coverage >= 0.99

    # 8 -> 1: a mid-wave sharded snapshot back onto one device.
    s8b = _sharded(cfg8)
    s8b.seed()
    for _ in range(3):
        s8b.gossip_window()
    mid8 = s8b.stats()
    tree8b = s8b.state_pytree()
    want8 = _decode_entries(tree8b, cfg8, 8)
    assert want8
    cfg1 = Config(**base, backend="jax").validate()
    sj2 = JaxStepper(cfg1)
    sj2.init()
    sj2.load_state_pytree(dict(tree8b))
    assert sj2.stats() == mid8
    got1 = _decode_entries(sj2.state_pytree(), cfg1, 1)
    assert got1 == want8
    while not sj2.exhausted and sj2.stats().coverage < 0.99:
        sj2.gossip_window()
    assert sj2.stats().coverage >= 0.99


def test_driver_resume_flag_sharded(tmp_path):
    """End-to-end -resume on backend=sharded through the driver."""
    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    base = dict(n=4000, backend="sharded", graph="kout", fanout=6, seed=3,
                crashrate=0.0, checkpoint_dir=str(tmp_path), progress=False)
    partial = run_simulation(
        Config(**base, checkpoint_every=1, max_rounds=30).validate(),
        printer=ProgressPrinter(enabled=False))
    assert not partial.converged
    assert checkpoint.latest(str(tmp_path)) is not None
    resumed = run_simulation(Config(**base, resume=True).validate(),
                             printer=ProgressPrinter(enabled=False))
    assert resumed.converged
    assert resumed.stats.total_received >= partial.stats.total_received


def test_resume_engine_mismatch_rejected(tmp_path):
    cfg_ring = Config(n=2000, backend="jax", graph="kout", fanout=6, seed=3,
                      engine="ring", progress=False).validate()
    s = JaxStepper(cfg_ring)
    s.init()
    s.seed()
    path = checkpoint.save(str(tmp_path), 1, s.state_pytree(), s.stats())
    cfg_event = cfg_ring.replace(engine="event")
    s2 = JaxStepper(cfg_event)
    s2.init()
    tree, _ = checkpoint.load(path)
    with pytest.raises(ValueError, match="ring engine"):
        s2.load_state_pytree(tree)


# --- Phase-1 (overlay) checkpointing: VERDICT r3 #7 -------------------------

def _overlay_cfg(backend, mode, **kw):
    return Config(n=2000 if backend == "jax" else 4000, backend=backend,
                  graph="overlay", overlay_mode=mode, fanout=5, seed=9,
                  coverage_target=0.9, progress=False, **kw).validate()


def _run_overlay_windows(s, k):
    out = []
    for _ in range(k):
        out.append(s.overlay_window())
        if out[-1][2]:
            break
    return out


def _stepper(cfg):
    if cfg.backend == "sharded":
        from gossip_simulator_tpu.backends.sharded import ShardedStepper

        s = ShardedStepper(cfg)
    else:
        s = JaxStepper(cfg)
    s.init()
    return s


@pytest.mark.parametrize("backend", ["jax", "sharded"])
@pytest.mark.parametrize("mode", ["rounds", "ticks"])
def test_overlay_snapshot_resume_trajectory(tmp_path, backend, mode):
    """Snapshot mid-construction, restore into a fresh stepper, and the
    remaining overlay windows reproduce the uninterrupted run exactly
    (round/tick-indexed keys make the trajectory state-determined)."""
    cfg = _overlay_cfg(backend, mode)
    s = _stepper(cfg)
    pre = _run_overlay_windows(s, 3)
    assert not pre[-1][2], "stabilized before the snapshot -- config too easy"
    tree = s.overlay_state_pytree()
    assert tree is not None
    mid_ms = s.sim_time_ms()
    reference = _run_overlay_windows(s, 500)
    assert reference[-1][2]

    s2 = _stepper(cfg.replace(resume=True, checkpoint_dir=str(tmp_path)))
    s2.load_overlay_state_pytree(tree, windows=3)
    assert s2.sim_time_ms() == mid_ms
    got = _run_overlay_windows(s2, 500)
    assert got == reference
    # Both complete phase 2 identically from the constructed overlay.
    s.seed()
    s2.seed()
    for _ in range(300):
        a, b = s.gossip_window(), s2.gossip_window()
        assert a == b
        if a.coverage >= 0.9:
            break
    assert a.coverage >= 0.9


def test_overlay_snapshot_mode_mismatch_rejected(tmp_path):
    cfg = _overlay_cfg("jax", "ticks")
    s = _stepper(cfg)
    _run_overlay_windows(s, 2)
    tree = s.overlay_state_pytree()
    s2 = _stepper(_overlay_cfg("jax", "rounds",
                               resume=True, checkpoint_dir=str(tmp_path)))
    with pytest.raises(ValueError, match="ticks engine"):
        s2.load_overlay_state_pytree(tree)


@pytest.mark.parametrize("backend", ["jax", "sharded"])
def test_driver_phase1_resume(tmp_path, backend):
    """End-to-end: a checkpointed run writes overlay_* snapshots; deleting
    the phase-2 state_* snapshots and resuming continues construction
    mid-overlay and finishes with the uninterrupted run's exact totals."""
    import glob
    import os

    from gossip_simulator_tpu.driver import run_simulation
    from gossip_simulator_tpu.utils.metrics import ProgressPrinter

    cfg = _overlay_cfg(backend, "ticks", checkpoint_every=2,
                       checkpoint_dir=str(tmp_path))
    full = run_simulation(cfg, printer=ProgressPrinter(enabled=False))
    overlays = glob.glob(str(tmp_path / "overlay_*.npz"))
    assert overlays, "no phase-1 snapshots written"
    # "Interrupt" after phase 1: drop every phase-2 snapshot, leaving the
    # latest overlay_* as the resume point.
    for p in glob.glob(str(tmp_path / "state_*.npz*")):
        os.remove(p)
    res = run_simulation(
        cfg.replace(resume=True, checkpoint_every=0).validate(),
        printer=ProgressPrinter(enabled=False))
    assert res.converged
    assert res.stats == full.stats
    assert res.stabilize_ms == full.stabilize_ms


def test_pre_round5_snapshot_coercions():
    """Round-4 event snapshots predate sup_cnt (deferred duplicate
    credits): restoring one must backfill zeros, not reject."""
    cfg = Config(n=2000, backend="jax", graph="kout", fanout=6, seed=3,
                 crashrate=0.0, progress=False).validate()
    s = JaxStepper(cfg)
    s.init()
    s.seed()
    s.gossip_window()
    mid = s.stats()
    tree = dict(s.state_pytree())
    # Precondition: no deferred credits pending at this snapshot point --
    # otherwise deleting the field would simulate an IMPOSSIBLE round-4
    # snapshot and the trajectory check below would fail for the wrong
    # reason.
    assert not np.asarray(tree["sup_cnt"]).any()
    del tree["sup_cnt"]  # simulate a round-4 snapshot
    s2 = JaxStepper(cfg)
    s2.init()
    s2.load_state_pytree(tree)
    assert s2.stats() == mid
    ref = s.gossip_window()
    assert s2.gossip_window() == ref


def test_live_overlay_spill_rejected_on_mesh():
    """A rounds-overlay snapshot holding UNDELIVERED spill pairs cannot
    restore onto the sharded backend (its routed delivery has no spill
    path; the pairs would block quiescence forever) -- rejected with a
    named error instead."""
    import gossip_simulator_tpu.models.overlay as ov
    from gossip_simulator_tpu.utils.checkpoint import \
        prepare_overlay_restore_tree

    cfg = Config(n=4000, backend="sharded", graph="overlay", fanout=5,
                 seed=9, overlay_mode="rounds", time_mode="rounds",
                 progress=False).validate()
    st = ov.init_state(cfg)
    tree = {k: np.asarray(v) for k, v in st._asdict().items()}
    tree["mk_spill"] = np.asarray(tree["mk_spill"]).copy()
    tree["mk_spill"][:, 0] = [7, 11]  # one live (src, dst) pair
    with pytest.raises(ValueError, match="spill"):
        prepare_overlay_restore_tree(tree, cfg, n_shards=8)
    # Empty spill buffers restore fine.
    tree["mk_spill"][:, 0] = -1
    prepare_overlay_restore_tree(tree, cfg, n_shards=8)


# --------------------------------------------------------------------------
# Mid-scenario resume (fault-injection subsystem, scenario.py): the
# scenario clock, crash/reboot state and healing state all live in the
# snapshot, so a resumed run walks the uninterrupted trajectory exactly
# -- including across an S=1 <-> S=8 reshard (scenario draws are
# (window, GLOBAL-id)-keyed, so only the shard-folded delay/drop streams
# diverge across shard counts, exactly as without a scenario).
# --------------------------------------------------------------------------

_SCEN = ('{"groups": 2, "downtime": 60, "events": ['
         '{"type": "churn", "start": 0, "end": 150, "rate": 2.0},'
         '{"type": "partition", "start": 20, "end": 60}]}')
_SCEN_BASE = dict(n=4000, graph="kout", fanout=6, seed=3, crashrate=0.0,
                  coverage_target=0.99, max_rounds=600, progress=False,
                  scenario=_SCEN, overlay_heal="on")


def test_mid_scenario_resume_reproduces_trajectory(tmp_path):
    cfg = Config(backend="sharded", **_SCEN_BASE).validate()
    s = _sharded(cfg)
    s.seed()
    for _ in range(3):
        s.gossip_window()
    mid = s.stats()
    assert mid.scen_crashed > 0  # genuinely mid-scenario
    path = checkpoint.save(str(tmp_path), 3, s.state_pytree(), mid)
    reference = [s.gossip_window() for _ in range(6)]

    s2 = _sharded(cfg.replace(resume=True, checkpoint_dir=str(tmp_path)))
    tree, _ = checkpoint.load(path)
    s2.load_state_pytree(tree)
    assert s2.stats() == mid
    for want in reference:
        assert s2.gossip_window() == want


def test_mid_scenario_reshard_1_to_8_converges(tmp_path):
    """An S=1 snapshot taken mid-churn (crash clocks + reboot markers +
    healed friends in flight) reshards onto the 8-mesh: restored Stats
    equal the snapshot's, the scenario timeline continues (same
    global-keyed draws), and the healed run still reaches the 99%
    target."""
    cfgj = Config(backend="jax", **_SCEN_BASE).validate()
    sj = JaxStepper(cfgj)
    sj.init()
    sj.seed()
    for _ in range(3):
        sj.gossip_window()
    mid = sj.stats()
    assert mid.scen_crashed > 0
    tree1 = sj.state_pytree()
    uninterrupted = sj.stats()
    for _ in range(60):
        uninterrupted = sj.gossip_window()
        if uninterrupted.coverage >= 0.99:
            break
    assert uninterrupted.coverage >= 0.99

    cfg8 = Config(backend="sharded", **_SCEN_BASE).validate()
    s8 = _sharded(cfg8)
    s8.load_state_pytree(dict(tree1))
    assert s8.stats() == mid
    st8 = mid
    for _ in range(60):
        st8 = s8.gossip_window()
        if st8.coverage >= 0.99:
            break
    assert st8.coverage >= 0.99
    # The scenario schedule is shard-count invariant: the resharded
    # continuation crashed/recovered the same global timeline the
    # uninterrupted single-device run did (delay/drop streams differ, so
    # runs can END at different windows with different recovery tails --
    # compare the crash totals, which the churn window fully determines).
    assert st8.scen_crashed == uninterrupted.scen_crashed


def test_fault_free_snapshot_resumes_into_scenario_run(tmp_path):
    """A pre-scenario (placeholder down_since) snapshot restores into a
    scenario-armed run: the crash clock starts empty and the timeline
    picks up from the restored tick."""
    base = dict(n=2000, backend="jax", graph="kout", fanout=6, seed=3,
                crashrate=0.0, coverage_target=0.99, max_rounds=600,
                progress=False)
    s = JaxStepper(Config(**base).validate())
    s.init()
    s.seed()
    s.gossip_window()
    tree = s.state_pytree()
    assert np.asarray(tree["down_since"]).shape == (1,)

    armed = Config(**base, scenario='{"downtime": 40, "events": '
                   '[{"type": "churn", "start": 0, "end": 200, '
                   '"rate": 1.5}]}').validate()
    s2 = JaxStepper(armed)
    s2.init()
    s2.load_state_pytree(tree)
    st = s2.stats()
    for _ in range(80):
        st = s2.gossip_window()
        if st.coverage >= 0.99 or s2.exhausted:
            break
    assert st.scen_crashed > 0

    # The reverse -- a full crash clock into a fault-free run -- is
    # rejected with a flag-naming error.
    tree2 = s2.state_pytree()
    assert np.asarray(tree2["down_since"]).shape == (2000,)
    s3 = JaxStepper(Config(**base).validate())
    s3.init()
    with pytest.raises(ValueError, match="-scenario"):
        s3.load_state_pytree(tree2)
