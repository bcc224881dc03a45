"""Elastic serving mode (-serve, ISSUE 11).

Five surfaces:
* Arrival processes (gossip_simulator_tpu/arrivals.py): deterministic,
  sorted, shard-count-invariant schedules for every -arrivals kind, with
  "fixed" pinned to the PR-5 analytic staircase (r * 1000 // rate) so the
  serve-off path stays bit-identical.
* The headline twin: a serve run forced through S=1 -> S=8 -> S=1 ends
  Stats-exact against an uninterrupted fixed-S twin (compare_runs exit 0),
  with reshard-pause ms in result.json and zero shed.
* Admission control: a saturated widest mesh defers pending injections
  (counted in Stats.shed, capped backoff) and still converges with every
  rumor delivered -- degradation, never loss.
* Graceful shutdown (utils/lifecycle): SIGTERM to a live CLI run lands a
  final atomic checkpoint + run-dir flush with reason "interrupted".
* Retention (-ckpt-keep): pruning removes old snapshots WITH their sha256
  sidecars and stale .tmp partials.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from gossip_simulator_tpu import arrivals
from gossip_simulator_tpu.config import Config, parse_serve_force
from gossip_simulator_tpu.driver import latency_summary, run_simulation
from gossip_simulator_tpu.utils import checkpoint
from gossip_simulator_tpu.utils.metrics import ProgressPrinter, Stats

# Stats-exactness recipe (see ISSUE 11): no randomized faults and a
# single-value delay draw make the trajectory shard-count invariant, so a
# resharding serve run must match its fixed-S twin bit-for-bit.
BASE = dict(n=2048, graph="kout", fanout=6, seed=3, crashrate=0.0,
            droprate=0.0, delaylow=10, delayhigh=11, protocol="si",
            engine="event", backend="jax", rumors=8, traffic="stream",
            stream_rate=40, coverage_target=0.99, progress=False)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quiet():
    return ProgressPrinter(enabled=False)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# Arrival processes
# --------------------------------------------------------------------------

def test_arrival_schedules_sorted_and_deterministic():
    base = Config(**BASE).validate()
    for kind in ("fixed", "poisson", "burst", "diurnal"):
        cfg = base.replace(arrivals=kind).validate()
        t1 = arrivals.arrival_ticks(cfg)
        t2 = arrivals.arrival_ticks(cfg)
        assert t1.shape == (cfg.rumors,), kind
        np.testing.assert_array_equal(t1, t2)
        assert (np.diff(t1.astype(np.int64)) >= 0).all(), kind
        assert int(t1[0]) == 0, f"{kind}: first arrival must be tick 0"


def test_fixed_arrivals_match_analytic_staircase():
    """-arrivals fixed IS the PR-5 staircase -- the serve-off injection
    path must stay bit-identical, so the table and the arithmetic must
    agree exactly."""
    cfg = Config(**BASE).validate()
    t = arrivals.arrival_ticks(cfg)
    expect = np.arange(cfg.rumors, dtype=np.int64) * 1000 // cfg.stream_rate
    np.testing.assert_array_equal(t.astype(np.int64), expect)
    # ...and the fixed default is the None fast path (no table in the
    # traced program at all).
    assert arrivals.table_or_none(cfg) is None
    assert arrivals.table_or_none(cfg.replace(arrivals="poisson")) is not None


def test_poisson_arrivals_seed_and_rate_sensitive():
    cfg = Config(**BASE, arrivals="poisson").validate()
    a = arrivals.arrival_ticks(cfg)
    b = arrivals.arrival_ticks(cfg.replace(seed=4).validate())
    c = arrivals.arrival_ticks(cfg.replace(stream_rate=80).validate())
    assert not np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_inject_ticks_override_wins():
    ticks = (0, 5, 10, 100, 100, 200, 300, 400)
    cfg = Config(**BASE, inject_ticks=ticks).validate()
    np.testing.assert_array_equal(arrivals.arrival_ticks(cfg),
                                  np.asarray(ticks, np.int32))
    assert cfg.last_inject_tick == 400


def test_serve_validation_rejections():
    with pytest.raises(ValueError, match="-serve"):
        Config(n=512, serve=True, progress=False).validate()
    with pytest.raises(ValueError, match="arrivals"):
        Config(n=512, arrivals="poisson", progress=False).validate()
    with pytest.raises(ValueError, match="nondecreasing"):
        Config(**{**BASE, "n": 512},
               inject_ticks=(10, 0, 20, 30, 40, 50, 60, 70)).validate()
    with pytest.raises(ValueError, match="serve-force"):
        parse_serve_force("8-4")
    with pytest.raises(ValueError, match="twice"):
        parse_serve_force("8@4,2@4")


# --------------------------------------------------------------------------
# Interpolated latency percentiles (satellite 3)
# --------------------------------------------------------------------------

def test_latency_summary_interpolated_percentiles():
    """True linear-interpolated percentiles, not bucket upper edges: for
    [10, 20, 30, 40] the old histogram-edge report said p50=30."""
    s = latency_summary([10, 20, 30, 40])
    assert s == {"min": 10, "max": 40, "p50": 25.0, "p90": 37.0,
                 "p99": 39.7, "mean": 25.0}
    one = latency_summary([7])
    assert one["p50"] == one["p99"] == 7.0


# --------------------------------------------------------------------------
# Checkpoint retention (-ckpt-keep, satellite 2)
# --------------------------------------------------------------------------

def test_ckpt_prune_keeps_newest_with_sidecars(tmp_path):
    d = str(tmp_path)
    tree = {"x": np.arange(4, dtype=np.int32)}
    for w in (1, 2, 3, 4):
        checkpoint.save(d, w, tree, Stats(n=4))
    # Stale partials from a crashed save must go too.
    open(os.path.join(d, "state_00000099.npz.tmp"), "w").close()
    open(os.path.join(d, "state_00000099.npz.json.tmp"), "w").close()
    removed = checkpoint.prune(d, keep=2)
    names = sorted(os.listdir(d))
    assert names == ["state_00000003.npz", "state_00000003.npz.json",
                     "state_00000004.npz", "state_00000004.npz.json"]
    assert any(p.endswith(".tmp") for p in removed)
    # keep=0 disables pruning; keep >= count is a no-op.
    assert checkpoint.prune(d, keep=0) == []
    assert checkpoint.prune(d, keep=10) == []
    assert checkpoint.latest(d).endswith("state_00000004.npz")


# --------------------------------------------------------------------------
# The headline twin: autoscale S=1 -> 8 -> 1, Stats-exact vs fixed-S
# --------------------------------------------------------------------------

def test_serve_reshard_stats_exact_vs_twin(tmp_path):
    da, db = str(tmp_path / "serve"), str(tmp_path / "twin")
    cfg_a = Config(**BASE, serve=True, serve_force="8@4,1@10",
                   run_dir=da).validate()
    cfg_b = Config(**BASE, run_dir=db).validate()
    ra = run_simulation(cfg_a, printer=_quiet())
    rb = run_simulation(cfg_b, printer=_quiet())
    assert ra.converged and rb.converged
    assert ra.stats.to_dict() == rb.stats.to_dict()
    res = json.load(open(os.path.join(da, "result.json")))
    assert res["serve"]["resizes"] == 2
    assert res["serve"]["final_shards"] == 1
    assert res["reshard_pause_ms"] > 0
    assert res["shed"] == 0
    serve_doc = json.load(open(os.path.join(da, "serve.json")))
    assert [d["action"] for d in serve_doc["decisions"]] == \
        ["widen", "narrow"]
    assert all(s["shards"] >= 1 for s in serve_doc["segments"])
    # compare_runs is the acceptance gate: trajectory-identical, exit 0.
    assert _load_script("compare_runs").main([da, db]) == 0


def test_serve_poisson_arrivals_reshard_zero_loss(tmp_path):
    """Non-trivial arrival process across a reshard: the schedule is a
    pure function of (seed, rate, rumors), so the rebuilt stepper
    continues it exactly -- every rumor delivered, nothing shed.  The
    watermark autoscaler is held off (serve_window longer than the run):
    this stream's occupancy sits under the low watermark, so it would
    otherwise narrow the forced S=4 mesh back down."""
    cfg = Config(**{**BASE, "n": 1024}, arrivals="poisson", serve=True,
                 serve_force="4@3", serve_window=10**6,
                 run_dir=str(tmp_path)).validate()
    res = run_simulation(cfg, printer=_quiet())
    assert res.converged
    assert res.stats.rumors_done == cfg.rumors
    assert res.stats.shed == 0
    doc = json.load(open(os.path.join(str(tmp_path), "result.json")))
    assert doc["serve"]["arrivals"] == "poisson"
    assert doc["serve"]["final_shards"] == 4


# --------------------------------------------------------------------------
# Admission control: defer, count, converge -- never lose
# --------------------------------------------------------------------------

def test_admission_control_defers_and_converges():
    cfg = Config(**{**BASE, "n": 512}, serve=True, serve_max_shards=1,
                 serve_high=0.01, serve_low=0.0,
                 serve_window=1).validate()
    res = run_simulation(cfg, printer=_quiet())
    assert res.converged
    assert res.stats.shed > 0  # saturation was real and was counted
    assert res.stats.rumors_done == cfg.rumors  # ...but nothing was lost


# --------------------------------------------------------------------------
# Scenario interop: reshard mid-churn with healing on (satellite 4)
# --------------------------------------------------------------------------

# The PR-4 acceptance timeline (bench.py CHURN_SCENARIO, verbatim).
CHURN = ('{"groups": 2, "downtime": 60, "events": ['
         '{"type": "churn", "start": 0, "end": 150, "rate": 2.0},'
         '{"type": "crash", "at": 30, "frac": 0.3, "group": 1},'
         '{"type": "partition", "start": 20, "end": 60}]}')


@pytest.mark.parametrize("backend,force", [("jax", "2@6"),
                                           ("sharded", "1@6")])
def test_serve_reshard_mid_churn_with_healing(backend, force):
    """One reshard in the middle of the churn window with -overlay-heal
    on: the snapshot carries scenario + heal state, so the fault timeline
    survives the mesh change in either direction -- the resharded run
    finishes the same rumors as its fixed-mesh twin, nothing is shed, and
    healing ran.  The timeline's permanent crash (30% of group 1 at
    t=30) caps per-rumor coverage below 0.99, and a rumor whose random
    source is already dead at its inject tick never starts, so the target
    is 0.8 and the run is held to its twin rather than to convergence."""
    cfg = Config(n=1600, graph="kout", fanout=6, seed=3, crashrate=0.0,
                 coverage_target=0.8, max_rounds=600, scenario=CHURN,
                 overlay_heal="on", backend=backend, engine="event",
                 rumors=16, traffic="stream", stream_rate=100,
                 serve=True, serve_force=force, progress=False).validate()
    res = run_simulation(cfg, printer=_quiet())
    twin = run_simulation(cfg.replace(serve=False, serve_force="").validate(),
                          printer=_quiet())
    assert res.stats.rumors_done == twin.stats.rumors_done >= 15, (
        res.stats, twin.stats)
    assert res.converged == twin.converged
    assert res.stats.shed == 0
    assert res.stats.heal_repaired > 0


# --------------------------------------------------------------------------
# Graceful shutdown (satellite 1)
# --------------------------------------------------------------------------

def test_sigterm_lands_checkpoint_and_interrupted_result(tmp_path):
    """Kill a live CLI run with SIGTERM: exit code 2 (not-converged), a
    final atomic snapshot in the checkpoint dir, and a run-dir result
    with reason "interrupted" -- the long-lived serving contract.

    The run must not end before the signal lands: a few nodes of this
    k-out graph have no in-edges, so coverage 1.0 is out of reach, and
    -overlay-heal on never reports an empty ring as exhausted, so the
    job would only stop at max_rounds (10 000 windows, minutes on a
    CPU)."""
    ckpt_dir = str(tmp_path / "ckpt")
    run_dir = str(tmp_path / "run")
    args = [sys.executable, "-m", "gossip_simulator_tpu",
            "-n", "2000", "-graph", "kout", "-fanout", "6", "-seed", "3",
            "-crashrate", "0", "-backend", "jax", "-engine", "event",
            "-rumors", "32", "-traffic", "stream", "-stream-rate", "5",
            "-coverage-target", "1.0", "-overlay-heal", "on",
            "-checkpoint-every", "1",
            "-checkpoint-dir", ckpt_dir, "-run-dir", run_dir]
    proc = subprocess.Popen(args, env=dict(os.environ),
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            if checkpoint.latest(ckpt_dir) is not None:
                break
            if proc.poll() is not None:
                pytest.fail(f"run exited early rc={proc.returncode}")
            time.sleep(0.25)
        else:
            pytest.fail("no checkpoint appeared within 120s")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert rc == 2
    assert checkpoint.latest(ckpt_dir) is not None
    res = json.load(open(os.path.join(run_dir, "result.json")))
    assert res["reason"] == "interrupted"
    assert res["converged"] is False


def test_request_shutdown_breaks_windowed_loop():
    """In-process flavor: the cooperative flag stops the windowed loop at
    the next boundary and the run reports "interrupted" (no subprocess,
    so this covers the driver plumbing on every platform)."""
    from gossip_simulator_tpu.utils import lifecycle

    lifecycle.reset()
    cfg = Config(**{**BASE, "n": 512}, serve=True).validate()
    lifecycle.request_shutdown()
    try:
        res = run_simulation(cfg, printer=_quiet())
    finally:
        lifecycle.reset()
    assert not res.converged
