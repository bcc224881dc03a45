"""Flight recorder (utils/trace.py, utils/artifact.py, -run-dir) and the
run comparator (scripts/compare_runs.py, scripts/check_bench.py).

The observability contract: recording must not perturb the run.  A traced,
artifact-archived run produces byte-identical stdout and the same final
Stats as an unflagged run on the same seed, on BOTH telemetry paths; the
archived trajectory fingerprint is path-independent; and the comparator
returns 0 on a same-seed twin pair and nonzero -- naming the first
divergent window -- on a perturbed-seed pair.
"""

import importlib.util
import io
import json
import os

import numpy as np
import pytest

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.driver import run_simulation
from gossip_simulator_tpu.utils import artifact, trace
from gossip_simulator_tpu.utils.metrics import ProgressPrinter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = dict(n=1500, backend="jax", graph="kout", fanout=6, seed=4,
            coverage_target=0.9)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(tmp_path, tag, run_dir=None, **kw):
    cfg = Config(**{**BASE, **kw})
    if run_dir is not None:
        cfg = Config(**{**BASE, **kw}, run_dir=str(run_dir),
                     trace=str(run_dir / "trace.json"))
    cfg = cfg.validate()
    buf = io.StringIO()
    jsonl = cfg.log_jsonl_resolved or str(tmp_path / f"{tag}.jsonl")
    with ProgressPrinter(enabled=True, jsonl_path=jsonl,
                         out=buf) as printer:
        res = run_simulation(cfg, printer=printer)
    recs = [json.loads(line) for line in open(jsonl)]
    return buf.getvalue(), recs, res


# ---------------------------------------------------------------------------
# Recording does not perturb the run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("telemetry", ["on", "off"])
def test_recording_is_invisible(tmp_path, telemetry):
    """Stdout bytes and final Stats with -trace + -run-dir active match
    the unflagged run on the same seed, on both telemetry paths."""
    out_plain, _, res_plain = _run(tmp_path, f"plain_{telemetry}",
                                   telemetry=telemetry)
    rdir = tmp_path / f"rec_{telemetry}"
    out_rec, _, res_rec = _run(tmp_path, f"rec_{telemetry}",
                               run_dir=rdir, telemetry=telemetry)
    assert out_rec == out_plain
    assert res_rec.stats == res_plain.stats
    assert res_rec.converged == res_plain.converged


def test_fingerprint_path_independent(tmp_path):
    """The archived trajectory fingerprint matches between the telemetry
    fast path and the windowed loop (Stats.round IS the tick column)."""
    fps = {}
    for telemetry in ("on", "off"):
        rdir = tmp_path / f"fp_{telemetry}"
        _run(tmp_path, f"fp_{telemetry}", run_dir=rdir, telemetry=telemetry)
        r = json.load(open(rdir / "result.json"))
        fps[telemetry] = (r["fingerprint"], r["fingerprint_basis"])
    assert fps["on"][0] == fps["off"][0]
    assert fps["on"][1] == "telemetry" and fps["off"][1] == "windows"


# ---------------------------------------------------------------------------
# Artifact layout and contents
# ---------------------------------------------------------------------------

def test_run_dir_layout(tmp_path):
    rdir = tmp_path / "art"
    _, recs, res = _run(tmp_path, "art", run_dir=rdir)
    for name in ("config.json", "env.json", "metrics.jsonl",
                 "telemetry.npz", "trace.json", "result.json"):
        assert (rdir / name).exists(), name

    cfg_doc = json.load(open(rdir / "config.json"))
    assert cfg_doc["flags"]["n"] == BASE["n"]
    assert cfg_doc["resolved"]["engine"] in ("event", "ring")

    env = json.load(open(rdir / "env.json"))
    assert "python" in env and "jax" in env

    result = json.load(open(rdir / "result.json"))
    assert result["total_message"] == res.stats.total_message
    assert result["fingerprint_windows"] == res.gossip_windows

    # The npz trajectory re-hashes to the recorded fingerprint, and its
    # last row is the final Stats.
    with np.load(rdir / "telemetry.npz") as z:
        traj = z["trajectory"]
        names = [str(s) for s in z["trajectory_names"]]
    assert names == list(artifact.TRAJECTORY_COLS)
    assert artifact.fingerprint_rows(traj) == result["fingerprint"]
    assert traj[-1].tolist() == [
        res.stats.round, res.stats.total_received,
        res.stats.total_message, res.stats.total_crashed,
        res.stats.total_removed]

    # metrics.jsonl landed inside the run dir (log_jsonl_resolved) and
    # opens with the v3 header.
    head = json.loads(open(rdir / "metrics.jsonl").readline())
    assert head["event"] == "header"
    assert head["columns"]["trajectory"] == list(artifact.TRAJECTORY_COLS)


def test_result_record_carries_run_dir_and_gates(tmp_path):
    rdir = tmp_path / "gates"
    _, recs, _ = _run(tmp_path, "gates", run_dir=rdir)
    r = [x for x in recs if x["event"] == "result"][0]
    assert r["run_dir"] == str(rdir)
    assert r["gates"]["engine"] == "event"
    assert "deliver_kernel" in r["gates"]
    # Parity guard: telemetry/checkpointing are excluded ON PURPOSE so
    # twin streams stay field-identical (test_telemetry byte parity).
    assert "telemetry" not in r["gates"]

    _, recs_plain, _ = _run(tmp_path, "plain_gates")
    rp = [x for x in recs_plain if x["event"] == "result"][0]
    assert rp["run_dir"] is None
    assert rp["gates"] == r["gates"]


def test_trace_json_structure(tmp_path):
    rdir = tmp_path / "tr"
    _run(tmp_path, "tr", run_dir=rdir)
    doc = json.load(open(rdir / "trace.json"))
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metadata"]["clock_origin_unix_ns"] > 0
    events = doc["traceEvents"]
    names = {e["name"] for e in events}
    assert "init" in names
    assert {"phase2.run_to_target", "phase2.dispatch", "phase2.sync",
            "seed", "stats", "telemetry.fetch"} <= names
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e and "cat" in e
    run = next(e for e in events if e["name"] == "phase2.run_to_target")
    assert run["args"]["messages"] > 0
    dispatch = [e for e in events if e["name"] == "phase2.dispatch"]
    sync = [e for e in events if e["name"] == "phase2.sync"]
    assert [e["args"]["call"] for e in dispatch] == list(range(len(sync)))
    assert sync[-1]["args"]["received"] > 0
    # One bounded call: dispatch, then the sync that waits for it, both
    # inside run_to_target.
    for d, s in zip(dispatch, sync):
        assert run["ts"] <= d["ts"] <= d["ts"] + d["dur"] <= s["ts"]
        assert s["ts"] + s["dur"] <= run["ts"] + run["dur"]


def _xplane(trace_dir):
    import glob

    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                       recursive=True)
    return ProfileData.from_file(path)


def _host_events(pd):
    """(name, start_ns, stats) of the profile's host events, without the
    Python tracer's function events."""
    return [(ev.name, ev.start_ns, dict(ev.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events
            if not ev.name.startswith("$")]


def test_span_is_a_profiler_annotation_without_tracer(tmp_path):
    """With no active Tracer a program span still lands in any profiler
    session, with its category and entry args as stats, and yields None."""
    import jax

    assert trace.active() is None
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("probe.span", cat="test", call=2) as sp:
            assert sp is None
    [(_, _, stats)] = [e for e in _host_events(_xplane(tmp_path))
                       if e[0] == "probe.span"]
    assert stats == {"cat": "test", "call": 2}


def test_program_spans_reach_a_profiler_session(tmp_path):
    """A plain CLI job under an outside profiler (no -trace, no -xprof):
    the program's boundaries are all on the host plane as `cat` spans."""
    import jax

    cfg = Config(**BASE, engine="ring").validate()
    with jax.profiler.trace(str(tmp_path)):
        run_simulation(cfg, printer=ProgressPrinter(enabled=False))
    cats = {name: stats["cat"] for name, _, stats in
            _host_events(_xplane(tmp_path)) if "cat" in stats}
    assert cats == {"init": "phase", "phase1.quiesce": "phase",
                    "seed": "device", "phase2.run_to_target": "phase",
                    "phase2.dispatch": "device", "phase2.sync": "device",
                    "telemetry.fetch": "device", "stats": "device"}


def test_trace_json_and_xprof_share_a_clock(tmp_path):
    """-trace and -xprof together: phase2.run_to_target's start in the JSON
    (clock_origin_unix_ns + ts) and in the xplane (profile_start_time +
    start_ns) is the same instant, within 1 ms."""
    xdir = tmp_path / "xprof"
    cfg = Config(n=2000, backend="jax", graph="kout", fanout=6, seed=4,
                 coverage_target=0.9, trace=str(tmp_path / "t.json"),
                 xprof_dir=str(xdir)).validate()
    run_simulation(cfg, printer=ProgressPrinter(enabled=False))
    doc = json.load(open(tmp_path / "t.json"))
    [run] = [e for e in doc["traceEvents"]
             if e["name"] == "phase2.run_to_target"]
    json_ns = doc["metadata"]["clock_origin_unix_ns"] + run["ts"] * 1e3
    pd = _xplane(xdir)
    env = next(p for p in pd.planes if p.name == "Task Environment")
    start = dict(env.stats)["profile_start_time"]
    [xp] = [t for name, t, _ in _host_events(pd)
            if name == "phase2.run_to_target"]
    assert abs(start + xp - json_ns) <= 1e6


@pytest.mark.parametrize("telemetry", ["on", "off"])
def test_ring_engine_named_scopes(telemetry):
    """The ring engine's compiled run program carries the four phase-2
    scope names on its op_name paths (profiles attribute device time by
    them); the scopes are metadata only."""
    import re

    from gossip_simulator_tpu.backends import make_stepper

    cfg = Config(n=2000, backend="jax", graph="kout", fanout=6, seed=3,
                 engine="ring", telemetry=telemetry).validate()
    st = make_stepper(cfg)
    st.init()
    st.overlay_window()
    args = (st.state, st.key, np.int32(1800), np.int32(64))
    if st._telem is not None:
        args += (st._telem.begin_gossip(),)
    txt = st._run_fn.lower(*args).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', txt)
    for scope in ("combine", "emit", "deliver", "poll"):
        assert any(f"/{scope}/" in p for p in paths), scope


@pytest.mark.parametrize("telemetry", ["on", "off"])
def test_sharded_ring_engine_named_scopes(telemetry):
    """The sharded ring engine's run program at S = 4 carries the four
    phase-2 scope names and `route` (the all_to_all with its pack and
    unpack) on its op_name paths; the exchange itself is under route."""
    import re

    from gossip_simulator_tpu.backends.sharded import ShardedStepper

    cfg = Config(n=4000, backend="sharded", graph="kout", fanout=6, seed=3,
                 engine="ring", telemetry=telemetry).validate()
    st = ShardedStepper(cfg, n_devices=4)
    st.init()
    args = (st.state, st.key, np.int32(3600), np.int32(64))
    if st._telem is not None:
        args += (st._telem.begin_gossip(),)
    txt = st._run_fn.lower(*args).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', txt)
    for scope in ("combine", "emit", "route", "deliver", "poll"):
        assert any(f"/{scope}/" in p for p in paths), scope
    a2a = re.findall(r'all-to-all\(.*op_name="([^"]*)"', txt)
    assert a2a and all("/route/" in p for p in a2a), a2a


def test_tracer_spans_nest_and_null_path():
    t = trace.Tracer()
    with trace.activated(t):
        with trace.span("outer", cat="test", k=1) as sp:
            assert sp == {"k": 1}
            sp["extra"] = 2
            with trace.span("inner"):
                pass
        trace.instant("mark", note="x")
    assert trace.active() is None
    names = [e["name"] for e in t.events]
    assert names == ["inner", "outer", "mark"]  # children close first
    assert t.events[1]["args"] == {"k": 1, "extra": 2}
    # Inactive module-level span is a shared no-op context.
    with trace.span("ignored") as sp:
        assert sp is None
    assert len(t.events) == 3


# ---------------------------------------------------------------------------
# Comparator self-tests
# ---------------------------------------------------------------------------

def test_compare_runs_twin_and_perturbed(tmp_path, capsys):
    comparator = _load_script("compare_runs")
    a = tmp_path / "a"
    b = tmp_path / "b"
    c = tmp_path / "c"
    _run(tmp_path, "a", run_dir=a, seed=4)
    _run(tmp_path, "b", run_dir=b, seed=4, telemetry="off")
    _run(tmp_path, "c", run_dir=c, seed=5)

    # Same-seed twin pair (even across telemetry paths): exit 0.
    assert comparator.main([str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "MATCH" in out

    # Perturbed seed: exit 1, naming the first divergent window.
    assert comparator.main([str(a), str(c)]) == 1
    out = capsys.readouterr().out
    assert "DIVERGED" in out
    assert "first divergent window:" in out

    # Missing dir: exit 2.
    assert comparator.main([str(a), str(tmp_path / "nope")]) == 2


def test_check_bench_roundtrip(tmp_path, monkeypatch, capsys):
    """--update then compare on a stubbed single-row capture set: the
    roundtrip passes, and a perturbed fresh capture fails naming the
    field."""
    checker = _load_script("check_bench")
    import bench

    monkeypatch.setattr(
        bench, "cpu_scale_rows",
        lambda seed: [("tiny", Config(
            n=1200, graph="kout", fanout=6, seed=seed, crashrate=0.0,
            coverage_target=0.9, backend="jax", progress=False,
            max_rounds=500))])
    monkeypatch.setattr(checker, "BASELINE",
                        str(tmp_path / "baseline.json"))
    assert checker.main(["--update"]) == 0
    assert checker.main([]) == 0
    capsys.readouterr()

    # Perturb the committed baseline: the fresh capture must FAIL on it.
    doc = json.load(open(tmp_path / "baseline.json"))
    doc["rows"]["tiny"]["total_message"] += 1
    json.dump(doc, open(tmp_path / "baseline.json", "w"))
    assert checker.main([]) == 1
    assert "tiny.total_message" in capsys.readouterr().out

    # Missing baseline: exit 2.
    os.remove(tmp_path / "baseline.json")
    assert checker.main([]) == 2
