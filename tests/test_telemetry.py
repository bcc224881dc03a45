"""Device-resident telemetry (utils/telemetry.py): fast-path replay parity.

The tentpole contract: an observing run (progress lines or JSONL) without
checkpointing takes the device-side fast path, and the replayed per-window
output is BYTE-identical to the windowed loop's on the same seed -- stdout
and JSONL records, every engine, both phases.  `-telemetry off` restores the
windowed loop, so each variant runs both ways and diffs.
"""

import io
import json

import pytest

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.driver import run_simulation
from gossip_simulator_tpu.utils.metrics import SCHEMA_VERSION, ProgressPrinter


def _capture(tmp_path, tag, **kw):
    cfg = Config(**kw).validate()
    buf = io.StringIO()
    p = tmp_path / f"{tag}.jsonl"
    with ProgressPrinter(enabled=True, jsonl_path=str(p),
                         out=buf) as printer:
        res = run_simulation(cfg, printer=printer)
    recs = [json.loads(line) for line in p.read_text().splitlines()]
    return buf.getvalue(), recs, res


# SI and SIR on both JAX backends (the ISSUE's parity matrix), plus the
# ring engine, both overlay modes (phase-1 replay) and a dieout run (the
# nonconvergence reason must survive the replay).
VARIANTS = {
    "si_event_jax": dict(n=1500, backend="jax", graph="kout", fanout=6,
                         seed=4, coverage_target=0.9),
    "sir_event_jax": dict(n=1500, backend="jax", graph="kout",
                          protocol="sir", removal_rate=0.2, fanout=8,
                          seed=3, coverage_target=0.8),
    "si_ring_jax": dict(n=1500, backend="jax", graph="kout", engine="ring",
                        fanout=6, seed=4, coverage_target=0.9),
    "overlay_ticks_jax": dict(n=1000, backend="jax", graph="overlay",
                              overlay_mode="ticks", fanout=5, seed=9,
                              coverage_target=0.9),
    "overlay_rounds_jax": dict(n=1000, backend="jax", graph="overlay",
                               overlay_mode="rounds", fanout=5, seed=9,
                               coverage_target=0.9),
    "si_event_sharded": dict(n=2000, backend="sharded", graph="kout",
                             fanout=6, seed=5, crashrate=0.0,
                             coverage_target=0.9),
    "sir_event_sharded": dict(n=2000, backend="sharded", graph="kout",
                              protocol="sir", removal_rate=0.25, fanout=6,
                              seed=5, crashrate=0.0, coverage_target=0.8),
    "dieout_jax": dict(n=1500, backend="jax", graph="kout", seed=1,
                       droprate=0.97, max_rounds=300, crashrate=0.0),
}


def _strip(rec):
    # Wall clocks differ between runs by construction; everything else in
    # the shared stream must match field-for-field.
    return {k: v for k, v in rec.items() if k not in ("wall_s", "phases_s")}


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_fast_path_replay_byte_identical(tmp_path, name):
    kw = VARIANTS[name]
    out_f, rec_f, res_f = _capture(tmp_path, "fast", **kw)
    out_w, rec_w, res_w = _capture(tmp_path, "win", telemetry="off", **kw)
    assert out_f == out_w  # stdout bytes
    fast = [_strip(r) for r in rec_f if r["event"] != "telemetry"]
    win = [_strip(r) for r in rec_w]
    assert fast == win  # JSONL event-for-event
    assert res_f.converged == res_w.converged
    assert res_f.stats == res_w.stats
    # Prove the observing run actually took the fast path: the telemetry
    # record carries a recorded gossip-window trajectory only then.
    telem = [r for r in rec_f if r["event"] == "telemetry"]
    assert telem and telem[0]["gossip_windows"] == res_f.gossip_windows


def test_result_record_schema(tmp_path):
    _, recs, res = _capture(tmp_path, "res", n=1500, backend="jax",
                            graph="kout", fanout=6, seed=4,
                            coverage_target=0.9)
    assert all(r["schema_version"] == SCHEMA_VERSION for r in recs)
    result = [r for r in recs if r["event"] == "result"]
    assert len(result) == 1
    r = result[0]
    assert r["converged"] is True and r["reason"] is None
    assert r["gossip_windows"] == res.gossip_windows
    assert r["total_message"] == res.stats.total_message
    assert "phases_s" in r and "init_s" in r["phases_s"]
    # result precedes telemetry at the tail of the stream
    assert recs[-1]["event"] == "telemetry"
    assert recs[-2]["event"] == "result"


def test_telemetry_per_window_consistency(tmp_path):
    _, recs, res = _capture(tmp_path, "tw", n=1500, backend="jax",
                            graph="kout", fanout=6, seed=4,
                            coverage_target=0.9)
    t = [r for r in recs if r["event"] == "telemetry"][0]
    per = t["per_window"]
    assert len(per["tick"]) == t["gossip_windows"] == res.gossip_windows
    assert per["received"][-1] == res.stats.total_received
    assert per["message"][-1] == res.stats.total_message
    assert sum(t["deltas"]["received"]) == res.stats.total_received
    assert sum(t["deltas"]["message"]) == res.stats.total_message
    cov = [r for r in recs if r["event"] == "coverage"]
    assert len(cov) == res.gossip_windows


def test_exchange_inflight_hwm_column(tmp_path):
    """The ISSUE-13 pipeline-depth column: a sharded run on the 8-device
    shim (auto -> double) records 2 in every window, a forced-serial run
    records 1, and single-device builds record 0 -- an all-zero column
    the summary omits (like the scenario columns)."""
    _, recs, _ = _capture(tmp_path, "xp2", **VARIANTS["si_event_sharded"])
    t = [r for r in recs if r["event"] == "telemetry"][0]
    assert (t["per_window"]["exchange_inflight_hwm"]
            == [2] * t["gossip_windows"])
    _, recs1, _ = _capture(tmp_path, "xp1", exchange_pipeline="off",
                           **VARIANTS["si_event_sharded"])
    t1 = [r for r in recs1 if r["event"] == "telemetry"][0]
    assert (t1["per_window"]["exchange_inflight_hwm"]
            == [1] * t1["gossip_windows"])
    _, recs0, _ = _capture(tmp_path, "xp0", **VARIANTS["si_event_jax"])
    t0 = [r for r in recs0 if r["event"] == "telemetry"][0]
    assert "exchange_inflight_hwm" not in t0["per_window"]


def test_exhausted_reason_on_fast_path(tmp_path):
    out, recs, res = _capture(tmp_path, "die", **VARIANTS["dieout_jax"])
    assert not res.converged
    assert res.stats.exhausted is True
    assert "(exhausted: no messages in flight)" in out
    r = [x for x in recs if x["event"] == "result"][0]
    assert r["reason"] == "exhausted: no messages in flight"
    assert r["exhausted"] is True


def test_telemetry_summary_block(tmp_path):
    cfg = Config(n=1500, backend="jax", graph="kout", fanout=6, seed=4,
                 coverage_target=0.9, telemetry_summary=True).validate()
    buf = io.StringIO()
    with ProgressPrinter(enabled=False, out=buf) as printer:
        run_simulation(cfg, printer=printer)
    out = buf.getvalue()
    assert "=== Telemetry ===" in out
    assert "phases:" in out and "throughput:" in out


def test_checkpointing_keeps_windowed_loop(tmp_path):
    # Checkpointing observes real per-window state the history cannot
    # carry, so it must still run the windowed loop (and write snapshots)
    # even with telemetry on.
    cfg = Config(n=1500, backend="jax", graph="kout", fanout=6, seed=4,
                 coverage_target=0.9, checkpoint_every=2,
                 checkpoint_dir=str(tmp_path / "ckpt")).validate()
    with ProgressPrinter(enabled=False) as printer:
        res = run_simulation(cfg, printer=printer)
    assert res.converged
    snaps = list((tmp_path / "ckpt").glob("state_*.npz"))
    assert snaps, "checkpointed run wrote no snapshots -- fast path taken?"


def test_printer_context_manager_closes_on_exception(tmp_path):
    p = tmp_path / "boom.jsonl"
    try:
        with ProgressPrinter(enabled=False, jsonl_path=str(p)) as printer:
            printer.section("Doomed")
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert printer._jsonl is None  # closed by __exit__
    recs = [json.loads(line) for line in p.read_text().splitlines()]
    # v3: the lazily-written column header precedes the first real record.
    assert [r["event"] for r in recs] == ["header", "section"]


def test_telemetry_off_quiet_run_unchanged(tmp_path):
    # The pre-telemetry quiet fast path must be exactly what -telemetry
    # off still runs: no histories, no telemetry record, same totals.
    base = dict(n=1500, backend="jax", graph="kout", fanout=6, seed=4,
                coverage_target=0.9, progress=False)
    r_on = run_simulation(Config(**base).validate(),
                          printer=ProgressPrinter(enabled=False))
    r_off = run_simulation(Config(telemetry="off", **base).validate(),
                           printer=ProgressPrinter(enabled=False))
    assert r_on.stats == r_off.stats
    assert r_on.gossip_windows == r_off.gossip_windows


def test_compile_s_is_first_dispatch():
    """compile_s is the host time of the first bounded call's dispatch
    (trace, lowering, compile or cache load, enqueue); the rest of that
    call and every later call are execute_s -- per phase."""
    from gossip_simulator_tpu.utils.telemetry import TelemetrySession

    s = TelemetrySession(Config(n=100))
    s.tally_gossip_call(0.75, 1.0)
    s.tally_gossip_call(0.01, 0.5)
    s.tally_overlay_call(0.25, 0.5)
    s.tally_overlay_call(0.02, 0.25)
    assert s.phases == pytest.approx({"compile_s": 1.0,
                                      "execute_s": 0.25 + 0.5 + 0.25 + 0.25})
