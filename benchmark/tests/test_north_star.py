"""The four-chip north-star cell (si_kout_north_star.4chip): its files
resolve to the sharded ring engine at 100M nodes, its reader gives
nothing without phase-2 spans and phase 2's time per poll window with
them, and a whole run at a small size on four CPU devices is correct
against the reference."""

import json
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "si_kout_north_star.4chip"
READER = "mesh_phase2_window_ms"


def _ctx(sims):
    return {"sims": sims, "traced": None, "config": {"fanout": 6},
            "n": 100_000_000, "chips": 4, "backend": "sharded",
            "trace": None, "device_kind": "TPU v5 lite"}


def test_cell_files_load():
    import harness

    spec = harness.load_cell(CELL)
    assert spec["chips"] == 4
    assert spec["config"]["name"] == "si_kout_north_star"
    assert spec["config"]["n_published"] == 100_000_000
    assert spec["traffic"]["name"] == "one_broadcast_mesh"
    assert [m["name"] for m in spec["per_layer"]] == [READER]
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_s", "setup_s"}
    assert set(spec["cell"]["limits"]) == {"unconverged", "differing",
                                           "overflow", "shape_gap"}


def test_argv_runs_the_sharded_ring_engine_at_100m():
    import harness
    import traffic

    spec = harness.load_cell(CELL)
    argv = traffic.program_argv(spec["config"], spec["traffic"], 4,
                                2**31 + 99)
    pairs = list(zip(argv, argv[1:]))
    assert ("-backend", "sharded") in pairs
    assert ("-engine", "ring") in pairs
    assert ("-n", "100000000") in pairs
    from gossip_simulator_tpu.config import parse_args

    cfg = parse_args(argv)
    assert (cfg.backend, cfg.engine_resolved, cfg.n) == (
        "sharded", "ring", 100_000_000)


def test_reader_gives_nothing_without_phase2_spans():
    import harness

    assert harness.reader(READER).read(_ctx([{"spans": {}}])) is None


def test_reader_divides_out_the_windows():
    import harness

    sims = [{"spans": {"phase2": 1.7}, "coverage_ms": 170},
            {"spans": {"phase2": 2.0}, "coverage_ms": 175}]
    assert harness.reader(READER).read(_ctx(sims)) == pytest.approx(
        1000.0 * 3.7 / (17 + 18))


RUN = """
import json, sys
sys.path.insert(0, {bench!r})
import harness
out = [harness.run({cell!r}, 2**31 + 4321, 0.0, trace,
                   require_tpu=False, nodes_per_chip=100_000)
       for trace in (False, True)]
print(json.dumps(out))
"""


def test_cell_is_correct_on_four_cpu_devices():
    """The whole run, untraced and traced, at 100 000 nodes per device on
    four CPU devices: the sharded ring engine against gossip_ref."""
    from gossip_simulator_tpu.utils.jaxsetup import forced_cpu_env

    proc = subprocess.run(
        [sys.executable, "-c", RUN.format(bench=BENCH, cell=CELL)],
        cwd=ROOT, env=forced_cpu_env(4), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    plain, traced = json.loads(proc.stdout.strip().splitlines()[-1])
    for res in (plain, traced):
        assert res["correct"] is True, res["compared"]
        assert res["device"]["count"] == 4
        assert res["compared"]["overflow"]["value"] == 0
    assert set(plain["metrics"]) == {"sim_s", "setup_s"}
    # The cell's reader is host-timed, so it reads on the CPU too.
    assert set(traced["metrics"]) == {READER}
