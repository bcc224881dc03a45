"""Autotuner + tuning-table correctness (ISSUE 12).

Three layers of protection around the tuned-constant surface:

* registry defaults are bit-identical to the constants they replaced --
  a registry-wired build with no table IS the old build (fingerprint
  pins on all four engine combos, test_multirumor convention, and the
  committed TUNING_TABLE.json must leave them unchanged too);
* the neutrality gate: a deliberately non-neutral planted candidate
  (slot_headroom=0.01 collapses the mail-ring cap -> counted drops ->
  trajectory divergence) must come back rejected and logged;
* the persistence round-trip: a swept winner lands in a table entry
  that Config resolves (resolved_gates names every active entry id) and
  tuning.value returns, persist="gated" values apply only behind a
  matching workload-shape key, entries from different spaces merge
  instead of shadowing, and scripts/compare_runs.py names a
  tuning-table mismatch FIRST when fingerprints diverge.
"""

import hashlib
import importlib.util
import io
import json
import os
import sys

import pytest

from gossip_simulator_tpu import tuning
from gossip_simulator_tpu.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_autotune():
    spec = importlib.util.spec_from_file_location(
        "autotune", os.path.join(REPO, "scripts", "autotune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# Registry: defaults bit-identical to the constants they replaced
# --------------------------------------------------------------------------

# The pre-registry constants, hardcoded here on purpose: editing a
# registered default must trip THIS pin, not silently move the build.
PRE_REGISTRY_DEFAULTS = {
    "overlay.delivery_chunk_base": 65_536,
    "overlay.delivery_chunk_cap": 1_048_576,
    "overlay.adaptive_chunk_max": 8_388_608,
    "overlay.spill_margin": 1.6,
    "overlay_ticks.delivery_chunk_cap": 2_097_152,
    "exchange.rank_max_shards": 16,
    "exchange.chernoff_pad": 8,
    # Pipelined exchange (ISSUE 13): depth 2 IS the serial trajectory --
    # the schedule overlaps, the bits are pinned identical (test_sharded's
    # off-vs-double pins); chunk 0 = inherit sender_compaction_cap.
    "exchange.pipeline_depth": 2,
    "exchange.pipeline_chunk": 0,
    "event.slot_headroom": 1.5,
    "event.drain_chunk_floor": 131_072,
    "event.drain_chunk_hi": 1_048_576,
    "event.drain_chunk_hi_lowdeg": 524_288,
    "event.drain_chunk_hi_suppress": 4_194_304,
    "pallas_graph.block_rows": 512,
    "config.overlay_ticks_auto_max": 10_000_000,
}


def test_registry_defaults_bit_identical():
    assert set(tuning.REGISTRY) == set(PRE_REGISTRY_DEFAULTS)
    for name, want in PRE_REGISTRY_DEFAULTS.items():
        t = tuning.REGISTRY[name]
        assert t.default == want, name
        assert want in t.candidates, name
        # No cfg, no table, no override: value() IS the old constant.
        assert tuning.value(name) == want, name


def test_registry_spaces_reference_registered_tunables():
    for space in tuning.SPACES.values():
        for name in space.tunables:
            assert name in tuning.REGISTRY, (space.name, name)
        # The workload dict must be a valid Config shape.
        Config(n=3000, **space.workload).validate()


def test_override_context_unknown_name_raises_and_restores():
    with pytest.raises(KeyError):
        with tuning.override({"nope.nothing": 1}):
            pass
    with tuning.override({"event.drain_chunk_floor": 4096}):
        assert tuning.value("event.drain_chunk_floor") == 4096
    assert tuning.value("event.drain_chunk_floor") == 131_072


# --------------------------------------------------------------------------
# Fingerprint pins: no table == committed table == pre-registry build
# (pinned hashes recorded pre-multirumor, test_multirumor convention)
# --------------------------------------------------------------------------

BASE = dict(graph="kout", fanout=6, seed=3, crashrate=0.01,
            coverage_target=0.95, progress=False)

FP_COMBOS = {
    "jax_event": dict(n=3000, backend="jax", engine="event"),
    "jax_ring": dict(n=3000, backend="jax", engine="ring"),
    "sharded_event": dict(n=4000, backend="sharded", engine="event"),
    "sharded_ring": dict(n=4000, backend="sharded", engine="ring"),
}

PINNED_HASH = {
    "jax_event": "477b07759900a563",
    "jax_ring": "33a08f76cf24827b",
    "sharded_event": "b8c00f159feac434",
    "sharded_ring": "a7f0a9290df481e5",
}


def _fingerprint(cfg, max_windows=400) -> str:
    from gossip_simulator_tpu.backends import make_stepper

    s = make_stepper(cfg)
    s.init()
    while not s.overlay_window()[2]:
        pass
    s.seed()
    rows = []
    for _ in range(max_windows):
        st = s.gossip_window()
        rows.append((st.round, st.total_received, st.total_message,
                     st.total_crashed, st.total_removed))
        if st.coverage >= cfg.coverage_target or s.exhausted:
            break
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(FP_COMBOS))
def test_no_table_and_committed_table_bit_identical(name):
    """-tuning-table off and the committed TUNING_TABLE.json (via auto)
    must both reproduce the pre-registry pinned trajectory: the registry
    wiring is invisible, and every committed entry is neutral."""
    off = Config(**BASE, **FP_COMBOS[name], tuning_table="off").validate()
    assert _fingerprint(off) == PINNED_HASH[name]
    auto = Config(**BASE, **FP_COMBOS[name], tuning_table="auto").validate()
    assert _fingerprint(auto) == PINNED_HASH[name]


# --------------------------------------------------------------------------
# The sweep: neutrality gate + winner persistence round-trip
# --------------------------------------------------------------------------

def test_sweep_rejects_planted_candidate_and_persists_winner(tmp_path):
    """In-process tiny sweep: the planted slot_headroom=0.01 candidate
    (ring cap collapses 17x under the sized load -> counted mail drops)
    must be rejected and logged; the surviving candidate's entry must
    round-trip through Config/resolved_gates/tuning.value."""
    mod = _load_autotune()
    table = str(tmp_path / "table.json")
    logs = []
    summary = mod.sweep_space(
        "chunk_ladder", 10_000, seed=3, table_file=table,
        workdir=str(tmp_path / "runs"),
        tunable="event.drain_chunk_floor", candidates=[8192],
        plant=("event.slot_headroom", 0.01), log=logs.append)

    planted = [r for r in summary["rows"]
               if r["tunable"] == "event.slot_headroom"]
    assert planted and planted[0]["verdict"] == "rejected", summary["rows"]
    assert any("REJECTED" in line and "slot_headroom" in line
               for line in logs), logs
    # slot_headroom is persist="never": even a passing value never persists.
    assert "event.slot_headroom" not in summary["persisted"]

    doc = json.load(open(table))
    assert doc["schema"] == tuning.TABLE_SCHEMA
    (entry,) = doc["entries"]
    assert entry["space"] == "chunk_ladder"
    assert entry["scale_band"] == "<=1m"
    assert entry["values"], entry
    # drain_chunk_* are persist="gated": their entry MUST carry the swept
    # workload shape (values never apply band-wide) and the id its digest.
    assert entry["shape"] == tuning.workload_shape(
        Config(n=10_000, tuning_table="off",
               **tuning.SPACES["chunk_ladder"].workload).validate())
    assert entry["id"].endswith("/" + tuning.shape_digest(entry["shape"]))
    rejected = {(r["tunable"], r["value"]) for r in summary["rows"]
                if r["verdict"] in ("rejected", "rejected_probe")}
    for name, v in entry["values"].items():
        assert tuning.REGISTRY[name].persist != "never", name
        assert (name, v) not in rejected, (name, v)

    cfg = Config(n=10_000, tuning_table=table,
                 **tuning.SPACES["chunk_ladder"].workload).validate()
    assert cfg.resolved_gates()["tuning_table"] == entry["id"]
    for name, v in entry["values"].items():
        assert tuning.value(name, cfg) == v, name
    # A different scale band misses the entry and falls back to defaults.
    big = cfg.replace(n=2_000_000).validate()
    assert big.resolved_gates()["tuning_table"] == "defaults"


def _gated_entry(cfg, values, entry_id="t", space="chunk_ladder"):
    """A schema-valid table entry whose gated values apply to `cfg`."""
    return {"id": entry_id, "platform": tuning._platform()[0],
            "device_kind": "", "scale_band": tuning.scale_band(cfg.n),
            "space": space, "shape": tuning.workload_shape(cfg),
            "values": values}


def test_explicit_cli_flag_outranks_table(tmp_path):
    """The resolution order's top rung: an explicit -event-chunk short-
    circuits at the call site before any table entry is consulted."""
    from gossip_simulator_tpu.models import event

    cfg = Config(n=10_000, fanout=6, graph="kout", backend="jax").validate()
    table = {"schema": tuning.TABLE_SCHEMA, "entries": [
        _gated_entry(cfg, {"event.drain_chunk_floor": 8192})]}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(table))
    cfg = cfg.replace(tuning_table=str(path)).validate()
    assert tuning.value("event.drain_chunk_floor", cfg) == 8192
    explicit = cfg.replace(event_chunk=65_536).validate()
    assert event.drain_chunk(explicit) == min(
        event.slot_cap(explicit), 65_536)


def test_gated_values_require_matching_shape(tmp_path):
    """A persist="gated" value applies ONLY to the workload shape its
    sweep validated: a different shape in the same scale band falls back
    to defaults, and a shapeless gated entry disables the whole table
    (load_table refuses it -- fail toward defaults, never toward a
    mis-applied constant)."""
    cfg = Config(n=10_000, fanout=6, graph="kout", backend="jax").validate()
    table = {"schema": tuning.TABLE_SCHEMA, "entries": [
        _gated_entry(cfg, {"event.drain_chunk_floor": 8192})]}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(table))
    match = cfg.replace(tuning_table=str(path)).validate()
    assert tuning.value("event.drain_chunk_floor", match) == 8192
    # Same platform, same band, different fanout: shape mismatch.
    other = match.replace(fanout=3).validate()
    assert tuning.value("event.drain_chunk_floor", other) == 131_072
    assert other.resolved_gates()["tuning_table"] == "defaults"
    # Gated values without a shape key never load.
    bad = {"schema": tuning.TABLE_SCHEMA, "entries": [{
        "id": "bad", "platform": tuning._platform()[0], "device_kind": "",
        "scale_band": "<=1m", "space": "chunk_ladder",
        "values": {"event.drain_chunk_floor": 8192}}]}
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(bad))
    with pytest.raises(ValueError):
        tuning.load_table(str(bad_path))
    shapeless = cfg.replace(tuning_table=str(bad_path)).validate()
    assert tuning.value("event.drain_chunk_floor", shapeless) == 131_072
    assert shapeless.resolved_gates()["tuning_table"] == "defaults"


def test_entries_merge_across_spaces_without_shadowing(tmp_path):
    """Two spaces persisted for the same (platform, band) must BOTH
    resolve: values merge across entries and resolved_gates stamps every
    active entry id (regression: first-match lookup let one space's
    entry shadow the other back to defaults)."""
    cfg = Config(n=10_000, fanout=6, graph="kout", backend="jax").validate()
    table = {"schema": tuning.TABLE_SCHEMA, "entries": [
        _gated_entry(cfg, {"event.drain_chunk_floor": 8192},
                     entry_id="a/chunk_ladder"),
        {"id": "b/overlay_chunk", "platform": tuning._platform()[0],
         "device_kind": "", "scale_band": "<=1m", "space": "overlay_chunk",
         "values": {"overlay.delivery_chunk_base": 32_768}}]}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(table))
    cfg = cfg.replace(tuning_table=str(path)).validate()
    assert tuning.value("event.drain_chunk_floor", cfg) == 8192
    assert tuning.value("overlay.delivery_chunk_base", cfg) == 32_768
    assert (cfg.resolved_gates()["tuning_table"]
            == "a/chunk_ladder+b/overlay_chunk")


def test_unexercised_candidates_are_not_timed(tmp_path):
    """A candidate whose override cannot change the derived constant at
    the swept shape (drain_chunk_hi above the floor-pinned ramp) must be
    flagged unexercised and skipped -- its neutrality verdict would be
    vacuous and a noise 'win' could persist an unvalidated value."""
    mod = _load_autotune()
    logs = []
    summary = mod.sweep_space(
        "chunk_ladder", 10_000, seed=3, table_file=None,
        workdir=str(tmp_path / "runs"),
        tunable="event.drain_chunk_hi", candidates=[2_097_152],
        log=logs.append)
    (row,) = summary["rows"]
    assert row["verdict"] == "unexercised"
    assert "run_s" not in row
    assert summary["winners"] == {}
    assert summary["baseline"]["run_s"] is None  # nothing was timed
    assert any("UNEXERCISED" in line for line in logs), logs


def test_probe_shapes_vary_seed_and_n_within_band():
    """The cross-shape probe gate for gated winners covers exactly the
    axes the entry's shape key does not pin: seed, and n inside the
    swept scale band."""
    mod = _load_autotune()
    shapes = mod._probe_shapes(262_144, 3, "<=1m")
    assert (262_144, 4) in shapes
    assert any(n != 262_144 and s == 3 and tuning.scale_band(n) == "<=1m"
               for n, s in shapes), shapes


# --------------------------------------------------------------------------
# compare_runs: tuning-table mismatch named FIRST on divergence
# --------------------------------------------------------------------------

def test_compare_runs_names_tuning_mismatch_first(capsys):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import compare_runs
    finally:
        sys.path.pop(0)

    def run(fp, table):
        return {"result": {"fingerprint": fp, "fingerprint_windows": 1},
                "config": {"resolved": {"tuning_table": table}},
                "telemetry": {}, "path": "x"}

    rc = compare_runs.compare(run("aaaa", "defaults"),
                              run("bbbb", "cpu/cpu/<=1m/chunk_ladder"),
                              0.25, False)
    out = capsys.readouterr().out
    assert rc == 1
    mism = out.index("tuning-table mismatch")
    assert mism > out.index("DIVERGED")
    assert mism < out.index("no trajectory array")
    # Identical tables: no mismatch line, divergence still reported.
    compare_runs.compare(run("aaaa", "defaults"), run("bbbb", "defaults"),
                         0.25, False)
    assert "tuning-table mismatch" not in capsys.readouterr().out


# --------------------------------------------------------------------------
# Docs + CLI surface
# --------------------------------------------------------------------------

def test_readme_documents_every_tunable():
    text = open(os.path.join(REPO, "README.md")).read()
    for name in tuning.REGISTRY:
        assert name in text, f"README Tuning section missing {name}"


def test_tuning_table_flag_validates():
    with pytest.raises(ValueError):
        Config(n=3000, tuning_table="/nonexistent/table.json").validate()
    for sel in ("auto", "off"):
        Config(n=3000, tuning_table=sel).validate()
