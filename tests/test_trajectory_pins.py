"""Trajectory pins of the phase-1 and phase-2 engines.

Each case runs one engine combination at CPU scale and hashes its
per-window trajectory; the hash must equal the one recorded when the
combination was first pinned.  A drift means the simulation changed:
a moved RNG stream, a reordered mail ring, a different membership.

* Stats pins: the single-rumor event and ring engines on one device and
  sharded (S=8, the event combo on the serial exchange schedule), the
  16-rumor event engine, and the partition-scenario corner.
* Pushsum pins: the numeric model on one device and sharded.
* Overlay pins: both phase-1 timing models (rounds, ticks) with both
  phase-2 engines, on one device and sharded, the static-boot gate, the
  ticks memory band (slot-major drain + lossless spill, at lowered band
  constants) and the split-round band (SPLIT_ROUND_MIN_ROWS=0), whose
  pin equals the fused round's: split == fused is a bit-identity
  contract.
"""

import hashlib
import json

import jax
import pytest

import gossip_simulator_tpu.config as config_mod
from gossip_simulator_tpu.backends import make_stepper
from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import overlay as ov
from gossip_simulator_tpu.models import overlay_ticks as ot


def _booted(cfg):
    s = make_stepper(cfg)
    s.init()
    while not s.overlay_window()[2]:
        pass
    s.seed()
    return s


def _stats_fingerprint(cfg, max_windows=400):
    """test_multirumor.py's per-window trajectory hash, verbatim."""
    s = _booted(cfg)
    rows = []
    for _ in range(max_windows):
        st = s.gossip_window()
        rows.append((st.round, st.total_received, st.total_message,
                     st.total_crashed, st.total_removed))
        if st.coverage >= cfg.coverage_target or s.exhausted:
            break
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


def _pushsum_fingerprint(cfg, max_windows=400):
    """Pushsum twin: relerr lives on device state, not Stats."""
    s = _booted(cfg)
    rows = []
    for _ in range(max_windows):
        st = s.gossip_window()
        rp, et = (int(v) for v in jax.device_get(
            (s.state.relerr_ppb, s.state.eps_tick)))
        rows.append((st.round, st.total_received, st.total_message, rp))
        if s.exhausted or et >= 0:
            break
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


def _overlay_fingerprint(cfg, max_windows=3000):
    """Per-window trajectory hash over BOTH phases: every overlay window's
    (makeups, breakups) pair, then the gossip phase's stats rows (the
    membership the overlay built feeds the epidemic, so a single flipped
    friend shows up here too)."""
    s = make_stepper(cfg)
    s.init()
    rows = []
    for _ in range(max_windows):
        mk, bk, q = s.overlay_window()
        rows.append((mk, bk))
        if q:
            break
    s.seed()
    for _ in range(400):
        st = s.gossip_window()
        rows.append((st.round, st.total_received, st.total_message,
                     st.total_crashed, st.total_removed))
        if st.coverage >= cfg.coverage_target or s.exhausted:
            break
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


# --------------------------------------------------------------------------
# Stats pins
# --------------------------------------------------------------------------

BASE = dict(graph="kout", fanout=6, seed=3, crashrate=0.01,
            coverage_target=0.95, progress=False)

_SCEN = ('{"groups": 2, "events": [{"type": "partition", '
         '"start": 20, "end": 60}]}')

STATS_PINS = {
    "jax_event": ("31f56f311ac49baf",
                  dict(**BASE, n=600, backend="jax", engine="event")),
    "jax_ring": ("0ca01679a7109dda",
                 dict(**BASE, n=600, backend="jax", engine="ring")),
    "sharded_event": ("90a5c2b304ab7400",
                      dict(**BASE, n=1200, backend="sharded",
                           engine="event", exchange_pipeline="off")),
    "sharded_ring": ("8f897c5e77c90e47",
                     dict(**BASE, n=1200, backend="sharded",
                          engine="ring")),
    "jax_event_r16": ("d06fe7f32c1d38bd",
                      dict(**{**BASE, "crashrate": 0.0}, n=600,
                           backend="jax", engine="event", rumors=16)),
    "jax_event_scen": ("f2cd82638309c371",
                       dict(**{**BASE, "crashrate": 0.0}, n=600,
                            backend="jax", engine="event",
                            scenario=_SCEN)),
}


@pytest.mark.parametrize("name", sorted(STATS_PINS))
def test_stats_trajectory_pin(name):
    pin, kw = STATS_PINS[name]
    assert _stats_fingerprint(Config(**kw).validate()) == pin, name


# --------------------------------------------------------------------------
# Pushsum pins
# --------------------------------------------------------------------------

PUSHSUM_PINS = {
    # On JAX 0.9 the jax pin is e74de6115bb7377b (0.4.37 gave
    # 15ab340394006f66): the installed XLA's drift, not a code change;
    # the sharded pin held across the upgrade.
    "pushsum_jax": ("e74de6115bb7377b",
                    dict(n=512, graph="kout", fanout=6, seed=3,
                         crashrate=0.0, droprate=0.0, backend="jax",
                         model="pushsum", coverage_target=0.9,
                         progress=False)),
    "pushsum_sharded": ("763456a0fb16569a",
                        dict(n=1024, graph="kout", fanout=6, seed=3,
                             crashrate=0.0, droprate=0.0,
                             backend="sharded", model="pushsum",
                             coverage_target=0.9, progress=False)),
}


@pytest.mark.parametrize("name", sorted(PUSHSUM_PINS))
def test_pushsum_trajectory_pin(name):
    pin, kw = PUSHSUM_PINS[name]
    assert _pushsum_fingerprint(Config(**kw).validate()) == pin, name


# --------------------------------------------------------------------------
# Overlay pins (phase 1 and the gossip phase it feeds)
# --------------------------------------------------------------------------

ROUNDS = dict(graph="overlay", overlay_mode="rounds", fanout=5, seed=9,
              backend="jax", progress=False, coverage_target=0.9)
TICKS = dict(graph="overlay", overlay_mode="ticks", fanout=5, seed=9,
             backend="jax", progress=False, coverage_target=0.9)


def _ticks_memory_band(monkeypatch):
    monkeypatch.setattr(ot, "TICKS_SLOTMAJOR_MIN_ROWS", 1000)
    monkeypatch.setattr(config_mod, "MAILBOX_CAP_MEMORY_BAND", 1000)


def _split_round_band(monkeypatch):
    monkeypatch.setattr(ov, "SPLIT_ROUND_MIN_ROWS", 0)


# name -> (pin, Config kwargs, band patch or None)
OVERLAY_PINS = {
    "rounds_jax_event": ("04e0ec088bbd7540",
                         dict(**ROUNDS, n=3000, engine="event"), None),
    "rounds_jax_ring": ("dc19a8b4a1264b0e",
                        dict(**ROUNDS, n=3000, engine="ring"), None),
    "rounds_sharded_event": ("db128648b850ae90",
                             dict(**{**ROUNDS, "backend": "sharded"},
                                  n=2400, engine="event",
                                  exchange_pipeline="off"), None),
    "rounds_sharded_ring": ("901bc268996e9676",
                            dict(**{**ROUNDS, "backend": "sharded"},
                                 n=2400, engine="ring"), None),
    "rounds_static_boot": ("b1559dda440276fc",
                           dict(**ROUNDS, n=3000, engine="event",
                                overlay_static_boot="on"), None),
    "ticks_jax_event": ("14236e8dca90cea8",
                        dict(**TICKS, n=2000, engine="event"), None),
    "ticks_jax_ring": ("18ba4a0566f0662c",
                       dict(**TICKS, n=2000, engine="ring"), None),
    "ticks_sharded_event": ("abceb8eca86a515e",
                            dict(**{**TICKS, "backend": "sharded"},
                                 n=2400, engine="event",
                                 exchange_pipeline="off"), None),
    "ticks_spill_corner": ("34fbce9b5d352777",
                           dict(**TICKS, n=2000, engine="event"),
                           _ticks_memory_band),
    # == rounds_jax_event: split == fused.
    "split_round_corner": ("04e0ec088bbd7540",
                           dict(**ROUNDS, n=3000, engine="event",
                                compact_chunk=256), _split_round_band),
}


@pytest.mark.parametrize("name", sorted(OVERLAY_PINS))
def test_overlay_trajectory_pin(name, monkeypatch):
    pin, kw, band = OVERLAY_PINS[name]
    if band is not None:
        band(monkeypatch)
    assert _overlay_fingerprint(Config(**kw).validate()) == pin, name
