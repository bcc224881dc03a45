"""Phase-2 megakernel (-phase2-kernel, ISSUE 18).

Same three-layer shape as test_pallas_deliver (the PR-6 gate this one
twins), all in interpret mode on CPU:

* Unit parity: each fused pass against the XLA chain it replaces --
  fused_emit vs the append_messages reservation chain (partition mask,
  duplicate filter, SIR trigger lane, word rows), fused_recv_land vs
  decode + filter + mailbox.ring_append, fused_drain_sum vs chunked
  deposit_sum (including chunk-split commutation, which is what lets the
  sharded engine's pmax-agreed chunks collapse to one static scan), and
  fused_deposit_both vs the deposit_local/deposit_rumors pair.
* Trajectory pins + A/B: `-phase2-kernel xla` must reproduce the
  pre-megakernel trajectories bit for bit (hashes below were captured on
  the commit before this PR), and pallas must match xla on every engine
  combo, S=1/S=8, R=1/R=16, pushsum, and the partition-scenario corner.
  Sharded event combos pin exchange_pipeline="off" so the fused
  receive-side landing (not the pipelined PR-6 path) is what runs.
* Gate policy: auto and xla resolve to xla with no probe, explicit
  pallas runs the kernels (interpret mode here), bogus values are
  rejected at validate() time, and checkpoints resume across gates in
  both directions.
"""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import epidemic
from gossip_simulator_tpu.ops import mailbox as mb
from gossip_simulator_tpu.ops import pallas_megakernel as mk
from gossip_simulator_tpu.utils import checkpoint

I32 = jnp.int32

BASE = dict(graph="kout", fanout=6, seed=3, crashrate=0.01,
            coverage_target=0.95, progress=False)


def _fingerprint(cfg, max_windows=400):
    """test_multirumor.py's per-window trajectory hash, verbatim."""
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


def _pushsum_fingerprint(cfg, max_windows=400):
    """Pushsum twin: relerr lives on device state, not Stats."""
    from gossip_simulator_tpu.backends import make_stepper

    s = make_stepper(cfg)
    s.init()
    while not s.overlay_window()[2]:
        pass
    s.seed()
    rows = []
    for _ in range(max_windows):
        st = s.gossip_window()
        rp, et = (int(v) for v in jax.device_get(
            (s.state.relerr_ppb, s.state.eps_tick)))
        rows.append((st.round, st.total_received, st.total_message, rp))
        if s.exhausted or et >= 0:
            break
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


def _stepper(cfg):
    from gossip_simulator_tpu.backends import make_stepper

    s = make_stepper(cfg)
    s.init()
    while not s.overlay_window()[2]:
        pass
    s.seed()
    return s


# --------------------------------------------------------------------------
# Unit parity: fused passes vs the XLA chains they replace
# --------------------------------------------------------------------------

def _emit_reference(cnt0, sf, drop, sv, ws, off, dw, cap, b, *, tb=None,
                    strig=None, sid=None, pmask=None, flags=None,
                    rbit=1, swords=None, ring_len=None):
    """NumPy replica of the append_messages reservation chain in kernel
    lane order: partition block, duplicate filter, trigger lane (not
    gated on svalid), weighted-prefix reservation over ALL valid
    senders, dual-ring write with unique trash lanes."""
    m, k = sf.shape
    kw = k + (1 if tb is not None else 0)
    L = ring_len if ring_len is not None else dw * cap + m * kw
    ids = np.zeros(L, np.int64)
    words = (None if swords is None
             else np.zeros((L, swords.shape[1]), np.int64))
    vcnt = np.zeros(dw, np.int64)
    adds = np.zeros(dw, np.int64)
    sup = np.zeros(dw, np.int64)
    lost = blk = 0
    for i in range(m):
        v = bool(sv[i])
        evs, pays, ec, dc, bn = [], [], 0, 0, 0
        for kk in range(k):
            f = int(sf[i, kk])
            e = v and not drop[i, kk] and f >= 0
            if pmask is not None and e and pmask[i, kk]:
                bn += 1
                e = False
            if flags is not None and e and (int(flags[max(f, 0)]) & rbit):
                dc += 1
                e = False
            evs.append(e)
            pays.append(f * b + int(off[i]))
            ec += e
        if tb is not None:
            et = bool(strig[i])
            evs.append(et)
            pays.append(tb + int(sid[i]) * b + int(off[i]))
            ec += et
        sc = int(ws[i])
        start = int(cnt0[sc]) + int(vcnt[sc])
        okr = v and (start + ec <= cap)
        vcnt[sc] += ec if v else 0
        adds[sc] += ec if okr else 0
        sup[sc] += dc
        lost += 0 if okr else ec
        blk += bn
        col = 0
        for kk in range(kw):
            e = evs[kk]
            flat = (sc * cap + start + col if (e and okr)
                    else dw * cap + i * kw + kk)
            ids[flat] = pays[kk] if e else 0
            if words is not None:
                words[flat] = swords[i] if e else 0
            col += e
    return ids, adds, sup, lost, blk, words


@pytest.mark.parametrize("variant", ["plain", "part_dup", "trig_words"])
def test_emit_parity(variant):
    """fused_emit vs the NumPy replica of append_messages' reservation
    chain, across the mask/trigger/word-row feature corners with dead
    rows, overflow, duplicates and blocked edges all present."""
    rng = np.random.default_rng(21)
    m, k, dw, cap, b = 24, 4, 3, 10, 8
    n = 12
    sf = rng.integers(-1, n, (m, k))
    drop = rng.random((m, k)) < 0.2
    sv = rng.random(m) < 0.85
    ws = rng.integers(0, dw, m)
    off = rng.integers(0, b, m)
    cnt0 = rng.integers(0, 2, dw)
    kwargs, refkw = {}, {}
    if variant == "part_dup":
        pmask = rng.random((m, k)) < 0.25
        flags = rng.integers(0, 2, n).astype(np.uint8)
        kwargs = dict(pmask=jnp.asarray(pmask, I32),
                      flags=jnp.asarray(flags))
        refkw = dict(pmask=pmask, flags=flags)
    elif variant == "trig_words":
        W = 2
        strig = rng.random(m) < 0.3
        sid = rng.integers(0, n, m)
        swords = rng.integers(1, 99, (m, W))
        kwargs = dict(tb=n * b, strig=jnp.asarray(strig, I32),
                      sender_ids=jnp.asarray(sid, I32),
                      swords=jnp.asarray(swords, np.uint32),
                      mail_words=jnp.zeros((dw * cap + m * (k + 1), W),
                                           jnp.uint32))
        refkw = dict(tb=n * b, strig=strig, sid=sid, swords=swords)
    kw_lanes = k + (1 if variant == "trig_words" else 0)
    ring0 = jnp.zeros((dw * cap + m * kw_lanes,), I32)
    out = mk.fused_emit(ring0, jnp.asarray(cnt0[None, :], I32),
                        jnp.asarray(sf, I32), jnp.asarray(drop),
                        jnp.asarray(sv), jnp.asarray(ws, I32),
                        jnp.asarray(off, I32), dw=dw, cap=cap, b=b,
                        interpret=True, **kwargs)
    fi, fad, fsu, flo, fbl = out[:5]
    xi, xad, xsu, xlo, xbl, xw = _emit_reference(
        cnt0, sf, drop, sv, ws, off, dw, cap, b,
        ring_len=int(ring0.shape[0]), **refkw)
    assert (np.asarray(fi) == xi).all()
    assert (np.asarray(fad) == xad).all()
    assert (np.asarray(fsu) == xsu).all()
    assert int(flo) == xlo
    if variant == "part_dup":
        assert int(fbl) == xbl
    if variant == "trig_words":
        assert (np.asarray(out[5]) == xw).all()


@pytest.mark.parametrize("dual", [False, True], ids=["ids", "ids_words"])
def test_recv_land_parity(dual):
    """fused_recv_land vs decode + duplicate filter + ring_append on a
    random wire batch with empty slots, overflow and duplicates."""
    rng = np.random.default_rng(22)
    dw, cap, b, nl, m, W = 3, 5, 4, 6, 80, 2
    wire = rng.integers(0, nl * dw * b, m)
    wire = np.where(rng.random(m) < 0.75, wire, -1)
    recv = jnp.asarray(wire, I32)
    flags = rng.integers(0, 2, nl).astype(np.uint8)
    ring0 = jnp.zeros((dw * cap + 1,), I32)
    cnt0 = jnp.asarray(rng.integers(0, 2, (1, dw)), I32)
    kwargs = {}
    if dual:
        wv = jnp.asarray(rng.integers(1, 99, (m, W)), np.uint32)
        kwargs = dict(words=wv,
                      mail_words=jnp.zeros((dw * cap + 1, W), jnp.uint32))
    out = mk.fused_recv_land(ring0, cnt0, jnp.zeros((), I32), recv,
                             dw=dw, cap=cap, b=b,
                             flags=jnp.asarray(flags), interpret=True,
                             **kwargs)
    fi, fc, fd, fs = out[0], out[1], out[2], out[3]
    rv = recv >= 0
    r = jnp.maximum(recv, 0)
    rd, rw, ro = r // (dw * b), (r // b) % dw, r % b
    dup = rv & ((jnp.asarray(flags).at[rd].get() & jnp.uint8(1)) > 0)
    xs = ((rw[:, None] == jnp.arange(dw, dtype=I32)[None, :])
          & dup[:, None]).sum(axis=0, dtype=I32)
    rv = rv & ~dup
    if dual:
        wvx = jnp.where(rv[:, None], kwargs["words"], jnp.uint32(0))
        (xi, xw), xc, xd = mb.ring_append(
            (ring0, kwargs["mail_words"]), cnt0, jnp.zeros((), I32),
            (rd * b + ro, wvx), rw, rv, dw, cap)
        assert (out[4] == xw).all()
    else:
        (xi,), xc, xd = mb.ring_append(
            (ring0,), cnt0, jnp.zeros((), I32), (rd * b + ro,), rw, rv,
            dw, cap)
    assert (fi == xi).all() and (fc == xc).all() and int(fd) == int(xd)
    assert (fs == xs).all()


def test_drain_sum_parity():
    """fused_drain_sum vs deposit_sum on the live prefix of one slot,
    and vs the same adds applied in two arbitrary chunks (integer adds
    commute -- this is what subsumes the sharded pmax chunk loop)."""
    rng = np.random.default_rng(23)
    n, cols, cap, b, dw = 7, 3, 24, 4, 2
    ids = jnp.asarray(rng.integers(0, n * b, dw * cap), I32)
    mass = jnp.asarray(rng.integers(-9, 9, (dw * cap, cols)), I32)
    acc0 = jnp.asarray(rng.integers(0, 5, (n, cols)), I32)
    for slot, m in ((0, 0), (0, 17), (1, cap)):
        fa = mk.fused_drain_sum(acc0, ids, mass, jnp.asarray(slot, I32),
                                jnp.asarray(m, I32), cap=cap, b=b,
                                interpret=True)
        lo = slot * cap
        ok = jnp.arange(cap, dtype=I32) < m
        xa = mb.deposit_sum(acc0, ids[lo:lo + cap] // b,
                            mass[lo:lo + cap], ok)
        assert (fa == xa).all(), (slot, m)
        c = 5  # chunk split: same sums in two passes
        xa2 = mb.deposit_sum(acc0, ids[lo:lo + c] // b, mass[lo:lo + c],
                             ok[:c])
        xa2 = mb.deposit_sum(xa2, ids[lo + c:lo + cap] // b,
                             mass[lo + c:lo + cap], ok[c:])
        assert (fa == xa2).all(), (slot, m)


def test_deposit_both_parity():
    """fused_deposit_both vs the deposit_local/deposit_rumors pair on a
    random multi-rumor batch with invalid edges."""
    rng = np.random.default_rng(24)
    B, n, k, W = 4, 9, 5, 3
    m = n * k
    pending = jnp.asarray(rng.integers(0, 3, (B, n)), I32)
    pr = jnp.asarray(rng.integers(0, 3, (B, n, W)), I32)
    slots = jnp.asarray(rng.integers(0, B, m), I32)
    valid = jnp.asarray(rng.random(m) < 0.7)
    dst = jnp.asarray(rng.integers(0, n, m), I32)
    newbits = jnp.asarray(rng.random((n, W)) < 0.5)
    fp_, fpr = mk.fused_deposit_both(pending, pr, dst, slots, valid,
                                     newbits, interpret=True)
    xp = epidemic.deposit_local(pending.reshape(-1), dst, slots, valid, n=n)
    xpr = epidemic.deposit_rumors(pr, dst, slots, valid, newbits)
    assert (fp_.reshape(-1) == xp).all() and (fpr == xpr).all()


# --------------------------------------------------------------------------
# Trajectory pins + A/B: xla must reproduce pre-PR runs bit for bit,
# pallas must match xla.  Hashes captured on the commit before this PR.
# --------------------------------------------------------------------------

_SCEN = ('{"groups": 2, "events": [{"type": "partition", '
         '"start": 20, "end": 60}]}')

PINNED_COMBOS = {
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

PUSHSUM_COMBOS = {
    # Re-pinned in PR 21: on JAX 0.9 the parent commit's xla path gives
    # e74de6115bb7377b too (0.4.37 gave 15ab340394006f66), so the drift
    # is the installed XLA's, not a code change; the sharded pin held.
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


# The pallas side runs in the TPU-semantics interpreter off-TPU (a host
# callback per ref access): the sharded combos and the pushsum pair take
# 5-15+ min each here (PR 21: pushsum_jax's pallas run 927 s, and passed),
# so they ride this file's explicit tier1.yml step (no marker filter)
# instead of the budgeted sweep -- the test_overlay_kernel policy.
def _sweep(names):
    return [pytest.param(n, marks=pytest.mark.slow)
            if n.startswith(("sharded", "pushsum")) else n
            for n in sorted(names)]


@pytest.mark.parametrize("name", _sweep(PINNED_COMBOS))
def test_engine_fingerprint_pin_and_ab(name):
    pin, kw = PINNED_COMBOS[name]
    fx = _fingerprint(Config(**kw, phase2_kernel="xla").validate())
    assert fx == pin, f"{name}: -phase2-kernel xla drifted from pre-PR"
    fpal = _fingerprint(Config(**kw, phase2_kernel="pallas").validate())
    assert fpal == fx, f"{name}: pallas != xla"


@pytest.mark.parametrize("name", _sweep(PUSHSUM_COMBOS))
def test_pushsum_fingerprint_pin_and_ab(name):
    pin, kw = PUSHSUM_COMBOS[name]
    fx = _pushsum_fingerprint(Config(**kw, phase2_kernel="xla")
                              .validate())
    assert fx == pin, f"{name}: -phase2-kernel xla drifted from pre-PR"
    fpal = _pushsum_fingerprint(Config(**kw, phase2_kernel="pallas")
                                .validate())
    assert fpal == fx, f"{name}: pallas != xla"


# --------------------------------------------------------------------------
# Cross-gate checkpoint interop: the gate changes no state layout
# --------------------------------------------------------------------------

@pytest.mark.parametrize("first,second", [("xla", "pallas"),
                                          ("pallas", "xla")],
                         ids=["xla_to_pallas", "pallas_to_xla"])
def test_cross_gate_checkpoint_resume(tmp_path, first, second):
    kw = dict(**BASE, n=600, backend="jax", engine="event")
    cfg_a = Config(**kw, phase2_kernel=first).validate()
    cfg_b = Config(**kw, phase2_kernel=second).validate()
    s = _stepper(cfg_a)
    for _ in range(3):
        s.gossip_window()
    mid = s.stats()
    path = checkpoint.save(str(tmp_path), 3, s.state_pytree(), mid)
    reference = [s.gossip_window() for _ in range(3)]

    s2 = _stepper(cfg_b)
    tree, _ = checkpoint.load(path)
    s2.load_state_pytree(tree)
    assert s2.stats() == mid
    for want in reference:
        assert s2.gossip_window() == want


# --------------------------------------------------------------------------
# Gate policy
# --------------------------------------------------------------------------

@pytest.mark.parametrize("value", ["auto", "xla"])
def test_auto_and_xla_resolve_to_xla_without_probe(value):
    cfg = Config(n=2000, phase2_kernel=value).validate()
    assert cfg.phase2_kernel_resolved == "xla"
    assert cfg.resolved_gates()["phase2_kernel"] == "xla"


def test_explicit_pallas_resolves_via_interpret():
    cfg = Config(n=2000, phase2_kernel="pallas").validate()
    assert cfg.phase2_kernel_resolved == "pallas"


def test_validate_rejects_unknown_kernel():
    with pytest.raises(ValueError, match="phase2_kernel"):
        Config(n=2000, phase2_kernel="cuda").validate()


def test_resolved_gates_reports_phase2():
    gates = Config(n=2000, backend="jax").validate().resolved_gates()
    assert gates["phase2_kernel"] in ("xla", "pallas", "unavailable")
    gates = Config(n=2000, backend="native").validate().resolved_gates()
    assert gates["phase2_kernel"] is None
