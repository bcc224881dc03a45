"""Fused Pallas delivery kernel (-deliver-kernel, ISSUE 9).

Three layers, all in interpret mode on CPU (the kernels are serial
reference passes there -- correctness surface, not speed):

* Unit parity: every fused wrapper (chunk step, ring append, deposits,
  unique-index scatter) against the XLA form it replaces, including the
  carry-continuation, rank-major, and spill contracts of
  mailbox._compact_chunk_step and the gated public entry points
  (deliver / deliver_pair / deliver_spill_pairs) across their corners
  (flat, prefix_len, spill_in/spill).
* Engine A/B: trajectory fingerprints (test_multirumor._fingerprint
  convention) with -deliver-kernel pallas vs xla on both backends and
  engines, single- and multi-rumor -- the gate must be bit-invisible.
* Gate policy: auto resolves to xla with no probe, explicit pallas runs
  the kernels (interpret mode here), bogus values are rejected at
  validate() time, and checkpoints resume across gates in both
  directions (the gate changes no state layout)."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import epidemic
from gossip_simulator_tpu.ops import mailbox as mb
from gossip_simulator_tpu.ops import pallas_deliver as pd
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
    h = hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]
    return {"windows": len(rows), "final": list(rows[-1]), "hash": h}


def _stepper(cfg):
    from gossip_simulator_tpu.backends import make_stepper

    s = make_stepper(cfg)
    s.init()
    while not s.overlay_window()[2]:
        pass
    s.seed()
    return s


def _chunk_init(nk, cap):
    return (jnp.full((nk * cap + 1,), -1, I32),
            jnp.zeros((nk + 1,), I32), jnp.zeros((), I32))


# --------------------------------------------------------------------------
# Unit parity: fused wrappers vs the XLA forms they replace
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rank_major", [False, True],
                         ids=["dst_major", "rank_major"])
def test_chunk_step_parity(rank_major):
    """Random key stream with collisions, sentinels, and capacity overflow:
    mailbox, total-arrivals count (incl. the sentinel bin), and drop count
    are bit-identical to the sort + segment_ranks + scatter chain."""
    rng = np.random.default_rng(1)
    nk, cap, m = 7, 3, 64
    key = jnp.asarray(rng.integers(0, nk + 1, m), I32)
    s = jnp.asarray(rng.integers(0, 1000, m), I32)
    fm, fc, fd = pd.fused_chunk_step(*_chunk_init(nk, cap), key, s, nk, cap,
                                     rank_major, interpret=True)
    xm, xc, xd = mb._compact_chunk_step(*_chunk_init(nk, cap), key, s, nk,
                                        cap, rank_major)
    assert (fm == xm).all() and (fc == xc).all() and fd == xd


@pytest.mark.parametrize("rank_major", [False, True],
                         ids=["dst_major", "rank_major"])
def test_chunk_step_spill_parity_lossless(rank_major):
    """Spill collection in the lossless band (scap >= overflow): counts and
    mailboxes identical; the pair buffer holds the same MULTISET of (src,
    key) pairs -- fused collects in arrival order, XLA in sorted order (the
    one documented at-rest divergence; README table)."""
    rng = np.random.default_rng(2)
    nk, cap, m = 7, 3, 64
    key = jnp.asarray(rng.integers(0, nk + 1, m), I32)
    s = jnp.asarray(rng.integers(0, 1000, m), I32)
    sp = lambda: (jnp.full((2, m + 1), -1, I32), jnp.zeros((), I32))
    fm, fc, fd, (fp, fs) = pd.fused_chunk_step(
        *_chunk_init(nk, cap), key, s, nk, cap, rank_major, spill=sp(),
        interpret=True)
    xm, xc, xd, (xp, xs) = mb._compact_chunk_step(
        *_chunk_init(nk, cap), key, s, nk, cap, rank_major, spill=sp())
    assert (fm == xm).all() and (fc == xc).all() and fd == xd and fs == xs
    fpn, xpn = np.asarray(fp), np.asarray(xp)
    assert sorted(map(tuple, fpn[:, :int(fs)].T)) == \
           sorted(map(tuple, xpn[:, :int(xs)].T))


def test_chunk_step_spill_redelivery_equivalence():
    """The spill buffers differ only by a within-destination-order-
    preserving permutation: re-delivering each through deliver_spill_pairs
    lands bit-identical mailboxes and counts."""
    rng = np.random.default_rng(3)
    nk, cap, m = 5, 1, 48
    key = jnp.asarray(rng.integers(0, nk, m), I32)
    s = jnp.asarray(rng.integers(0, 1000, m), I32)
    sp = lambda: (jnp.full((2, m + 1), -1, I32), jnp.zeros((), I32))
    *_, (fp, fs) = pd.fused_chunk_step(*_chunk_init(nk, cap), key, s, nk,
                                       cap, False, spill=sp(),
                                       interpret=True)
    *_, (xp, xs) = mb._compact_chunk_step(*_chunk_init(nk, cap), key, s,
                                          nk, cap, False, spill=sp())
    assert fs == xs
    cap2 = 16  # redeliver into roomier mailboxes: all spilled land
    (fm, fc, fd), _ = mb.deliver_spill_pairs(_chunk_init(nk, cap2), fp, nk,
                                             cap2, rank_major=False)
    (xm, xc, xd), _ = mb.deliver_spill_pairs(_chunk_init(nk, cap2), xp, nk,
                                             cap2, rank_major=False)
    assert (fm == xm).all() and (fc == xc).all() and fd == xd


def test_chunk_step_spill_overflow_counts_identical():
    """Past the spill buffer's own capacity (counted-drops regime) the kept
    pair SET may legitimately differ; mbox/count/dropped/scnt must not."""
    rng = np.random.default_rng(4)
    nk, cap, m, scap = 4, 1, 64, 3
    key = jnp.asarray(rng.integers(0, nk, m), I32)
    s = jnp.asarray(rng.integers(0, 1000, m), I32)
    sp = lambda: (jnp.full((2, scap + 1), -1, I32), jnp.zeros((), I32))
    fm, fc, fd, (_, fs) = pd.fused_chunk_step(
        *_chunk_init(nk, cap), key, s, nk, cap, False, spill=sp(),
        interpret=True)
    xm, xc, xd, (_, xs) = mb._compact_chunk_step(
        *_chunk_init(nk, cap), key, s, nk, cap, False, spill=sp())
    assert (fm == xm).all() and (fc == xc).all() and fd == xd and fs == xs


def test_chunk_step_carry_continuation():
    """Chained chunks continue per-destination ranks through the carried
    count array exactly like the XLA chain."""
    rng = np.random.default_rng(5)
    nk, cap = 5, 2
    cf = cx = _chunk_init(nk, cap)
    for _ in range(3):
        key = jnp.asarray(rng.integers(0, nk + 1, 16), I32)
        s = jnp.asarray(rng.integers(0, 99, 16), I32)
        cf = pd.fused_chunk_step(*cf, key, s, nk, cap, False,
                                 interpret=True)
        cx = mb._compact_chunk_step(*cx, key, s, nk, cap, False)
    for a, b in zip(cf, cx):
        assert (jnp.asarray(a) == jnp.asarray(b)).all()


@pytest.mark.parametrize("nrings", [1, 2], ids=["single", "dual"])
def test_ring_append_parity(nrings):
    """ring_append gate: flat payload ring and the (M, W) word-ring pair,
    with preloaded counts, invalid lanes, and slot overflow."""
    rng = np.random.default_rng(6)
    dw, cap, m, W = 3, 4, 40, 2
    rings = (jnp.zeros((dw * cap + 1,), I32),
             jnp.zeros((dw * cap + 1, W), jnp.uint32))[:nrings]
    pay = (jnp.asarray(rng.integers(1, 100, m), I32),
           jnp.asarray(rng.integers(1, 100, (m, W)), np.uint32))[:nrings]
    cnt = jnp.asarray(rng.integers(0, 2, (1, dw)), I32)
    wslot = jnp.asarray(rng.integers(0, dw, m), I32)
    valid = jnp.asarray(rng.random(m) < 0.8)
    fr, fc, fd = pd.fused_ring_append(rings, cnt, jnp.zeros((), I32), pay,
                                      wslot, valid, dw, cap, interpret=True)
    xr, xc, xd = mb.ring_append(rings, cnt, jnp.zeros((), I32), pay, wslot,
                                valid, dw, cap)
    for a, b in zip(fr, xr):
        assert (a == b).all()
    assert (fc == xc).all() and fd == xd


def test_deposit_parity():
    """epidemic.deposit_local / deposit_rumors gates: integer adds commute,
    so the serial pass is bit-identical to the flat OOB-drop scatter."""
    rng = np.random.default_rng(7)
    B, n, k, W = 4, 9, 5, 3
    m = n * k
    pending = jnp.asarray(rng.integers(0, 3, B * n), I32)
    slots = jnp.asarray(rng.integers(0, B, m), I32)
    valid = jnp.asarray(rng.random(m) < 0.7)
    dst = jnp.asarray(rng.integers(0, n, m), I32)
    f = epidemic.deposit_local(pending, dst, slots, valid, n=n,
                               kernel="pallas")
    x = epidemic.deposit_local(pending, dst, slots, valid, n=n, kernel="xla")
    assert (f == x).all()
    pr = jnp.asarray(rng.integers(0, 3, (B, n, W)), I32)
    newbits = jnp.asarray(rng.random((n, W)) < 0.5)
    f = epidemic.deposit_rumors(pr, dst, slots, valid, newbits,
                                kernel="pallas")
    x = epidemic.deposit_rumors(pr, dst, slots, valid, newbits,
                                kernel="xla")
    assert (f == x).all()


def test_unique_set_parity():
    """event.append_messages' dual-ring write: unique in-bounds indices by
    construction, so the serial pass == the unique_indices scatters."""
    rng = np.random.default_rng(8)
    L, m, W = 40, 12, 2
    ids = jnp.asarray(rng.integers(0, 9, L), I32)
    words = jnp.asarray(rng.integers(0, 9, (L, W)), np.uint32)
    flat = jnp.asarray(rng.permutation(L)[:m], I32)
    iv = jnp.asarray(rng.integers(0, 99, m), I32)
    wv = jnp.asarray(rng.integers(0, 99, (m, W)), np.uint32)
    fi, fw = pd.fused_unique_set((ids, words), flat, (iv, wv),
                                 interpret=True)
    assert (fi == ids.at[flat].set(iv, unique_indices=True)).all()
    assert (fw == words.at[flat].set(wv, unique_indices=True)).all()


@pytest.mark.parametrize("compact", [None, 16], ids=["single", "chunked"])
def test_deliver_gate_parity(compact):
    rng = np.random.default_rng(9)
    n, cap, m = 11, 3, 70
    src = jnp.asarray(rng.integers(0, n, m), I32)
    dst = jnp.asarray(rng.integers(0, n, m), I32)
    valid = jnp.asarray(rng.random(m) < 0.8)
    out_p = mb.deliver(src, dst, valid, n, cap, compact_chunk=compact,
                       kernel="pallas")
    out_x = mb.deliver(src, dst, valid, n, cap, compact_chunk=compact,
                       kernel="xla")
    for a, b in zip(out_p, out_x):
        assert (jnp.asarray(a) == jnp.asarray(b)).all()


@pytest.mark.parametrize("flat", [False, True], ids=["2d", "flat"])
@pytest.mark.parametrize("mode", ["single", "chunked", "prefix", "spill"])
def test_deliver_pair_gate_parity(flat, mode):
    """deliver_pair across its corners: single-pass, chunked-compacted,
    prefix-dense (the ticks drain), and the spill_in + spill band."""
    rng = np.random.default_rng(10)
    n, cap, m = 9, 2, 60
    src = jnp.asarray(rng.integers(0, 1000, m), I32)
    dst = jnp.asarray(rng.integers(0, n, m), I32)
    typ = jnp.asarray(rng.integers(0, 2, m), I32)
    kw = {}
    if mode == "single":
        evalid = jnp.asarray(rng.random(m) < 0.8)
    elif mode == "chunked":
        evalid = jnp.asarray(rng.random(m) < 0.8)
        kw = dict(compact_chunk=16)
    elif mode == "prefix":
        live = 41
        evalid = jnp.arange(m) < live
        kw = dict(compact_chunk=16, prefix_len=jnp.asarray(live, I32))
    else:  # spill: prior-round pairs redelivered first, overflow collected
        evalid = jnp.asarray(rng.random(m) < 0.8)
        spill_in = jnp.asarray(
            np.stack([rng.integers(0, 1000, 8),
                      np.r_[rng.integers(0, 2 * n, 5), -1, -1, -1]]), I32)
        sp = lambda: (jnp.full((2, m + 1), -1, I32), jnp.zeros((), I32))
        kw = dict(compact_chunk=16, spill_in=spill_in, spill=sp())
    out_p = mb.deliver_pair(src, dst, typ, evalid, n, cap, flat=flat,
                            kernel="pallas", **kw)
    kw2 = dict(kw)
    if mode == "spill":
        kw2["spill"] = (jnp.full((2, m + 1), -1, I32), jnp.zeros((), I32))
    out_x = mb.deliver_pair(src, dst, typ, evalid, n, cap, flat=flat,
                            kernel="xla", **kw2)
    ncmp = len(out_p) - (1 if mode == "spill" else 0)
    for a, b in zip(out_p[:ncmp], out_x[:ncmp]):
        assert (jnp.asarray(a) == jnp.asarray(b)).all()
    if mode == "spill":  # pair buffers: same count, same multiset
        (fp, fs), (xp, xs) = out_p[-1], out_x[-1]
        assert fs == xs
        fpn, xpn = np.asarray(fp), np.asarray(xp)
        assert sorted(map(tuple, fpn[:, :int(fs)].T)) == \
               sorted(map(tuple, xpn[:, :int(xs)].T))


# --------------------------------------------------------------------------
# Engine A/B: the gate must be trajectory-invisible
# --------------------------------------------------------------------------

AB_COMBOS = {
    "jax_event": dict(n=600, backend="jax", engine="event"),
    "jax_ring": dict(n=600, backend="jax", engine="ring"),
    "sharded_event": dict(n=1200, backend="sharded", engine="event"),
    "sharded_ring": dict(n=1200, backend="sharded", engine="ring"),
    "jax_event_r16": dict(n=600, backend="jax", engine="event", rumors=16,
                          crashrate=0.0),
    "sharded_event_r16": dict(n=1200, backend="sharded", engine="event",
                              rumors=16, crashrate=0.0),
}


# Off-TPU the pallas side runs in the TPU-semantics interpreter, which
# pays a host callback per ref access: a sharded combo (8 virtual devices)
# took ~30 min here (PR 21, sharded_event passed), far past the budgeted
# tier-1 sweep, so the sharded combos ride the explicit tier1.yml step
# for this file (no marker filter).
@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.slow) if n.startswith("sharded")
    else n for n in sorted(AB_COMBOS)])
def test_engine_fingerprint_ab(name):
    """-deliver-kernel pallas must reproduce the xla trajectory bit for bit
    on every engine combo, single- and multi-rumor (R=16 exercises the
    in-register word-row combine)."""
    kw = {**BASE, **AB_COMBOS[name]}
    fx = _fingerprint(Config(**kw, deliver_kernel="xla").validate())
    fp = _fingerprint(Config(**kw, deliver_kernel="pallas").validate())
    assert fx == fp


# --------------------------------------------------------------------------
# Cross-gate checkpoint interop: the gate changes no state layout
# --------------------------------------------------------------------------

@pytest.mark.parametrize("first,second", [("xla", "pallas"),
                                          ("pallas", "xla")],
                         ids=["xla_to_pallas", "pallas_to_xla"])
def test_cross_gate_checkpoint_resume(tmp_path, first, second):
    """Snapshot under one gate, resume under the other: the continued
    per-window Stats match the uninterrupted run exactly."""
    kw = dict(**BASE, n=600, backend="jax", engine="event")
    cfg_a = Config(**kw, deliver_kernel=first).validate()
    cfg_b = Config(**kw, deliver_kernel=second).validate()
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
    cfg = Config(n=2000, deliver_kernel=value).validate()
    assert cfg.deliver_kernel_resolved == "xla"
    assert cfg.resolved_gates()["deliver_kernel"] == "xla"


def test_explicit_pallas_resolves_via_interpret():
    cfg = Config(n=2000, deliver_kernel="pallas").validate()
    assert cfg.deliver_kernel_resolved == "pallas"


def test_validate_rejects_unknown_kernel():
    with pytest.raises(ValueError, match="deliver_kernel"):
        Config(n=2000, deliver_kernel="cuda").validate()
