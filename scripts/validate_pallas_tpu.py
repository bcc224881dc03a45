#!/usr/bin/env python
"""TPU-side validation of the Pallas kernels (graph generators + the
fused delivery kernel), recorded as an artifact (PALLAS_VALIDATION.json
at the repo root).  bench.py runs this automatically during a TPU bench
pass.

tests/test_pallas_graph.py can only check structure off-TPU (the interpret
mode PRNG is an all-zero stub -- see ops/pallas_graph.py's own warning), so
the statistical properties the simulation leans on -- destination
uniformity, Poisson degrees, seed decorrelation -- are validated HERE on
real hardware.

Checks (all on freshly generated tables):
* kout: chi-square destination uniformity over 256 buckets (statistic
  within 5 sigma of its dof), mean/variance of the uniform draw, no self
  loops, two seeds produce >99% differing entries.
* erdos: degree mean/var against Poisson(lam), chi-square of the degree
  histogram against the Poisson pmf (tail merged), destination uniformity,
  no self loops in live slots.
* deliver (ISSUE 9, run_deliver_checks): the -deliver-kernel fused forms
  (ops/pallas_deliver) bit-identical to the XLA chains they replace --
  chunk step both layouts, spill counts + pair multiset, ring append,
  deliver/deliver_pair gates, deposits, unique-set dual ring.  These are
  PRNG-free, so they also run in interpret mode on CPU hosts
  (--interpret), where the verdict is MERGED into the existing
  artifact without disturbing recorded TPU results.

Run: python scripts/validate_pallas_tpu.py [--out PALLAS_VALIDATION.json]
     python scripts/validate_pallas_tpu.py --interpret   # CPU deliver-only
Exit 0 iff every check passes (also exits 3 when no TPU is present and
--interpret was not given).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gossip_simulator_tpu.utils import jaxsetup  # noqa: E402

jaxsetup.setup()

import jax  # noqa: E402
import numpy as np  # noqa: E402


def _chi2_uniform(values: np.ndarray, n: int, buckets: int = 256) -> dict:
    """Chi-square statistic of `values` (uniform over [0, n)) bucketed into
    `buckets` equal ranges; 5-sigma window around the dof."""
    counts = np.bincount((values.astype(np.int64) * buckets) // n,
                         minlength=buckets)
    expect = values.size / buckets
    stat = float(((counts - expect) ** 2 / expect).sum())
    dof = buckets - 1
    bound = 5.0 * math.sqrt(2.0 * dof)
    return {"stat": round(stat, 1), "dof": dof,
            "window": [round(dof - bound, 1), round(dof + bound, 1)],
            "ok": abs(stat - dof) <= bound}


def _chi2_poisson(deg: np.ndarray, lam: float) -> dict:
    """Chi-square of the observed degree histogram against Poisson(lam).
    Sparse edge bins are MERGED into their neighbors (not dropped) until
    every bin's expected count is >= 5, so excess mass in the clamped
    overflow tail still moves the statistic."""
    m = deg.size
    hi = int(lam + 5 * math.sqrt(lam))
    pmf = np.zeros(hi + 2)
    p = math.exp(-lam)
    for i in range(hi + 1):
        pmf[i] = p
        p *= lam / (i + 1)
    pmf[hi + 1] = max(1.0 - pmf[: hi + 1].sum(), 0.0)
    obs = list(np.bincount(np.minimum(deg, hi + 1), minlength=hi + 2)
               .astype(float))
    exp = list(pmf * m)
    while len(exp) > 1 and exp[-1] < 5:  # fold the tail inward
        exp[-2] += exp.pop()
        obs[-2] += obs.pop()
    while len(exp) > 1 and exp[0] < 5:  # and the low-degree head
        exp[1] += exp[0]
        obs[1] += obs[0]
        exp.pop(0)
        obs.pop(0)
    o, e = np.asarray(obs), np.asarray(exp)
    stat = float(((o - e) ** 2 / e).sum())
    dof = len(exp) - 1
    bound = 5.0 * math.sqrt(2.0 * dof)
    return {"stat": round(stat, 1), "dof": dof,
            "window": [round(dof - bound, 1), round(dof + bound, 1)],
            "ok": abs(stat - dof) <= bound}


def run_checks() -> dict:
    from gossip_simulator_tpu.ops.pallas_graph import erdos_pallas, kout_pallas

    checks = []

    def add(name, ok, **detail):
        checks.append({"name": name, "ok": bool(ok), **detail})

    # --- kout -------------------------------------------------------------
    n, k, rows = 1_000_000, 8, 131_072
    f = np.asarray(kout_pallas(n, k, 0, rows, 7, False))
    flat = f.reshape(-1)
    add("kout_chi2_uniform", **_chi2_uniform(flat, n))
    mean_rel = float(flat.mean() / ((n - 1) / 2) - 1)
    add("kout_mean", abs(mean_rel) < 0.01, rel_err=round(mean_rel, 5))
    var_rel = float(flat.var() / (n * n / 12.0) - 1)
    add("kout_var", abs(var_rel) < 0.02, rel_err=round(var_rel, 5))
    ids = np.arange(rows)[:, None]
    add("kout_no_self_loops", (f != ids).all())
    g = np.asarray(kout_pallas(n, k, 0, rows, 8, False))
    differ = float((f != g).mean())
    add("kout_seed_decorrelation", differ > 0.99, differ=round(differ, 5))

    # --- erdos ------------------------------------------------------------
    lam, rows_e = 8.0, 131_072
    fe, deg = erdos_pallas(n, lam, 0, rows_e, 7, False)
    fe, deg = np.asarray(fe), np.asarray(deg).astype(np.int64)
    mean_err = float(deg.mean() - lam)
    sigma = math.sqrt(lam / rows_e)
    add("erdos_degree_mean", abs(mean_err) < 5 * sigma,
        err=round(mean_err, 5), sigma5=round(5 * sigma, 5))
    var_rel = float(deg.var() / lam - 1)
    add("erdos_degree_var", abs(var_rel) < 0.05, rel_err=round(var_rel, 5))
    add("erdos_degree_chi2_poisson", **_chi2_poisson(deg, lam))
    live = np.arange(fe.shape[1])[None, :] < deg[:, None]
    dests = fe[live]
    add("erdos_chi2_uniform", **_chi2_uniform(dests, n))
    ids_e = np.broadcast_to(np.arange(rows_e)[:, None], fe.shape)
    add("erdos_no_self_loops", (fe[live] != ids_e[live]).all())

    return {
        "device": jax.devices()[0].device_kind,
        "n": n, "kout_draws": rows * k, "erdos_rows": rows_e, "lam": lam,
        "checks": checks,
        "all_pass": all(c["ok"] for c in checks),
    }


def run_deliver_checks() -> dict:
    """Bit-identity of every fused delivery form against the XLA chain it
    replaces (ops/pallas_deliver vs ops/mailbox + models/epidemic).  No
    PRNG inside the kernels, so the same assertions hold natively on TPU
    and in interpret mode on CPU; `mode` records which one ran."""
    import jax.numpy as jnp

    from gossip_simulator_tpu.models import epidemic
    from gossip_simulator_tpu.ops import mailbox as mb
    from gossip_simulator_tpu.ops import pallas_deliver as pd

    mode = "tpu" if jax.default_backend() == "tpu" else "interpret"
    I32 = jnp.int32
    checks = []

    def add(name, ok, **detail):
        checks.append({"name": name, "ok": bool(ok), **detail})

    def eq(*pairs):
        return all(bool((jnp.asarray(a) == jnp.asarray(b)).all())
                   for a, b in pairs)

    def init(nk, cap):
        return (jnp.full((nk * cap + 1,), -1, I32),
                jnp.zeros((nk + 1,), I32), jnp.zeros((), I32))

    rng = np.random.default_rng(0)
    nk, cap, m = 7, 3, 64
    key = jnp.asarray(rng.integers(0, nk + 1, m), I32)
    s = jnp.asarray(rng.integers(0, 1000, m), I32)
    for rank_major in (False, True):
        f = pd.fused_chunk_step(*init(nk, cap), key, s, nk, cap, rank_major)
        x = mb._compact_chunk_step(*init(nk, cap), key, s, nk, cap,
                                   rank_major)
        add(f"chunk_step_rank_major_{rank_major}", eq(*zip(f, x)))

    sp = lambda: (jnp.full((2, m + 1), -1, I32), jnp.zeros((), I32))
    fm, fc, fd, (fp, fs) = pd.fused_chunk_step(
        *init(nk, cap), key, s, nk, cap, False, spill=sp())
    xm, xc, xd, (xp, xs) = mb._compact_chunk_step(
        *init(nk, cap), key, s, nk, cap, False, spill=sp())
    add("chunk_step_spill_counts",
        eq((fm, xm), (fc, xc), (fd, xd), (fs, xs)))
    fpn, xpn = np.asarray(fp), np.asarray(xp)
    add("chunk_step_spill_pair_multiset",
        sorted(map(tuple, fpn[:, :int(fs)].T))
        == sorted(map(tuple, xpn[:, :int(xs)].T)),
        note="order divergence is documented; the multiset must match")

    dw, rcap, W = 3, 4, 2
    rings = (jnp.zeros((dw * rcap + 1,), I32),
             jnp.zeros((dw * rcap + 1, W), jnp.uint32))
    pay = (jnp.asarray(rng.integers(1, 100, m), I32),
           jnp.asarray(rng.integers(1, 100, (m, W)), np.uint32))
    cnt = jnp.asarray(rng.integers(0, 2, (1, dw)), I32)
    wslot = jnp.asarray(rng.integers(0, dw, m), I32)
    valid = jnp.asarray(rng.random(m) < 0.8)
    fr, fcn, frd = pd.fused_ring_append(rings, cnt, jnp.zeros((), I32),
                                        pay, wslot, valid, dw, rcap)
    xr, xcn, xrd = mb.ring_append(rings, cnt, jnp.zeros((), I32), pay,
                                  wslot, valid, dw, rcap)
    add("ring_append_dual", eq(*zip(fr, xr), (fcn, xcn), (frd, xrd)))

    n = 11
    src = jnp.asarray(rng.integers(0, n, m), I32)
    dst = jnp.asarray(rng.integers(0, n, m), I32)
    dvalid = jnp.asarray(rng.random(m) < 0.8)
    for compact in (None, 16):
        f = mb.deliver(src, dst, dvalid, n, cap, compact_chunk=compact,
                       kernel="pallas")
        x = mb.deliver(src, dst, dvalid, n, cap, compact_chunk=compact,
                       kernel="xla")
        add(f"deliver_gate_compact_{compact}", eq(*zip(f, x)))
    typ = jnp.asarray(rng.integers(0, 2, m), I32)
    f = mb.deliver_pair(src, dst, typ, dvalid, n, cap, kernel="pallas")
    x = mb.deliver_pair(src, dst, typ, dvalid, n, cap, kernel="xla")
    add("deliver_pair_gate", eq(*zip(f, x)))

    B, k, Wr = 4, 5, 3
    md = n * k
    pending = jnp.asarray(rng.integers(0, 3, B * n), I32)
    slots = jnp.asarray(rng.integers(0, B, md), I32)
    dvalid = jnp.asarray(rng.random(md) < 0.7)
    ddst = jnp.asarray(rng.integers(0, n, md), I32)
    add("deposit_local",
        eq((epidemic.deposit_local(pending, ddst, slots, dvalid, n=n,
                                   kernel="pallas"),
            epidemic.deposit_local(pending, ddst, slots, dvalid, n=n,
                                   kernel="xla"))))
    pr = jnp.asarray(rng.integers(0, 3, (B, n, Wr)), I32)
    newbits = jnp.asarray(rng.random((n, Wr)) < 0.5)
    add("deposit_rumors",
        eq((epidemic.deposit_rumors(pr, ddst, slots, dvalid, newbits,
                                    kernel="pallas"),
            epidemic.deposit_rumors(pr, ddst, slots, dvalid, newbits,
                                    kernel="xla"))))

    L, mu = 40, 12
    ids = jnp.asarray(rng.integers(0, 9, L), I32)
    words = jnp.asarray(rng.integers(0, 9, (L, W)), np.uint32)
    flat = jnp.asarray(rng.permutation(L)[:mu], I32)
    iv = jnp.asarray(rng.integers(0, 99, mu), I32)
    wv = jnp.asarray(rng.integers(0, 99, (mu, W)), np.uint32)
    fi, fw = pd.fused_unique_set((ids, words), flat, (iv, wv))
    add("unique_set_dual",
        eq((fi, ids.at[flat].set(iv, unique_indices=True)),
           (fw, words.at[flat].set(wv, unique_indices=True))))

    return {
        "mode": mode,
        "device": jax.devices()[0].device_kind,
        "checks": checks,
        "all_pass": all(c["ok"] for c in checks),
    }


def _merge_out(path: str, updates: dict) -> dict:
    """Merge `updates` into the JSON artifact at `path` (preserving any
    recorded sections -- e.g. the CPU --interpret verdict must not erase
    the TPU graph checks, and vice versa)."""
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            data = {}
    data.update(updates)
    with open(path, "w") as fh:
        json.dump(data, fh, indent=1)
    return data


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PALLAS_VALIDATION.json"))
    ap.add_argument("--interpret", action="store_true",
                    help="run only the (PRNG-free) delivery-kernel checks "
                         "in interpret mode -- valid on CPU hosts; the "
                         "verdict is merged into --out")
    args = ap.parse_args()
    if args.interpret:
        result = run_deliver_checks()
        _merge_out(args.out, {"deliver_interpret": result})
        print(json.dumps({"deliver_interpret": result}))
        return 0 if result.get("all_pass") else 1
    if jax.default_backend() != "tpu":
        print(json.dumps({"skipped": "no TPU present; interpret-mode PRNG "
                                     "validates nothing (use --interpret "
                                     "for the PRNG-free deliver checks)"}))
        return 3
    result = run_checks()
    deliver = run_deliver_checks()
    _merge_out(args.out, {**result, "deliver_tpu": deliver})
    print(json.dumps({**result, "deliver_tpu": deliver}))
    return 0 if (result["all_pass"] and deliver.get("all_pass")) else 1

if __name__ == "__main__":
    sys.exit(main())
