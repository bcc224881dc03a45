#!/usr/bin/env python
"""Profile the production event-engine window at bench shape and rank device
op costs (the data behind the README roadmap's percentages).

Runs the epidemic to its steady state (a few windows past the seed), traces
`--windows` windowed device calls with jax.profiler, then parses the chrome
trace (plugins/profile/*/\\*.trace.json.gz) and aggregates device-track 'X'
events by op name.

Usage: python scripts/profile_window.py [--n 10000000] [--windows 20]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gossip_simulator_tpu.utils import jaxsetup  # noqa: E402

jaxsetup.setup()

import jax  # noqa: E402

from gossip_simulator_tpu.backends.jax_backend import JaxStepper  # noqa: E402
from gossip_simulator_tpu.config import Config  # noqa: E402


def parse_trace(trace_dir: str,
                top: int = 18) -> tuple[list[tuple[str, float, int]], float]:
    """Aggregate device-track complete ('X') events by name; return the
    top ops as (name, total_ms, count) plus the loop total (the longest
    single op -- the outer while -- whose duration IS the device time of
    the traced region; summing all ops would double-count nested
    jit/while wrappers)."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # Device tracks: pid whose process_name mentions TPU/device (the host
    # python tracks carry the same op names prefixed differently).
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
    device_pids = {p for p, nm in pid_names.items()
                   if "TPU" in nm or "/device:" in nm or "Chip" in nm}
    agg = collections.Counter()
    cnt = collections.Counter()
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        name = e.get("name", "?")
        agg[name] += e.get("dur", 0) / 1e3  # us -> ms
        cnt[name] += 1
    loop_total = max(agg.values(), default=0.0)
    return [(nm, ms, cnt[nm]) for nm, ms in agg.most_common(top)], loop_total


# ---------------------------------------------------------------------------
# Roofline (--roofline): bytes-touched-per-delivered-message from the SoA
# column layout, combined with the measured CPU floors already committed in
# PROFILE_OVERLAY.json / PROFILE_EXCHANGE.json, written to ROOFLINE.json.
# Each term lists the minimum memory traffic a delivered message must
# touch, so a measured ns/message divides into a stated factor-off-roofline
# (benchmark/roofline.py copies the emit/deliver/combine derivations).
# ---------------------------------------------------------------------------

# HBM bandwidth the ns/message floors are quoted at.  TPU v4 HBM2 is
# 1228 GB/s per chip (public spec); the CPU column uses the measured
# dense-delivery floor instead of a paper number.
TPU_V4_HBM_GBPS = 1228.0


def _roofline_terms(fanout: int, rumors: int, pushsum_dim: int) -> dict:
    """Analytic bytes/message per pipeline term, from the SoA layout:
    uint8 node flags, int32 counters/ids, uint32 rumor words packed 32/word,
    int32 pushsum limbs (LIMBS 16-bit limbs per scalar, weight block last).
    Amortized per-row reads divide by fanout (one row emits k messages)."""
    from gossip_simulator_tpu.models import pushsum as ps

    k = fanout
    w = max(1, -(-rumors // 32))          # packed uint32 words/node
    c = (pushsum_dim + 1) * ps.LIMBS      # int32 mass columns/node
    terms = {
        "emit": {
            "bytes_per_message": 4 + 1 + 4 + 4 * w + 8.0 / k,
            "derivation": "friends id read (int32 4) + dest flag read for "
                          "the duplicate filter (uint8 1) + mail-ring id "
                          "write (int32 4) + rumor-word row write "
                          f"(uint32 4*W={4 * w}) + per-sender wslot/off "
                          f"draws amortized over k={k} edges (8/k)",
        },
        "route": {
            "bytes_per_message": 4 * (4 + 4 * w),
            "derivation": "sharded only: mail read + wire encode + "
                          "all_to_all landing read + local ring write, "
                          f"each (4 + 4*W={4 + 4 * w}) for the id and "
                          "its word row; S=1 runs this term at 0",
        },
        "deliver": {
            "bytes_per_message": 4 + 4 * w + 1 + 1 + 4,
            "derivation": "mail id read (4) + word row read "
                          f"(4*W={4 * w}) + dest flag read+write "
                          "(uint8 1+1) + received counter update "
                          "(int32 4)",
        },
        "combine": {
            "bytes_per_message": 8 * w,
            "derivation": "first-touch OR into the packed rumor words: "
                          f"read + write 4*W={4 * w} each "
                          f"(pushsum twin: read+add+write {4 * c} B over "
                          f"C={c} int32 limb columns = {8 * c} B)",
            "pushsum_bytes_per_message": 8 * c,
        },
    }
    total = sum(t["bytes_per_message"] for t in terms.values())
    return terms, total, w, c


def write_roofline(out_path: str, fanout: int, rumors: int,
                   pushsum_dim: int, date: str) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    terms, total, w, c = _roofline_terms(fanout, rumors, pushsum_dim)
    for t in terms.values():
        t["ns_per_message_at_tpu_v4_hbm"] = (
            t["bytes_per_message"] / TPU_V4_HBM_GBPS)
    evidence = []
    po = os.path.join(repo, "PROFILE_OVERLAY.json")
    if os.path.exists(po):
        d = json.load(open(po))
        fl = d["rows"]["chunk_floor"]
        vals = [v["dense_ns_per_lane"] for v in fl.values()]
        evidence.append({
            "source": "PROFILE_OVERLAY.json",
            "row": "chunk_floor.*.dense_ns_per_lane",
            "ns_per_lane": [round(v, 1) for v in vals],
            "note": "measured CPU dense delivery floor (XLA, per mail "
                    "lane) -- the deliver term's CPU reality check",
        })
    pe = os.path.join(repo, "PROFILE_EXCHANGE.json")
    if os.path.exists(pe):
        d = json.load(open(pe))
        rr = d["rows"]["route"]["rank_zero_loss"]
        evidence.append({
            "source": "PROFILE_EXCHANGE.json",
            "row": "route.rank_zero_loss.ns_per_lane",
            "ns_per_lane": round(rr["ns_per_lane"], 1),
            "note": "measured CPU rank route (XLA, per wire lane) -- "
                    "the route term's CPU reality check",
        })
    doc = {
        "session": "r19",
        "date": date,
        "device": "cpu",
        "hbm_bw_GBps": {"tpu_v4": TPU_V4_HBM_GBPS,
                        "source": "public chip spec; CPU floors are "
                                  "measured, not quoted"},
        "layout": {
            "node_flags": "uint8[n]",
            "counters": "int32 (received counts, ring counts, mass "
                        "residue)",
            "rumor_words": f"uint32[n, W], W=ceil(R/32)={w} at R={rumors}",
            "pushsum_mass": f"int32[n, (dim+1)*LIMBS]={c} cols at "
                            f"dim={pushsum_dim}",
            "mail_ring": "int32[dw*cap] ids (+ uint32[dw*cap, W] words)",
        },
        "shape": {"fanout": fanout, "rumors": rumors, "words": w,
                  "pushsum_dim": pushsum_dim},
        "terms": terms,
        "total_bytes_per_message": round(total, 2),
        "total_ns_per_message_at_tpu_v4_hbm": round(
            total / TPU_V4_HBM_GBPS, 4),
        "evidence": evidence,
        "tpu_status": {
            "status": "not measured on a chip",
            "date": date,
        },
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=False)
        f.write("\n")
    ps_msg = doc["total_ns_per_message_at_tpu_v4_hbm"] * 1e3
    print(f"wrote {out_path}: total {doc['total_bytes_per_message']} "
          f"B/message -> {ps_msg:.3f} ps/message at TPU v4 HBM")
    for nm, t in terms.items():
        print(f"  {nm:8s} {t['bytes_per_message']:7.2f} B/msg")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--fanout", type=int, default=3)
    ap.add_argument("--coverage-target", type=float, default=0.90)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument("--windows", type=int, default=20)
    ap.add_argument("--out", default="/tmp/gossip_profile")
    ap.add_argument("--phase", choices=("gossip", "overlay"),
                    default="gossip",
                    help="overlay: profile phase-1 construction windows "
                         "instead (use --overlay-mode to pick the engine)")
    ap.add_argument("--overlay-mode", choices=("rounds", "ticks"),
                    default="rounds")
    ap.add_argument("--roofline", action="store_true",
                    help="derive the per-term bytes/message roofline from "
                         "the SoA layout plus the committed CPU floors and "
                         "write it to --roofline-out (no profiling run)")
    ap.add_argument("--roofline-out",
                    default=os.path.join(
                        os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))), "ROOFLINE.json"))
    ap.add_argument("--rumors", type=int, default=16,
                    help="roofline R (words = ceil(R/32))")
    ap.add_argument("--pushsum-dim", type=int, default=1)
    ap.add_argument("--date", default="2026-08-07",
                    help="stamp for the roofline")
    args = ap.parse_args()
    if args.roofline:
        return write_roofline(args.roofline_out, args.fanout, args.rumors,
                              args.pushsum_dim, args.date)
    on_tpu = jax.default_backend() == "tpu"
    if args.phase == "overlay":
        cfg = Config(n=args.n, graph="overlay",
                     overlay_mode=args.overlay_mode, backend="jax",
                     seed=0, progress=False).validate()
        s = JaxStepper(cfg)
        s.init()
        # Quiescence frees the phase-1 buffers (ostate -> None) and turns
        # further overlay_window() calls into host no-ops that would skew
        # ms/window -- step with a live guard and report actual windows.
        step = lambda: s.ostate is not None and not s.overlay_window()[2]
        ready = lambda: jax.block_until_ready(
            s.ostate.friends if s.ostate is not None else s.state.friends)
        label = f"phase=overlay/{args.overlay_mode}"
    else:
        cfg = Config(n=args.n, fanout=args.fanout, graph="kout",
                     backend="jax", seed=0, crashrate=0.001,
                     coverage_target=args.coverage_target, max_rounds=3000,
                     pallas=on_tpu, progress=False).validate()
        s = JaxStepper(cfg)
        s.init()
        s.seed()
        step = lambda: bool(s.gossip_window()) or True
        ready = lambda: jax.block_until_ready(s.state.flags)
        label = "phase=gossip"

    # Steady state: run past the early near-empty windows.
    for _ in range(args.warmup):
        if not step():
            print("quiesced during warmup -- lower --warmup/--n")
            return 1
    ready()
    ran = 0
    t0 = time.perf_counter()
    with jax.profiler.trace(args.out):
        for _ in range(args.windows):
            if not step():
                break
            ran += 1
        ready()
    wall = time.perf_counter() - t0
    if ran == 0:
        print("no live windows profiled -- lower --warmup")
        return 1
    rows, loop_total = parse_trace(args.out)
    print(f"device={jax.devices()[0].device_kind} n={cfg.n} {label} "
          f"windows={ran} wall={wall:.2f}s "
          f"({wall / ran * 1e3:.1f} ms/window, device "
          f"{loop_total / ran:.1f} ms/window)")
    print(f"{'op':44s} {'ms_total':>9s} {'ms/win':>8s} {'count':>6s} "
          f"{'%loop':>5s}")
    for nm, ms, c in rows:
        print(f"{nm[:44]:44s} {ms:9.1f} {ms / ran:8.2f} {c:6d} "
              f"{100 * ms / loop_total:5.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
