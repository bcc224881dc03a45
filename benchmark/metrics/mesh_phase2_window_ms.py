"""mesh_phase2_window_ms (ms): phase 2's wall time per 10 ms poll window
of simulated time on the sharded ring engine (parallel/sharded_step.py)
-- phase2_window_ms's quantity for the four-chip cell: the harness's
bench.phase2 span around run_to_target() over the windows the broadcast
took to reach the target (coverage_ms, a whole window's end)."""

WINDOW_MS = 10


def read(ctx):
    v = [(s["spans"]["phase2"], -(-int(s["coverage_ms"]) // WINDOW_MS))
         for s in ctx["sims"]
         if "phase2" in s["spans"] and s["coverage_ms"] > 0]
    if not v:
        return None
    return 1000.0 * sum(t for t, _ in v) / sum(w for _, w in v)
