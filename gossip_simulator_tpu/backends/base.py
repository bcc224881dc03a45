"""The Stepper backend seam.

BASELINE.json's north star asks for the per-round node-update loop behind a
``Stepper`` interface (Init/Step/Stats) so backends are swappable:

* ``native``  -- event-driven Python oracle, faithful to the reference's
                 goroutine/channel semantics in *simulated* time (small N).
* ``cpp``     -- the same discrete-event algorithm in C++ (ctypes), the fast
                 CPU baseline standing in for the reference's Go loop.
* ``jax``     -- vectorized single-device XLA program (the product).
* ``sharded`` -- jax over a `jax.sharding.Mesh`, cross-shard all_to_all.

One ``gossip_window()`` call advances 10 simulated milliseconds -- the
reference driver's poll cadence (simulator.go:223,244) -- or one round in
rounds mode, so the driver's printing loop is backend-agnostic.
"""

from __future__ import annotations

import abc

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.utils.metrics import Stats

WINDOW_MS = 10  # reference poll interval (simulator.go:223, 244)


class Stepper(abc.ABC):
    name: str = "abstract"

    def __init__(self, cfg: Config):
        self.cfg = cfg

    # --- lifecycle ------------------------------------------------------------
    @abc.abstractmethod
    def init(self) -> None:
        """Allocate node state (mirrors simulator.go:207-217)."""

    @abc.abstractmethod
    def overlay_window(self) -> tuple[int, int, bool]:
        """Advance overlay construction by one poll window.

        Returns ``(makeups, breakups, quiesced)`` -- the membership events
        observed during the window and whether the system has stabilized
        (no makeup/breakup activity for a full window, simulator.go:221-234).
        For static graphs ("kout", "erdos", "ring") the first call generates
        the graph and returns quiesced immediately.
        """

    @abc.abstractmethod
    def seed(self) -> None:
        """Pick a uniform-random node and inject its initial broadcast
        (simulator.go:240-241)."""

    @abc.abstractmethod
    def gossip_window(self) -> Stats:
        """Advance the epidemic by one poll window (10 simulated ms in ticks
        mode; one round in rounds mode) and return a counters snapshot."""

    @abc.abstractmethod
    def stats(self) -> Stats:
        """Current counters snapshot (host-side)."""

    @abc.abstractmethod
    def sim_time_ms(self) -> float:
        """Simulated milliseconds elapsed in the current phase."""

    # --- optional -------------------------------------------------------------
    @property
    def primary_host(self) -> bool:
        """False on the non-zero ranks of a multi-process run: they
        participate in collective snapshot gathers but must not write files
        (every rank holds the same replicated/gathered values)."""
        return True

    def state_pytree(self):
        """Backend state as arrays for checkpointing; None if unsupported.
        Under -distributed this is a COLLECTIVE call: every process must
        make it, even though only the primary host writes the result."""
        return None

    def load_state_pytree(self, tree) -> None:
        raise NotImplementedError(f"{self.name} does not support checkpoint restore")

    def overlay_state_pytree(self):
        """Mid-construction phase-1 state for checkpointing; None if
        unsupported (the discrete-event oracles run phase 1 in seconds at
        their feasible n).  Collective under -distributed, like
        state_pytree."""
        return None

    def load_overlay_state_pytree(self, tree, windows: int = 0) -> None:
        raise NotImplementedError(
            f"{self.name} does not support phase-1 checkpoint restore")


def run_bounded_to_target(stepper) -> Stats:
    """Shared host loop for the JAX backends' run_to_target fast path.

    Re-enters the backend's bounded device-side while_loop (`_run_fn`, see
    epidemic.run_call_budget) until the coverage target, max_rounds, or
    exhaustion (nothing in flight -- the liveness bound the reference lacks,
    simulator.go:243-251).  Requires `stepper._run_fn(state, key, target,
    until) -> state` with donated state, plus `.state/.key/.exhausted`.

    With a TelemetrySession on the stepper (`stepper._telem`, see
    utils/telemetry.py) the run fn additionally threads the device-resident
    per-window History through the loop -- `_run_fn(state, key, target,
    until, hist) -> (state, hist)` -- and the per-call wall clock lands in
    the session's phase ledger (the first call's dispatch = compile_s, the
    rest = execute_s; TelemetrySession).
    """
    import time

    import jax
    import numpy as np

    cfg = stepper.cfg
    from gossip_simulator_tpu.models import epidemic

    from gossip_simulator_tpu.utils import trace as _trace

    target = int(np.ceil(cfg.coverage_target * cfg.n))
    budget = epidemic.run_call_budget(cfg)
    tick = int(jax.device_get(stepper.state.tick))
    telem = getattr(stepper, "_telem", None)
    hist = telem.begin_gossip() if telem is not None else None
    calls = 0
    while True:
        until = min(cfg.max_rounds, tick + budget)
        t0 = time.perf_counter()
        # Two spans per bounded device call, where the host work splits:
        # `dispatch` is the jitted call up to its return (on a fresh
        # stepper's first call: trace, lowering, compile or persistent-cache
        # load, then the enqueue), `sync` the device_get that waits for the
        # device to finish the call.
        with _trace.span("phase2.dispatch", cat="device", call=calls):
            if hist is not None:
                stepper.state, hist = stepper._run_fn(
                    stepper.state, stepper.key, np.int32(target),
                    np.int32(until), hist)
            else:
                stepper.state = stepper._run_fn(
                    stepper.state, stepper.key,
                    np.int32(target), np.int32(until))
        dispatch_s = time.perf_counter() - t0
        with _trace.span("phase2.sync", cat="device", call=calls) as sp:
            st = stepper.state
            from gossip_simulator_tpu.models.event import \
                in_flight as _inflight

            import jax.numpy as jnp

            # Multi-rumor convergence is the WORST rumor: the loop runs
            # until every rumor's per-rumor count reaches the target.
            recv_metric = (jnp.min(st.rumor_recv[:cfg.rumors])
                           if cfg.multi_rumor else st.total_received)
            tick, recv, in_flight = (int(x) for x in jax.device_get(
                (st.tick, recv_metric, _inflight(st))))
            if sp is not None:
                sp.update(until=int(until), tick=tick, received=recv,
                          in_flight=in_flight)
        calls += 1
        if telem is not None:
            telem.tally_gossip_call(dispatch_s, time.perf_counter() - t0)
        # Exhaustion is recorded whatever ends the run (the windowed loop's
        # per-window flag ends up reflecting the LAST window too), so a wave
        # that dies in the same window the round cap is hit still reports
        # "exhausted" -- reason parity with the windowed path.  Healing can
        # revive an empty ring (a pending dead-friend detection re-sends
        # from an already-infected healer), so heal-on runs never exit on
        # emptiness -- they run to target or max_rounds.  A streaming run
        # with an empty ring is not dead while the injection schedule has
        # rumors still to start.
        if (in_flight == 0 and cfg.protocol != "pushpull"
                and not cfg.overlay_heal_resolved
                and (not cfg.multi_rumor
                     or tick > cfg.last_inject_tick)):
            stepper.exhausted = True
        if (recv >= target or tick >= cfg.max_rounds
                or stepper.exhausted):
            break
        # Cooperative shutdown (utils/lifecycle): a signalled run stops at
        # the next bounded-call boundary; the driver then writes the final
        # checkpoint and flushes artifacts with reason "interrupted".
        from gossip_simulator_tpu.utils import lifecycle as _lifecycle

        if _lifecycle.shutdown_requested():
            break
    if telem is not None:
        telem.end_gossip(hist)
    return stepper.stats()
