"""Single-device JAX/XLA backend -- the product path.

One device call per progress window (10 ticks or 1 round); counters stay
device-resident and come to the host once per window (the reference instead
polls global atomics every 10 ms of wall time, simulator.go:221-253).
`run_to_target` exposes the zero-host-sync while_loop path used by bench.py.

First call per config compiles (~seconds); all subsequent windows reuse the
executable.  The same model code runs on TPU and CPU unchanged.
"""

from __future__ import annotations

import jax
import numpy as np

from gossip_simulator_tpu.backends.base import Stepper, WINDOW_MS
from gossip_simulator_tpu.models import epidemic, event, graphs, overlay
from gossip_simulator_tpu.models.state import msg64_value
from gossip_simulator_tpu.utils import rng as _rng
from gossip_simulator_tpu.utils.metrics import Stats


class JaxStepper(Stepper):
    name = "jax"

    def init(self) -> None:
        cfg = self.cfg
        self.key = _rng.base_key(cfg.seed)
        if cfg.model == "pushsum":
            from gossip_simulator_tpu.models import pushsum

            self._engine = pushsum
        else:
            self._engine = event if cfg.engine_resolved == "event" \
                else epidemic
        self._mean_delay = (
            (cfg.delaylow + cfg.delayhigh) / 2.0
            if cfg.effective_time_mode == "ticks" else 1.0)
        self._overlay_rounds = 0
        self.exhausted = False
        if cfg.telemetry_enabled:
            from gossip_simulator_tpu.utils.telemetry import TelemetrySession

            self._telem = TelemetrySession(cfg)
        else:
            self._telem = None
        if cfg.resume:
            # State arrives via load_state_pytree; building a graph (or the
            # phase-1 overlay buffers) here would be thrown away -- minutes
            # and GBs at 1e8 nodes.
            self.state = None
            self._overlay_done = True
        elif cfg.graph == "overlay":
            self._setup_overlay(build_state=True)
        else:
            friends, cnt = graphs.generate(cfg, graphs.graph_key(cfg))
            self.state = self._engine.init_state(cfg, friends, cnt)
            self._overlay_done = True
        self._seed_fn = jax.jit(self._engine.make_seed_fn(cfg))
        self._window = 1 if cfg.effective_time_mode == "rounds" else WINDOW_MS
        self._window_fn = self._engine.make_window_fn(cfg, self._window)
        self._run_fn = self._engine.make_run_to_coverage_fn(
            cfg, telemetry=self._telem is not None)
        self._mailbox_dropped = 0

    # --- phase 1 ---------------------------------------------------------------
    def _setup_overlay(self, build_state: bool) -> None:
        """Overlay-engine machinery (round fn, module, optional initial
        state).  `build_state=False` is the phase-1 RESUME path: the
        restored snapshot replaces the initial state, so building the
        bootstrap burst here would be thrown away."""
        cfg = self.cfg
        self._faithful_overlay = cfg.overlay_mode_resolved == "ticks"
        self._osplit = False
        if self._faithful_overlay:
            from gossip_simulator_tpu.models import overlay_ticks

            self._omod = overlay_ticks
            self._oround = overlay_ticks.make_poll_fn(cfg)
            self.ostate = (overlay_ticks.init_state(cfg, self.key)
                           if build_state else None)
        else:
            self._omod = overlay
            self._osplit = overlay.use_split_round(cfg)
            if self._osplit:
                # Memory scale: one round as two jitted calls so donation
                # can alias dead buffers across the boundary (the fused
                # round held ~19.5 GB at n=1e8 -- overlay.make_split_
                # round_fn).  Host pays two dispatches per round; a round
                # is seconds of device work at this n.
                self._oround = overlay.make_split_round_fn(cfg)
            else:
                self._oround = jax.jit(overlay.make_round_fn(cfg))
            # base_key: the static-bootstrap band draws the initial
            # friends table + burst at init (overlay.init_state).
            self.ostate = (overlay.init_state(cfg, base_key=self.key)
                           if build_state else None)
        self._overlay_done = False
        self._orun = None  # lazy: compiled only on the fast path
        self.state = None

    def _quiesced_jit(self):
        """Jitted quiescence predicate: the eager form materializes the
        (cap, n) >= 0 emission masks (~1.7 GB at n=1e8) before reducing;
        fused, the reductions never allocate them."""
        if getattr(self, "_oq", None) is None:
            self._oq = jax.jit(self._omod.quiesced)
        return self._oq

    def _advance_overlay(self) -> None:
        """One overlay round.  In split mode the state is handed over in
        a popped box so no frame here retains the old state while the
        round's serialized calls run (overlay.make_split_round_fn's
        memory contract)."""
        if getattr(self, "_osplit", False):
            box = [self.ostate]
            self.ostate = None
            self.ostate = self._oround(box, self.key)
        else:
            self.ostate = self._oround(self.ostate, self.key)

    def overlay_window(self) -> tuple[int, int, bool]:
        if self._overlay_done:
            return 0, 0, True
        self._advance_overlay()
        self._overlay_rounds += 1
        faithful = self._faithful_overlay
        tick = self.ostate.tick if faithful else 0
        # Split rounds with the dead-row skip already computed quiescence
        # from the emission counts (overlay.make_split_round_fn); the
        # eager predicate reduces multi-GB masks at memory scale.
        q_fast = getattr(self._oround, "last_quiesced", None)
        mk, bk, tick = jax.device_get(
            (self.ostate.win_makeups, self.ostate.win_breakups, tick))
        q = (q_fast if q_fast is not None
             else jax.device_get(self._quiesced_jit()(self.ostate)))
        # True simulated ms from the tick clock in faithful mode; the
        # rounds engine only estimates rounds x mean_delay.
        self._phase1_ms = (float(tick) if faithful
                           else self._overlay_rounds * self._mean_delay)
        if bool(q):
            self._finish_overlay()
        return int(mk), int(bk), bool(q)

    def overlay_run_to_quiescence(self, max_windows: int,
                                  budget: int | None = None
                                  ) -> tuple[int, bool]:
        """Phase-1 fast path: bounded device-side while_loop to quiescence
        (the overlay analog of run_to_target) -- one host sync per bounded
        call instead of one jit dispatch + device_get per window, which
        profiled at ~2.4x the device time.
        Trajectory-identical to the windowed loop (window-indexed keys,
        same quiescence predicate); only for runs with nothing observing
        per-window state (driver gates on printer.observing).  Returns
        (windows_run, quiesced)."""
        if self._overlay_done:
            return 0, True
        import time

        telem = self._telem
        if getattr(self, "_osplit", False):
            # Split-round mode (memory scale): the bounded device-side
            # while_loop would re-fuse the round into one program and
            # re-create the OOM; run the host loop instead -- a round is
            # seconds of device work at this n, so the per-round
            # dispatch + quiescence sync is noise.  Telemetry records
            # host-side here, riding the per-round device_get the split
            # already pays.
            from gossip_simulator_tpu.utils import trace as _trace

            oq = self._quiesced_jit()
            q = False
            while self._overlay_rounds < max_windows:
                t0 = time.perf_counter()
                with _trace.span("phase1.split_round", cat="device"):
                    self._advance_overlay()
                dispatch_s = time.perf_counter() - t0
                self._overlay_rounds += 1
                self._phase1_ms = self._overlay_rounds * self._mean_delay
                # Round 7: with the dead-row skip on, the split round
                # computes quiescence from the emission counts INSIDE the
                # jitted b2 call (one scalar) -- the eager quiesced()
                # otherwise reduces the (cap, n) emission masks every
                # round (~6.4 GB of reads at n=1e8).
                q_fast = getattr(self._oround, "last_quiesced", None)
                if telem is not None:
                    st = self.ostate
                    mk, bk, dr = jax.device_get(
                        (st.win_makeups, st.win_breakups,
                         st.mailbox_dropped))
                    telem.overlay_host_row(
                        [self._overlay_rounds, int(mk), int(bk), int(dr)])
                    telem.tally_overlay_call(dispatch_s,
                                             time.perf_counter() - t0)
                    q = (bool(q_fast) if q_fast is not None
                         else bool(jax.device_get(oq(self.ostate))))
                else:
                    q = (bool(q_fast) if q_fast is not None
                         else bool(jax.device_get(oq(self.ostate))))
                if q:
                    break
            if q:
                self._finish_overlay()
            return self._overlay_rounds, q
        if self._orun is None:
            self._orun = self._omod.make_run_fn(
                self.cfg, telemetry=telem is not None)
        if budget is None:
            # Bounded windows per device call; the sizing lives with
            # each overlay module's cost model.
            budget = self._omod.run_call_budget(self.cfg)
        from gossip_simulator_tpu.utils import trace as _trace

        hist = telem.begin_overlay(max_windows) if telem is not None else None
        q = False
        calls = 0
        while True:
            lim = min(budget, max_windows - self._overlay_rounds)
            if lim <= 0:
                break
            t0 = time.perf_counter()
            # dispatch / sync split: see base.run_bounded_to_target.
            with _trace.span("phase1.dispatch", cat="device", call=calls):
                if hist is not None:
                    self.ostate, polls, q, hist = self._orun(
                        self.ostate, self.key, np.int32(lim), hist)
                else:
                    self.ostate, polls, q = self._orun(
                        self.ostate, self.key, np.int32(lim))
            dispatch_s = time.perf_counter() - t0
            with _trace.span("phase1.sync", cat="device", call=calls) as sp:
                faithful = self._faithful_overlay
                tick = self.ostate.tick if faithful else 0
                polls, q, tick = jax.device_get((polls, q, tick))
                if sp is not None:
                    sp.update(windows=int(polls))
            calls += 1
            if telem is not None:
                telem.tally_overlay_call(dispatch_s, time.perf_counter() - t0)
            self._overlay_rounds += int(polls)
            self._phase1_ms = (float(tick) if faithful
                               else self._overlay_rounds * self._mean_delay)
            if bool(q):
                break
        if hist is not None:
            telem.end_overlay(hist)
        if bool(q):
            self._finish_overlay()
        return self._overlay_rounds, bool(q)

    def _finish_overlay(self) -> None:
        self._overlay_done = True
        # Freeze phase-1 elapsed time: once the epidemic state exists,
        # sim_time_ms switches to its tick (which starts at 0), so the
        # driver's "Took Xms to stabilize" needs this snapshot.
        self._stabilize_ms = self._phase1_ms
        self._mailbox_dropped = int(jax.device_get(
            self.ostate.mailbox_dropped))
        self.state = self._engine.init_state(
            self.cfg, self.ostate.friends, self.ostate.friend_cnt)
        self.ostate = None  # free phase-1 buffers

    # --- phase 2 ---------------------------------------------------------------
    def seed(self) -> None:
        self._seeded = True
        self.state = self._seed_fn(self.state, self.key)

    def gossip_window(self) -> Stats:
        self.state = self._window_fn(self.state, self.key)
        stats, in_flight = self._stats_and_inflight()
        # Heal-on runs never report exhaustion mid-run: a pending dead-
        # friend detection can re-send from an infected healer and revive
        # an empty ring (see base.run_bounded_to_target).
        self.exhausted = (in_flight == 0
                          and self.cfg.protocol != "pushpull"
                          and not self.cfg.overlay_heal_resolved)
        stats.exhausted = self.exhausted
        return stats

    def reset_state(self) -> None:
        """Rebuild phase-2 state from scratch (same seed => same trajectory)
        without re-tracing the jitted step functions.  Needed after a run:
        the hot fns donate their input buffers, so the old state is gone."""
        cfg = self.cfg
        if cfg.graph == "overlay":
            raise ValueError("reset_state requires a static graph")
        # Free the old state FIRST: regenerating while the previous
        # friends table + mail ring are still referenced doubles the HBM
        # footprint (~12 GB transient at 1e8 x fanout 6 -- enough to crash
        # a 16 GB v5e worker, observed r2).
        self.state = None
        friends, cnt = graphs.generate(cfg, graphs.graph_key(cfg))
        self.state = self._engine.init_state(cfg, friends, cnt)
        self.exhausted = False
        if self._telem is not None:
            self._telem.reset_gossip()

    def run_to_target(self) -> Stats:
        """Bench fast path: bounded device-side while_loop toward the
        coverage target (base.run_bounded_to_target)."""
        from gossip_simulator_tpu.backends.base import run_bounded_to_target

        return run_bounded_to_target(self)

    @property
    def overlay_clock_scale(self) -> float:
        """Simulated-ms per recorded overlay clock unit: the tick-faithful
        engine records true ticks; the rounds engine records round counts
        estimated at mean_delay ms each (the windowed loop's clock)."""
        if getattr(self, "_faithful_overlay", False):
            return 1.0
        return getattr(self, "_mean_delay", 1.0)

    def _stats_and_inflight(self) -> tuple[Stats, int]:
        """All progress-window scalars in ONE host round-trip (each
        device_get is a synchronous host<->device hop)."""
        st = self.state
        extra = st.mail_dropped if hasattr(st, "mail_dropped") else 0
        rem = (event.removed_count(st)
               if self.cfg.protocol == "sir" else 0)
        R = self.cfg.rumors
        multi = self.cfg.multi_rumor
        rmin = st.rumor_recv[:R].min() if multi else -1
        rdone = (st.rumor_done[:R] >= 0).sum() if multi else 0
        (tm, tr, tc, trm, tick, dropped, in_flight, sc, sr, pd,
         hr, rmin, rdone) = jax.device_get(
            (st.total_message, st.total_received, st.total_crashed,
             rem, st.tick, extra, event.in_flight(st),
             st.scen_crashed, st.scen_recovered, st.part_dropped,
             st.heal_repaired, rmin, rdone))
        return Stats(
            n=self.cfg.n, round=int(tick),
            total_received=int(tr), total_message=msg64_value(tm),
            total_crashed=int(tc), total_removed=int(trm),
            mailbox_dropped=self._mailbox_dropped + int(dropped),
            scen_crashed=int(sc), scen_recovered=int(sr),
            part_dropped=int(pd), heal_repaired=int(hr),
            exhausted=self.exhausted,
            rumors=R, rumor_min_recv=int(rmin), rumors_done=int(rdone),
        ), int(in_flight)

    def stats(self) -> Stats:
        return self._stats_and_inflight()[0]

    def sim_time_ms(self) -> float:
        if self.state is None or not self._overlay_done:
            return getattr(self, "_phase1_ms",
                           self._overlay_rounds * self._mean_delay)
        if not getattr(self, "_seeded", False):
            # Between quiescence and the broadcast: phase-1 elapsed time
            # (the epidemic tick is 0 and would misreport stabilization).
            return getattr(self, "_stabilize_ms", 0.0)
        return float(jax.device_get(self.state.tick))

    # --- checkpoint ------------------------------------------------------------
    def overlay_state_pytree(self):
        """Mid-construction phase-1 snapshot (None once the overlay is
        done -- phase-2 snapshots take over then)."""
        if self._overlay_done or self.ostate is None:
            return None
        # np.array (COPY), not np.asarray: on the CPU platform asarray of
        # a device buffer is zero-copy, and the donating round fns reuse
        # that buffer on the next call -- the "snapshot" would silently
        # track the live state (observed as resumed-trajectory drift in
        # the checkpoint tests; on TPU the device->host transfer always
        # copied, which is why hardware never showed it).
        return {k: np.array(v) for k, v in self.ostate._asdict().items()}

    def load_overlay_state_pytree(self, tree, windows: int = 0) -> None:
        """Resume INTO phase 1: validate the overlay snapshot
        (utils/checkpoint.prepare_overlay_restore_tree), rebuild the
        engine machinery without the bootstrap burst, and continue
        construction from the restored state.  `windows` is the snapshot's
        overlay-window count (drives the rounds engine's estimated
        clock; the ticks engine's clock rides the restored tick)."""
        from gossip_simulator_tpu.utils.checkpoint import \
            prepare_overlay_restore_tree

        cfg = self.cfg
        tree = prepare_overlay_restore_tree(tree, cfg, n_shards=1)
        self._setup_overlay(build_state=False)
        cls = (self._omod.OverlayTickState if self._faithful_overlay
               else self._omod.OverlayState)
        # jax.numpy.array (device COPY), not asarray: a zero-copy restore
        # feeding the donating round fns lets XLA reuse a buffer it does
        # not own (see load_state_pytree's note).
        self.ostate = cls(**{k: jax.numpy.array(v)
                             for k, v in tree.items()})
        self._overlay_rounds = int(windows)
        self._phase1_ms = (
            float(np.asarray(tree["tick"])) if self._faithful_overlay
            else self._overlay_rounds * self._mean_delay)

    def state_pytree(self):
        if self.state is None:
            return None
        # COPY (np.array), never view: see overlay_state_pytree's note on
        # the CPU zero-copy + donated-buffer-reuse aliasing.
        tree = {k: np.array(v) for k, v in self.state._asdict().items()}
        if "pending" in tree:
            # The ring engine's flat delay ring is saved in its logical
            # (d, n) shape, as the sharded backend saves its shards' rings,
            # so snapshots move between the two (epidemic.ring_row).
            tree["pending"] = tree["pending"].reshape(-1, self.cfg.n)
        if "mail_ids" in tree:
            # Record the mail-ring geometry so a future build whose AUTO
            # slot-cap/chunk sizing differs can repack instead of rejecting
            # the snapshot (see load_state_pytree).  Pushsum sizes its
            # slots for emission volume, so its own module is the
            # geometry authority there.
            cfg, n = self.cfg, self.cfg.n
            if cfg.model == "pushsum":
                from gossip_simulator_tpu.models import pushsum as geo
            else:
                geo = event
            tree["mail_geom"] = np.asarray(
                [geo.slot_cap(cfg, n), geo.drain_chunk(cfg, n)],
                dtype=np.int64)
        # Phase-1 overlay drops live host-side, not in the device state --
        # persist them or a resumed run under-reports mailbox_dropped.
        tree["host_mailbox_dropped"] = np.int64(self._mailbox_dropped)
        return tree

    def load_state_pytree(self, tree) -> None:
        """Restore a snapshot (validation, legacy coercion and mail-ring
        geometry repack shared with the sharded backend:
        utils/checkpoint.prepare_restore_tree)."""
        from gossip_simulator_tpu.models.event import EventState
        from gossip_simulator_tpu.models.state import SimState
        from gossip_simulator_tpu.utils.checkpoint import prepare_restore_tree

        cfg = self.cfg
        tree = prepare_restore_tree(tree, cfg, n_shards=1)
        self._mailbox_dropped = int(tree.pop("host_mailbox_dropped", 0))
        if cfg.model == "pushsum":
            from gossip_simulator_tpu.models.pushsum import PushSumState

            cls = PushSumState
        else:
            cls = EventState if cfg.engine_resolved == "event" else SimState
        if cls is SimState:
            tree["pending"] = np.reshape(tree["pending"], -1)
        # jax.numpy.array (device COPY), not asarray: on the CPU platform
        # asarray of a host array can be zero-copy, and these leaves feed
        # straight into DONATING jitted fns -- XLA then reuses a buffer it
        # does not own, corrupting the restored state (the load-side twin
        # of state_pytree's copy note; TPU transfers always copy).
        self.state = cls(**{k: jax.numpy.array(v)
                            for k, v in tree.items()})
        self._overlay_done = True
        self._seeded = True  # snapshots are taken mid-phase-2
