"""Mesh-sharded backend: the 100M-node path (BASELINE.json config 5).

Same Stepper surface as the single-chip jax backend; state lives sharded
across the mesh from birth (graph generation happens per shard -- nothing
is ever materialized on one device), and every window is one jitted
shard_map call whose collectives ride ICI.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from gossip_simulator_tpu.backends.base import Stepper, WINDOW_MS
from gossip_simulator_tpu.models import epidemic, overlay
from gossip_simulator_tpu.parallel import sharded_step
from gossip_simulator_tpu.parallel.mesh import AXIS, node_mesh, shard_size
from gossip_simulator_tpu.utils import rng as _rng
from gossip_simulator_tpu.models.state import msg64_value
from gossip_simulator_tpu.utils.metrics import Stats


def _host_gather(x) -> np.ndarray:
    """Leaf -> host array.  Under -distributed a node-sharded array is not
    fully addressable from one process; process_allgather (a collective --
    every process must traverse the same leaves in the same order, which
    NamedTuple._asdict guarantees) assembles the global value on every
    host.  Replicated scalars and single-process runs take the plain path.
    np.array (COPY), not np.asarray: on the CPU platform asarray of a
    device buffer is zero-copy and the donating window fns reuse the
    buffer on the next call, silently mutating the 'snapshot' (see
    JaxStepper.overlay_state_pytree's note)."""
    if getattr(x, "is_fully_addressable", True):
        return np.array(x)
    from jax.experimental import multihost_utils

    # gossip-lint: allow(donation-aliasing) process_allgather materializes
    # a fresh global array from the collective -- it never aliases the
    # donated per-shard state buffers, so the zero-copy view is safe.
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


class ShardedStepper(Stepper):
    name = "sharded"

    @property
    def primary_host(self) -> bool:
        return jax.process_index() == 0

    def __init__(self, cfg, n_devices: int | None = None):
        super().__init__(cfg)
        self.mesh = node_mesh(n_devices)
        # Validate divisibility and the per-shard ring's int32 bound early.
        cfg.check_ring_rows(shard_size(cfg.n, self.mesh),
                            shards=int(self.mesh.shape[AXIS]))

    def init(self) -> None:
        cfg = self.cfg
        self.key = _rng.base_key(cfg.seed)
        self._mean_delay = (
            (cfg.delaylow + cfg.delayhigh) / 2.0
            if cfg.effective_time_mode == "ticks" else 1.0)
        self._overlay_rounds = 0
        self.exhausted = False
        self._mailbox_dropped = 0
        self._window = 1 if cfg.effective_time_mode == "rounds" else WINDOW_MS
        if cfg.telemetry_enabled:
            from gossip_simulator_tpu.utils.telemetry import TelemetrySession

            self._telem = TelemetrySession(
                cfg, n_shards=int(self.mesh.shape[AXIS]))
        else:
            self._telem = None
        telem_on = self._telem is not None
        if cfg.model == "pushsum":
            from gossip_simulator_tpu.parallel import pushsum_sharded

            self._window_fn = pushsum_sharded.make_window_fn(
                cfg, self.mesh, self._window)
            self._seed_fn = pushsum_sharded.make_seed_fn(cfg, self.mesh)
            self._run_fn = pushsum_sharded.make_run_to_coverage_fn(
                cfg, self.mesh, telemetry=telem_on)
            init_fn = pushsum_sharded.make_sharded_pushsum_init
        elif cfg.engine_resolved == "event":
            from gossip_simulator_tpu.parallel import event_sharded

            self._window_fn = event_sharded.make_window_fn(
                cfg, self.mesh, self._window)
            self._seed_fn = event_sharded.make_seed_fn(cfg, self.mesh)
            self._run_fn = event_sharded.make_run_to_coverage_fn(
                cfg, self.mesh, telemetry=telem_on)
            init_fn = event_sharded.make_sharded_event_init
        else:
            self._window_fn = sharded_step.make_window_fn(cfg, self.mesh,
                                                          self._window)
            self._seed_fn = sharded_step.make_seed_fn(cfg, self.mesh)
            self._run_fn = sharded_step.make_run_to_coverage_fn(
                cfg, self.mesh, telemetry=telem_on)
            init_fn = sharded_step.make_sharded_init
        if cfg.resume:
            # State arrives via load_state_pytree; building a sharded graph
            # here would be thrown away (see JaxStepper.init).
            self.state = None
            self._overlay_done = True
        elif cfg.graph == "overlay":
            self._setup_overlay(build_state=True)
        else:
            self._init_fn = init_fn(cfg, self.mesh)
            self.state = self._init_fn()
            self._overlay_done = True

    # --- phase 1 ---------------------------------------------------------------
    def _setup_overlay(self, build_state: bool) -> None:
        """Overlay machinery over the mesh; `build_state=False` is the
        phase-1 resume path (see JaxStepper._setup_overlay)."""
        cfg = self.cfg
        self._faithful_overlay = cfg.overlay_mode_resolved == "ticks"
        if self._faithful_overlay:
            from gossip_simulator_tpu.parallel import \
                overlay_ticks_sharded as ots

            self._oround = ots.make_poll_fn(cfg, self.mesh)
            self.ostate = (ots.make_sharded_init(cfg, self.mesh)(self.key)
                           if build_state else None)
        else:
            n_local = shard_size(cfg.n, self.mesh)
            if n_local >= overlay.SPLIT_ROUND_MIN_ROWS:
                # The sharded rounds engine always runs the FUSED round
                # inside shard_map (the split round's host-driven call
                # sequence cannot run per shard); per-shard slices at
                # memory scale can hit the fused-round OOM class the
                # single-device split exists to avoid (advisor r4).
                import warnings

                warnings.warn(
                    f"sharded overlay: {n_local} rows/shard is at the "
                    f"fused-round memory band (>= "
                    f"{overlay.SPLIT_ROUND_MIN_ROWS}); the sharded engine "
                    "has no split-round fallback -- use at least "
                    f"{-(-cfg.n // overlay.SPLIT_ROUND_MIN_ROWS)} devices "
                    "for this n, or expect HBM exhaustion on 16 GB chips",
                    stacklevel=2)
            self._oround = sharded_step.make_overlay_round_fn(
                cfg, self.mesh)
            self.ostate = (sharded_step.make_sharded_overlay_init(
                cfg, self.mesh)() if build_state else None)
        self._overlay_done = False
        self.state = None

    def _overlay_mod(self):
        if getattr(self, "_faithful_overlay", False):
            from gossip_simulator_tpu.models import overlay_ticks

            return overlay_ticks
        return overlay

    def overlay_window(self) -> tuple[int, int, bool]:
        if self._overlay_done:
            return 0, 0, True
        self.ostate = self._oround(self.ostate, self.key)
        self._overlay_rounds += 1
        faithful = getattr(self, "_faithful_overlay", False)
        quiesced = self._overlay_mod().quiesced(self.ostate)
        tick = self.ostate.tick if faithful else 0
        mk, bk, q, tick = jax.device_get(
            (self.ostate.win_makeups, self.ostate.win_breakups,
             quiesced, tick))
        self._phase1_ms = (float(tick) if faithful
                           else self._overlay_rounds * self._mean_delay)
        if bool(q):
            self._finish_overlay()
        return int(mk), int(bk), bool(q)

    def overlay_run_to_quiescence(self, max_windows: int,
                                  budget: int | None = None
                                  ) -> tuple[int, bool]:
        """Phase-1 fast path for quiet runs (see JaxStepper's method --
        same contract, same driver gate).  The bounded while_loop wraps
        the jitted shard_map'd poll OUTSIDE shard_map: the quiescence
        counters are replicated on the outer state (psum'd inside the
        poll), so the loop condition is mesh-uniform by construction and
        every shard runs the same trip count."""
        if self._overlay_done:
            return 0, True
        import time

        telem = self._telem
        omod = self._overlay_mod()
        if getattr(self, "_orun", None) is None:
            self._orun = overlay.make_bounded_run(
                self._oround, omod.quiesced, telemetry=telem is not None)
        if budget is None:
            # Per-call device work scales with the SHARD slice, so the
            # single-chip watchdog budget stretches by the shard count
            # (scaled inside run_call_budget, before its >=1 clamp).
            budget = omod.run_call_budget(self.cfg,
                                          shards=self.mesh.shape[AXIS])
        from gossip_simulator_tpu.utils import trace as _trace

        faithful = getattr(self, "_faithful_overlay", False)
        hist = telem.begin_overlay(max_windows) if telem is not None else None
        q = False
        calls = 0
        while True:
            lim = min(budget, max_windows - self._overlay_rounds)
            if lim <= 0:
                break
            t0 = time.perf_counter()
            # Each bounded call dispatches the shard_map'd poll: the
            # cross-shard all_to_all exchange lives inside it, so the
            # dispatch and sync spans together are the host-visible
            # "sharded exchange" cost envelope (the split: see
            # base.run_bounded_to_target).
            with _trace.span("phase1.dispatch", cat="device", call=calls):
                if hist is not None:
                    self.ostate, polls, q, hist = self._orun(
                        self.ostate, self.key, np.int32(lim), hist)
                else:
                    self.ostate, polls, q = self._orun(
                        self.ostate, self.key, np.int32(lim))
            dispatch_s = time.perf_counter() - t0
            with _trace.span("phase1.sync", cat="device", call=calls) as sp:
                tick = self.ostate.tick if faithful else 0
                polls, q, tick = jax.device_get((polls, q, tick))
                if sp is not None:
                    sp.update(windows=int(polls),
                              shards=int(self.mesh.shape[AXIS]))
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
        # Freeze phase-1 elapsed time (see JaxStepper.overlay_window).
        self._stabilize_ms = self._phase1_ms
        self._mailbox_dropped = int(
            jax.device_get(self.ostate.mailbox_dropped))
        self.state = self._epidemic_from_overlay()
        self.ostate = None

    def _epidemic_from_overlay(self):
        cfg, mesh = self.cfg, self.mesh
        n_local = shard_size(cfg.n, mesh)
        from jax.sharding import PartitionSpec as P

        n_shards = int(mesh.shape[AXIS])
        if cfg.engine_resolved == "event":
            from gossip_simulator_tpu.models import event as _event
            from gossip_simulator_tpu.parallel import event_sharded

            def build(c, friends, cnt):
                return _event.init_state(c, friends, cnt, n_shards=n_shards)
            out_specs = event_sharded.event_state_specs(cfg)
        else:
            def build(c, friends, cnt):
                return epidemic.init_state(c, friends, cnt, n_local=n_local,
                                           n_shards=n_shards)
            out_specs = sharded_step.sim_state_specs(cfg)

        from gossip_simulator_tpu.parallel.mesh import shard_map

        fn = shard_map(lambda f, c: build(cfg, f, c), mesh=mesh,
                       in_specs=(P("nodes", None), P("nodes")),
                       out_specs=out_specs)
        return jax.jit(fn)(self.ostate.friends, self.ostate.friend_cnt)

    # --- phase 2 ---------------------------------------------------------------
    def seed(self) -> None:
        self._seeded = True
        self.state = self._seed_fn(self.state, self.key)

    def gossip_window(self) -> Stats:
        from gossip_simulator_tpu.models.event import in_flight as _inflight
        from gossip_simulator_tpu.utils import trace as _trace

        # The per-window sharded dispatch (all_to_all exchange inside).
        with _trace.span("phase2.sharded_window", cat="device",
                         shards=int(self.mesh.shape[AXIS])):
            self.state = self._window_fn(self.state, self.key)
        stats = self.stats()
        in_flight = int(jax.device_get(_inflight(self.state)))
        # Heal-on runs never report exhaustion mid-run (see
        # base.run_bounded_to_target).
        self.exhausted = (in_flight == 0
                          and self.cfg.protocol != "pushpull"
                          and not self.cfg.overlay_heal_resolved)
        stats.exhausted = self.exhausted
        return stats

    def reset_state(self) -> None:
        """Rebuild phase-2 state (same seed => same trajectory) without
        re-tracing; the hot fns donate their inputs (see JaxStepper)."""
        if self.cfg.graph == "overlay":
            raise ValueError("reset_state requires a static graph")
        self.state = self._init_fn()
        self.exhausted = False
        if self._telem is not None:
            self._telem.reset_gossip()

    def run_to_target(self) -> Stats:
        """Bounded device-side while_loop (base.run_bounded_to_target)."""
        from gossip_simulator_tpu.backends.base import run_bounded_to_target

        return run_bounded_to_target(self)

    @property
    def overlay_clock_scale(self) -> float:
        """See JaxStepper.overlay_clock_scale."""
        if getattr(self, "_faithful_overlay", False):
            return 1.0
        return getattr(self, "_mean_delay", 1.0)

    def stats(self) -> Stats:
        from gossip_simulator_tpu.models import event as event_mod

        st = self.state
        extra = st.mail_dropped if hasattr(st, "mail_dropped") else 0
        rem = (event_mod.removed_count(st)
               if self.cfg.protocol == "sir" else 0)
        R = self.cfg.rumors
        multi = self.cfg.multi_rumor
        rmin = st.rumor_recv[:R].min() if multi else -1
        rdone = (st.rumor_done[:R] >= 0).sum() if multi else 0
        (tm, tr, tc, trm, xo, tick, dropped, sc, sr, pd,
         hr, rmin, rdone) = jax.device_get(
            (st.total_message, st.total_received, st.total_crashed,
             rem, st.exchange_overflow, st.tick, extra,
             st.scen_crashed, st.scen_recovered, st.part_dropped,
             st.heal_repaired, rmin, rdone))
        return Stats(
            n=self.cfg.n, round=int(tick),
            total_received=int(tr), total_message=msg64_value(tm),
            total_crashed=int(tc), total_removed=int(trm),
            mailbox_dropped=self._mailbox_dropped + int(dropped),
            exchange_overflow=int(xo),
            scen_crashed=int(sc), scen_recovered=int(sr),
            part_dropped=int(pd), heal_repaired=int(hr),
            exhausted=self.exhausted,
            rumors=R, rumor_min_recv=int(rmin), rumors_done=int(rdone),
        )

    def sim_time_ms(self) -> float:
        if self.state is None or not self._overlay_done:
            return getattr(self, "_phase1_ms",
                           self._overlay_rounds * self._mean_delay)
        if not getattr(self, "_seeded", False):
            # Between quiescence and the broadcast: phase-1 elapsed time.
            return getattr(self, "_stabilize_ms", 0.0)
        return float(jax.device_get(self.state.tick))

    def overlay_state_pytree(self):
        """Host-gathered mid-construction phase-1 snapshot (None once the
        overlay is done).  Sharded leaves gather to global arrays; the
        ticks engine's packed ring gathers as S per-shard rings
        concatenated (spec P(AXIS)), so it restores onto the same shard
        count only -- prepare_overlay_restore_tree checks the geometry."""
        if self._overlay_done or self.ostate is None:
            return None
        return {k: _host_gather(v) for k, v in self.ostate._asdict().items()}

    def load_overlay_state_pytree(self, tree, windows: int = 0) -> None:
        """Resume INTO phase 1 on the mesh (see JaxStepper's method)."""
        from jax.sharding import NamedSharding

        from gossip_simulator_tpu.utils.checkpoint import \
            prepare_overlay_restore_tree

        cfg, mesh = self.cfg, self.mesh
        tree = prepare_overlay_restore_tree(tree, cfg,
                                            n_shards=mesh.shape[AXIS])
        self._setup_overlay(build_state=False)
        if self._faithful_overlay:
            from gossip_simulator_tpu.models.overlay_ticks import \
                OverlayTickState
            from gossip_simulator_tpu.parallel.overlay_ticks_sharded import \
                overlay_tick_state_specs

            cls, specs = OverlayTickState, overlay_tick_state_specs()
        else:
            from gossip_simulator_tpu.models.state import OverlayState

            cls, specs = OverlayState, sharded_step.overlay_state_specs()
        # jnp.array (device COPY) before placement: see load_state_pytree's
        # zero-copy + donation note.
        self.ostate = cls(**{
            k: jax.device_put(jnp.array(v),
                              NamedSharding(mesh, getattr(specs, k)))
            for k, v in tree.items()})
        self._overlay_rounds = int(windows)
        self._phase1_ms = (
            float(np.asarray(tree["tick"])) if self._faithful_overlay
            else self._overlay_rounds * self._mean_delay)

    def state_pytree(self):
        """Host-gathered snapshot (np.asarray collects all shards).  The
        event mail ring is S per-shard rings concatenated, so mail_geom
        records the PER-SHARD slot geometry plus the shard count -- a
        snapshot restores onto the same device count only.  The ring
        engine's per-shard flat delay rings are saved as the logical (d, n)
        ring, the single-device backend's form, so its state restores onto
        any mesh and onto `-backend jax`."""
        if self.state is None:
            return None
        tree = {k: _host_gather(v) for k, v in self.state._asdict().items()}
        if "pending" in tree:
            tree["pending"] = self._ring_logical(tree["pending"])
        if "mail_ids" in tree:
            cfg = self.cfg
            if cfg.model == "pushsum":
                from gossip_simulator_tpu.models import pushsum as geo
            else:
                from gossip_simulator_tpu.models import event as geo

            n_local = shard_size(cfg.n, self.mesh)
            tree["mail_geom"] = np.asarray(
                [geo.slot_cap(cfg, n_local), geo.drain_chunk(cfg, n_local),
                 self.mesh.shape[AXIS]], dtype=np.int64)
        # Phase-1 overlay drops live host-side, not in the device state --
        # persist them or a resumed run under-reports mailbox_dropped.
        tree["host_mailbox_dropped"] = np.int64(self._mailbox_dropped)
        return tree

    def load_state_pytree(self, tree) -> None:
        """Restore a snapshot onto the mesh (validation, legacy coercion
        and per-shard mail-ring repack shared with the single-device
        backend: utils/checkpoint.prepare_restore_tree), then device_put
        every leaf with its partition spec -- the restored run's trajectory
        is identical to the uninterrupted one (step keys depend only on
        (seed, tick, shard))."""
        from jax.sharding import NamedSharding

        from gossip_simulator_tpu.models.event import EventState
        from gossip_simulator_tpu.models.state import SimState
        from gossip_simulator_tpu.parallel import event_sharded
        from gossip_simulator_tpu.utils.checkpoint import prepare_restore_tree

        cfg, mesh = self.cfg, self.mesh
        tree = prepare_restore_tree(tree, cfg, n_shards=mesh.shape[AXIS])
        self._mailbox_dropped = int(tree.pop("host_mailbox_dropped", 0))
        if cfg.model == "pushsum":
            from gossip_simulator_tpu.models.pushsum import PushSumState
            from gossip_simulator_tpu.parallel import pushsum_sharded

            cls = PushSumState
            specs = pushsum_sharded.pushsum_state_specs(cfg)
        elif cfg.engine_resolved == "event":
            cls, specs = EventState, event_sharded.event_state_specs(cfg)
        else:
            cls, specs = SimState, sharded_step.sim_state_specs(cfg)
            tree["pending"] = self._ring_per_shard(tree["pending"])
        # jnp.array (device COPY) before placement: on the CPU platform
        # device_put of a host array can be zero-copy, and the restored
        # leaves feed straight into DONATING jitted fns -- XLA then reuses
        # a buffer it does not own, corrupting the restored state
        # (observed as nondeterministic quiet-resume totals on the CPU
        # mesh; the save-side twin of _host_gather's copy note.  TPU
        # device_put always copies to HBM, masking this on hardware).
        self.state = cls(**{
            k: jax.device_put(jnp.array(v),
                              NamedSharding(mesh, getattr(specs, k)))
            for k, v in tree.items()})
        self._overlay_done = True
        self._seeded = True  # snapshots are taken mid-phase-2

    def _ring_logical(self, flat: np.ndarray) -> np.ndarray:
        """The shards' flat rings side by side, (S, d, n_local), as the
        logical (d, n) ring: slot t of node s * n_local + r."""
        s = int(self.mesh.shape[AXIS])
        n_local = shard_size(self.cfg.n, self.mesh)
        return (flat.reshape(s, -1, n_local).transpose(1, 0, 2)
                .reshape(-1, self.cfg.n))

    def _ring_per_shard(self, ring: np.ndarray) -> np.ndarray:
        """Inverse of _ring_logical: (d, n) to each shard's flat ring."""
        s = int(self.mesh.shape[AXIS])
        n_local = shard_size(self.cfg.n, self.mesh)
        d = ring.shape[0]
        return np.ascontiguousarray(
            ring.reshape(d, s, n_local).transpose(1, 0, 2)).reshape(-1)
