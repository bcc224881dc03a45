"""Host-side span tracer: the flight recorder's timing substrate.

Every cross-revision perf claim so far (PR 2's exchange overhead, PR 3's
phase-1 floors, PR 6's kernel parity) was established by hand-run A/B twins
and hand-diffed BENCH_SELF rows.  This module records the same phase
boundaries mechanically: named spans (phase-1 rounds, phase-2 windows and
bounded calls, checkpoint save/load, sharded exchange, bench captures) with
counter payloads (messages, mail high-water, drops) as span args.

One recorder, two exporters on one clock:

* every span is a ``jax.profiler.TraceAnnotation`` carrying its category
  as the ``cat`` stat (plus its entry args), whether or not a tracer is
  active -- any profiler session sees the program's spans on the device
  trace's clock: `-xprof DIR`, a benchmark's traced run, an external
  XProf capture;
* `-trace PATH` writes them as Chrome trace-event JSON (`chrome://tracing`
  / Perfetto "X" complete events) stamped on the same wall clock: the
  document's ``clock_origin_unix_ns`` plus ``ts`` x 1000 is Unix ns, the
  value an xplane gives as ``profile_start_time`` + an event's start.

Instrumentation sites use the module-level ``span()`` / ``instant()``
helpers.  With no active tracer a span is the annotation alone (one
enter/exit, ~1 us, a no-op unless a profiler is running) and yields None,
so call sites compute no counters and force no syncs.  All sites sit on
host-side per-call/per-window boundaries, never inside jitted code.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Optional


def _annotation(name: str, cat: str, args: dict):
    from jax import profiler

    return profiler.TraceAnnotation(name, cat=cat, **args)


class Tracer:
    """Collects Chrome trace-event "X" (complete) events host-side.

    Timestamps are microseconds from the tracer's construction on the wall
    clock (``time.time_ns``), the clock the JAX profiler stamps host events
    with; ``clock_origin_unix_ns`` in the document's metadata is that
    origin.  One tracer spans one run (or one bench suite).  Thread-safe
    appends: the sharded backend and bench are single-threaded today, but
    the lock keeps the recorder safe if a callback ever fires from a jax
    runtime thread.
    """

    def __init__(self, path: str = "", xprof_dir: str = ""):
        self.path = path
        self.xprof_dir = xprof_dir
        self.events: list[dict] = []
        self.origin_ns = time.time_ns()
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._xprof_cm = None

    # --- clock ----------------------------------------------------------
    def now_us(self) -> float:
        return (time.time_ns() - self.origin_ns) / 1e3

    # --- recording ------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host", **args):
        """One timed region.  `args` (counters: messages, mail high-water,
        drops, ...) land in the event's ``args`` dict; values must be
        JSON-serializable scalars.  Yields the args dict so counters only
        known at span exit (window totals, drop counts) can be added
        before the event is sealed:

            with tracer.span("gossip.window") as sp:
                stats = stepper.gossip_window()
                sp["messages"] = stats.total_message
        """
        ann = _annotation(name, cat, args)
        t0 = self.now_us()
        try:
            with ann:
                yield args
        finally:
            ev = {"name": name, "cat": cat, "ph": "X", "ts": t0,
                  "dur": self.now_us() - t0, "pid": self._pid,
                  "tid": threading.get_ident()}
            if args:
                ev["args"] = dict(args)
            with self._lock:
                self.events.append(ev)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        """Zero-duration marker (trace-event "i")."""
        ev = {"name": name, "cat": cat, "ph": "i", "s": "p",
              "ts": self.now_us(), "pid": self._pid,
              "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    # --- xprof (device timeline) ----------------------------------------
    def start(self) -> None:
        """Begin the optional device-side profile (`-xprof DIR`)."""
        if self.xprof_dir and self._xprof_cm is None:
            import jax

            self._xprof_cm = jax.profiler.trace(self.xprof_dir)
            self._xprof_cm.__enter__()

    def stop(self) -> None:
        if self._xprof_cm is not None:
            self._xprof_cm.__exit__(None, None, None)
            self._xprof_cm = None

    # --- output ---------------------------------------------------------
    def to_json(self, metadata: Optional[dict] = None) -> dict:
        with self._lock:
            events = list(self.events)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "metadata": {"clock_origin_unix_ns": self.origin_ns,
                             **(metadata or {})}}

    def write(self, path: str = "", metadata: Optional[dict] = None) -> str:
        """Write the trace file (one JSON document, Chrome/Perfetto
        loadable); returns the path written."""
        out = path or self.path
        if not out:
            raise ValueError("Tracer.write: no path configured")
        parent = os.path.dirname(os.path.abspath(out))
        os.makedirs(parent, exist_ok=True)
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(metadata), f)
        os.replace(tmp, out)
        return out


# --- module-level active tracer ---------------------------------------------
#
# The driver (or bench) activates one tracer around a run; every
# instrumentation site in backends/checkpoint/bench reaches it through
# these helpers and costs a single None check when tracing is off.

_ACTIVE: Optional[Tracer] = None


def activate(tracer: Tracer) -> Tracer:
    global _ACTIVE
    _ACTIVE = tracer
    return tracer


def deactivate() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> Optional[Tracer]:
    return _ACTIVE


class _AnnotationOnly:
    """Inactive-tracer span: the profiler annotation alone.  Yields None
    (callers guard counter updates with `if sp:`)."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, cat: str, args: dict):
        self._ann = _annotation(name, cat, args)

    def __enter__(self):
        self._ann.__enter__()
        return None

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False


def span(name: str, cat: str = "host", **args):
    """Context manager: a profiler annotation, and a timed span on the
    active tracer.  Yields the span's args dict (add counters before
    exit), or None when no tracer is active."""
    t = _ACTIVE
    if t is None:
        return _AnnotationOnly(name, cat, args)
    return t.span(name, cat=cat, **args)


def instant(name: str, cat: str = "host", **args) -> None:
    t = _ACTIVE
    if t is not None:
        t.instant(name, cat=cat, **args)


@contextlib.contextmanager
def activated(tracer: Optional[Tracer]):
    """Scoped activation (used by the driver and bench): activates on
    entry, starts the optional xprof profile, and always deactivates --
    a raised run never leaves a stale tracer behind for the next run in
    the same process (bench, tests)."""
    if tracer is None:
        yield None
        return
    prev = _ACTIVE
    activate(tracer)
    tracer.start()
    try:
        yield tracer
    finally:
        tracer.stop()
        globals()["_ACTIVE"] = prev
