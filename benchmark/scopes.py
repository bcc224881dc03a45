"""Device time inside phase 2 by the program's named scopes, and the
device's idle gaps labelled by what the host was doing in them, from a
traced simulation's .xplane.pb:

    python3 benchmark/scopes.py <file.xplane.pb>

prints the reduction as one JSON line.  The harness does not call it yet
(PERF.md, Open questions).

Scopes.  The ring engine's phase-2 program opens jax.named_scope `combine`,
`emit`, `deliver` and `poll` (models/epidemic.py).  On a TPU the op_name
path of an "XLA Ops" event is the `tf_op` stat of the event's metadata,
which jax.profiler.ProfileData does not expose, so the device planes are
read from the file here (a few protobuf fields).  Ops nest on the line (a
while loop's interval holds its body's ops), so each op counts its self
time: its interval less the union of the ops nested in it.  Self times add
up to busy time, and no loop is counted twice.  An op belongs to the
innermost of the four names on its own path; an op whose path names none
-- the ops the compiler adds carry no path at all -- belongs to the scope
of the innermost op enclosing it on the line, the loop that runs it, else
to `unscoped`.  `inherited_s` gives how much of each scope came that way.

Gaps.  Each idle gap of device 0 inside bench.sim is labelled with the
innermost host event covering its midpoint, of two kinds only: program
spans (annotations with a `cat` stat, utils/trace.py) and JAX's
compile-path annotations.  `prep_idle_s` sums the gaps inside a program
`*.dispatch` or `seed` span or a compile-path annotation: the chip waiting
while the host traces, lowers and loads a fresh stepper's programs.  It is
None in a trace with no program span.
"""

from __future__ import annotations

import reduce as R

SCOPES = ("combine", "emit", "deliver", "poll")
UNSCOPED = "unscoped"
UNLABELLED = "unlabelled"
PHASE2_SPAN = "bench.phase2"
COMPILE_PATH = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
                "backend_compile_and_load")
PREP_SPANS = ("seed",)
PREP_SUFFIX = ".dispatch"
OP_PATH_STAT = "tf_op"
TOP = 10


# --- the xplane's device planes (protobuf wire format) ----------------------

def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of one message: ints for varints, memoryview
    slices for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entries(entries: list) -> dict:
    """map<int64, message> entries -> {key: value bytes}."""
    out = {}
    for e in entries:
        kv = dict(_fields(e))
        out[kv.get(1, 0)] = kv.get(2, b"")
    return out


def _plane_ops(plane: dict, names: dict) -> list:
    """(start_ns, end_ns, op name, op_name path) of the plane's op lines."""
    ops_meta = {}
    for mid, md in _map_entries(plane.get(4, [])).items():
        name = disp = path = ""
        for f, v in _fields(md):
            if f == 2:
                name = _text(v)
            elif f == 4:
                disp = _text(v)
            elif f == 5:
                st = dict(_fields(v))
                if names.get(st.get(1)) == OP_PATH_STAT:
                    path = (_text(st[5]) if 5 in st
                            else names.get(st.get(7), ""))
        ops_meta[mid] = (disp or name.split(" = ")[0].lstrip("%"), path)
    out = []
    for line in plane.get(3, []):
        lf = {}
        for f, v in _fields(line):
            lf.setdefault(f, []).append(v)
        if _text(lf.get(2, [b""])[0]) not in R.OP_LINES:
            continue
        ts_ps = lf.get(3, [0])[0] * 1000
        for ev in lf.get(4, []):
            e = dict(_fields(ev))
            s = ts_ps + e.get(2, 0)
            name, path = ops_meta.get(e.get(1, 0), ("", ""))
            out.append((s / 1000, (s + e.get(3, 0)) / 1000, name, path))
    return out


def device_ops(raw: bytes) -> list:
    """[(device plane name, [(start_ns, end_ns, op, path)])], by id."""
    devices = []
    for f, pv in _fields(memoryview(raw)):
        if f != 1:
            continue
        plane = {}
        for g, v in _fields(pv):
            plane.setdefault(g, []).append(v)
        pname = _text(plane.get(2, [b""])[0])
        if not R.DEVICE_PLANE.match(pname):
            continue
        names = {}
        for sm in _map_entries(plane.get(5, [])).values():
            kv = dict(_fields(sm))
            names[kv.get(1, 0)] = _text(kv.get(2, b""))
        devices.append((pname, _plane_ops(plane, names)))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    return devices


def host_events(pd) -> list:
    """(name, start_ns, end_ns, is program span) of the host events this
    module reads: bench.* spans, program spans, compile-path annotations."""
    out = []
    for p in pd.planes:
        if R.DEVICE_PLANE.match(p.name):
            continue
        for ln in p.lines:
            for ev in ln.events:
                name = ev.name
                if name.startswith("$"):  # the Python tracer's events
                    continue
                program = name not in COMPILE_PATH and not name.startswith(
                    R.SPAN_PREFIX) and any(k == "cat" for k, _ in ev.stats)
                if program or name in COMPILE_PATH or name.startswith(
                        R.SPAN_PREFIX):
                    out.append((name, ev.start_ns, ev.end_ns, program))
    return out


# --- reduction --------------------------------------------------------------

def scope_of(path: str):
    """The innermost of SCOPES on an op_name path ("jit(f)/emit/while/
    body/deliver/add", optionally ":<op type>" after it), else None."""
    for part in reversed(path.rsplit(":", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return None


def self_intervals(ops: list) -> list:
    """[(op, scope, inherited, self pieces)]: each op's interval less the
    union of the ops nested in it on the line, and its scope -- from its
    own path, else (inherited) from the innermost op enclosing it.  `ops`:
    (start, end, name, path) tuples."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    kids = {i: [] for i in order}
    scope, inherited = {}, {}
    stack = []
    for i in order:
        while stack and ops[stack[-1]][1] <= ops[i][0]:
            stack.pop()
        own = scope_of(ops[i][3])
        outer = scope[stack[-1]] if stack else UNSCOPED
        scope[i] = own or outer
        inherited[i] = own is None and outer != UNSCOPED
        if stack:
            kids[stack[-1]].append(ops[i])
        stack.append(i)
    out = []
    for i in order:
        s, e = ops[i][0], ops[i][1]
        cut = [s] + [x for iv in R._merge(kids[i], s, e) for x in iv] + [e]
        out.append((ops[i], scope[i], inherited[i],
                    [(a, b) for a, b in zip(cut[0::2], cut[1::2]) if b > a]))
    return out


def _inside(a: float, b: float, spans: list) -> float:
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e in spans)


def label_gaps(gaps: list, events: list) -> list:
    """[(label, seconds, prep)] per gap: the innermost event covering the
    gap's midpoint; prep if any covering event is a dispatch or seed span
    or a compile-path annotation.  `events`: (name, start, end, program)."""
    evs = sorted((s, e, name, program) for name, s, e, program in events
                 if program or name in COMPILE_PATH)
    out, active, k = [], [], 0
    for s, e in gaps:
        mid = (s + e) / 2
        while k < len(evs) and evs[k][0] <= mid:
            active.append(evs[k])
            k += 1
        active = [a for a in active if a[1] > mid]
        label = (min(active, key=lambda a: a[1] - a[0])[2] if active
                 else UNLABELLED)
        prep = any(a[2] in COMPILE_PATH or a[2] in PREP_SPANS
                   or a[2].endswith(PREP_SUFFIX) for a in active)
        out.append((label, (e - s) / 1e9, prep))
    return out


def reduce_events(devices: list, events: list) -> dict:
    """`devices` as device_ops gives them, `events` as host_events; split
    out so a test can feed it."""
    if not devices or not devices[0][1]:
        return {}
    ops = devices[0][1]
    win = [(s, e) for name, s, e, _ in events if name == R.WINDOW_SPAN]
    lo, hi = win[0] if win else (min(o[0] for o in ops),
                                 max(o[1] for o in ops))
    phase2 = R._merge([(s, e) for name, s, e, _ in events
                       if name == PHASE2_SPAN], lo, hi)
    self_s, inherited_s, by_op = {}, {}, {}
    for op, scope, inherited, pieces in self_intervals(ops):
        t = sum(_inside(a, b, phase2) for a, b in pieces) / 1e9
        if t > 0:
            self_s[scope] = self_s.get(scope, 0.0) + t
            if inherited:
                inherited_s[scope] = inherited_s.get(scope, 0.0) + t
            by_op[(op[2], scope)] = by_op.get((op[2], scope), 0.0) + t
    busy = R._merge(ops, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    labelled = label_gaps(gaps, events)
    idle_by_label = {}
    for label, sec, _ in labelled:
        idle_by_label[label] = idle_by_label.get(label, 0.0) + sec
    program = any(p for *_, p in events)
    return {
        "device": devices[0][0],
        "phase2_busy_s": sum(_inside(s, e, phase2) for s, e in busy) / 1e9,
        "self_s": self_s,
        "inherited_s": inherited_s,
        "top_self": [[name, scope, t] for (name, scope), t in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_s": sum(sec for _, sec, _ in labelled),
        "idle_by_label": idle_by_label,
        "prep_idle_s": (sum(sec for _, sec, prep in labelled if prep)
                        if program else None),
    }


def reduce_scopes(path: str, pd) -> dict:
    """The whole reduction of one .xplane.pb (`pd`: its ProfileData)."""
    with open(path, "rb") as f:
        raw = f.read()
    try:
        devices = device_ops(raw)
    except (ValueError, IndexError) as e:
        return {"error": f"{type(e).__name__}: {e}"}
    return reduce_events(devices, host_events(pd))


if __name__ == "__main__":
    import json
    import sys

    from jax.profiler import ProfileData

    print(json.dumps(reduce_scopes(sys.argv[1],
                                   ProfileData.from_file(sys.argv[1]))))
