"""The scope and idle-gap reduction (scopes.py) on a synthetic trace
written as an .xplane.pb with known answers: three nested while loops
whose ops carry `tf_op` paths on their event metadata (as a v5e trace
keeps them), and host spans of the program with a compile inside one."""

import pytest

import scopes as S

NS = 1e-9

# Device line timestamp 1000 ns; offsets are absolute ns - 1000, in ps.
# Absolute: copy.7 1100-1150; while.1 1200-2000 holds while.2 1210-1900
# (fusion.4 1220-1300, while.3 1300-1600 with gather.5 1320-1400 and a
# compiler-made dynamic-update-slice.8, no path, 1450-1550) and fusion.6
# 1920-1980; copy.7 again 2200-2250.
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 100000 duration_ps: 50000 }
    events { metadata_id: 1 offset_ps: 200000 duration_ps: 800000 }
    events { metadata_id: 2 offset_ps: 210000 duration_ps: 690000 }
    events { metadata_id: 4 offset_ps: 220000 duration_ps: 80000 }
    events { metadata_id: 3 offset_ps: 300000 duration_ps: 300000 }
    events { metadata_id: 5 offset_ps: 320000 duration_ps: 80000 }
    events { metadata_id: 10 offset_ps: 450000 duration_ps: 100000 }
    events { metadata_id: 6 offset_ps: 920000 duration_ps: 60000 }
    events { metadata_id: 7 offset_ps: 1200000 duration_ps: 50000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 8 offset_ps: 200000 duration_ps: 800000 } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9
    name: "jit(run_fn_t)/while/body/poll/reduce_or:" } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]) while()"
    display_name: "while.1"
    stats { metadata_id: 1 str_value: "jit(run_fn_t)/while:" } } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = (s32[]) while()"
    display_name: "while.2"
    stats { metadata_id: 1 str_value: "jit(run_fn_t)/while/body/while:" } } }
  event_metadata { key: 3 value { id: 3 name: "%while.3 = (s32[]) while()"
    display_name: "while.3"
    stats { metadata_id: 1
      str_value: "jit(run_fn_t)/while/body/while/body/deliver/while:" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = s32[] fusion()"
    display_name: "fusion.4"
    stats { metadata_id: 1 str_value:
      "jit(run_fn_t)/while/body/while/body/combine/dynamic-update-slice:" } } }
  event_metadata { key: 5 value { id: 5 name: "%gather.5 = s32[] gather()"
    stats { metadata_id: 1 str_value:
      "jit(run_fn_t)/while/body/while/body/deliver/while/body/emit/gather:" }
  } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = pred[] fusion()"
    display_name: "fusion.6" stats { metadata_id: 1 ref_value: 9 } } }
  event_metadata { key: 7 value { id: 7 name: "%copy.7 = s32[] copy()"
    display_name: "copy.7" } }
  event_metadata { key: 8 value { id: 8 name: "jit_run_fn_t(17)" } }
  event_metadata { key: 10 value { id: 10
    name: "%dynamic-update-slice.8 = s32[] dynamic-update-slice()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 9 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1020000 duration_ps: 80000
      stats { metadata_id: 1 str_value: "device" } }
    events { metadata_id: 3 offset_ps: 1150000 duration_ps: 1350000 }
    events { metadata_id: 4 offset_ps: 1160000 duration_ps: 50000
      stats { metadata_id: 1 str_value: "device" }
      stats { metadata_id: 2 int64_value: 0 } }
    events { metadata_id: 5 offset_ps: 1170000 duration_ps: 20000 }
    events { metadata_id: 6 offset_ps: 1210000 duration_ps: 1090000
      stats { metadata_id: 1 str_value: "device" } }
    events { metadata_id: 8 offset_ps: 2500000 duration_ps: 300000 } }
  stat_metadata { key: 1 value { id: 1 name: "cat" } }
  stat_metadata { key: 2 value { id: 2 name: "call" } }
  event_metadata { key: 1 value { id: 1 name: "bench.sim" } }
  event_metadata { key: 2 value { id: 2 name: "seed" } }
  event_metadata { key: 3 value { id: 3 name: "bench.phase2" } }
  event_metadata { key: 4 value { id: 4 name: "phase2.dispatch" } }
  event_metadata { key: 5 value { id: 5 name: "backend_compile_and_load" } }
  event_metadata { key: 6 value { id: 6 name: "phase2.sync" } }
  event_metadata { key: 8 value { id: 8 name: "PjitFunction(f)" } }
  event_metadata { key: 9 value { id: 9
    name: "$driver.py:43 run_simulation" } }
}
'''


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path), ProfileData.from_file(str(path))


def test_device_ops_match_profile_data(xplane):
    """The file's own decode gives ProfileData's op intervals, and each
    op's path from its metadata (plain or interned string)."""
    path, pd = xplane
    [(pname, ops)] = S.device_ops(open(path, "rb").read())
    assert pname == "/device:TPU:0"
    ref = [(ev.start_ns, ev.end_ns) for p in pd.planes if p.name == pname
           for ln in p.lines if ln.name == "XLA Ops" for ev in ln.events]
    assert [(s, e) for s, e, *_ in ops] == ref
    by_name = {name: path for _, _, name, path in ops}
    assert by_name["gather.5"].endswith("/deliver/while/body/emit/gather:")
    assert by_name["dynamic-update-slice.8"] == ""
    assert by_name["fusion.6"] == "jit(run_fn_t)/while/body/poll/reduce_or:"
    assert by_name["copy.7"] == ""


def test_scope_self_times_and_gaps(xplane):
    out = S.reduce_scopes(*xplane)
    assert out["device"] == "/device:TPU:0"
    # Inside bench.phase2 (1150-2500): each op's interval less its nested
    # ops.  while.1 keeps 1200-1210, 1900-1920, 1980-2000; while.2 (its
    # path names no scope, nor does while.1's) 1210-1220, 1600-1900;
    # while.3 (deliver) 1300-1320, 1400-1450, 1550-1600, and the
    # dynamic-update-slice inside it reads as deliver; the second copy.7
    # 50.  The first copy.7 is outside.
    assert out["self_s"] == pytest.approx(
        {"unscoped": (50 + 310 + 50) * NS, "combine": 80 * NS,
         "emit": 80 * NS, "deliver": (120 + 100) * NS, "poll": 60 * NS},
        abs=1e-12)
    assert out["inherited_s"] == pytest.approx({"deliver": 100 * NS},
                                               abs=1e-12)
    assert out["phase2_busy_s"] == pytest.approx(850 * NS, abs=1e-12)
    assert sum(out["self_s"].values()) == pytest.approx(
        out["phase2_busy_s"], abs=1e-12)
    assert out["top_self"][0] == ["while.2", "unscoped",
                                  pytest.approx(310 * NS, abs=1e-12)]
    # Idle in bench.sim (1000-3000): 1000-1100 in `seed`, 1150-1200 in
    # the compile inside phase2.dispatch, 2000-2200 in phase2.sync,
    # 2250-3000 under no program span (PjitFunction and the Python
    # tracer's event do not count).
    assert out["idle_by_label"] == pytest.approx(
        {"seed": 100 * NS, "backend_compile_and_load": 50 * NS,
         "phase2.sync": 200 * NS, S.UNLABELLED: 750 * NS}, abs=1e-12)
    assert out["idle_s"] == pytest.approx(1100 * NS, abs=1e-12)
    assert out["prep_idle_s"] == pytest.approx(150 * NS, abs=1e-12)


def test_no_program_spans_no_prep():
    """A trace without the program's spans (an older program) reads no
    prep time, and one without device ops reads nothing."""
    ops = [(10.0, 20.0, "fusion.1", "")]
    events = [("bench.sim", 0.0, 40.0, False),
              ("backend_compile_and_load", 0.0, 10.0, False)]
    out = S.reduce_events([("/device:TPU:0", ops)], events)
    assert out["prep_idle_s"] is None
    assert out["idle_by_label"] == pytest.approx(
        {"backend_compile_and_load": 10 * NS, S.UNLABELLED: 20 * NS},
        abs=1e-12)
    assert out["self_s"] == {}  # no bench.phase2 span
    assert S.reduce_events([], events) == {}


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/emit/while/body/closed_call/deliver/add:", "deliver"),
    ("jit(f)/emit/while:", "emit"),
    ("jit(f)/poll/reduce_sum", "poll"),
    ("jit(f)/combined/add:", None),
    ("", None),
])
def test_scope_of(path, scope):
    assert S.scope_of(path) == scope
