"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed here and compiles for a topology that is
described rather than attached (on-chip-measurement guide §2).  That
catches what interpret mode cannot: Mosaic's refusals, VMEM overruns, a
step that does not fit HBM, a mesh program without its collective.

* One compile per Pallas kernel family at a real width (n >= 1M rows).
  A family the v5e compiler still refuses is xfail(strict=True) with the
  compiler's message, so the PR that repairs it must flip the mark.
* The jitted single-device SI window step at n = 10M, its
  memory_analysis() bytes under the chip's 16 GiB.
* The ring engine's phase-2 run program at 1M rows: the scatter-add
  writes the flat delay ring in place, with no copy of the ring to
  another layout and back.
* The S=4 sharded window step on a mesh over the described devices, with
  the all-to-all in its compiled text.
* The sharded ring engine's run program at 1M rows per shard on that
  mesh: each shard's flat delay ring is written in place, as on one chip.
* The sharded ring engine's seed program at 25M rows per shard, the
  north-star cell's, within the chip's HBM.
* The ring engine's compaction chunk loop, on one chip and at S=4: it
  carries no bool[n] mask and computes nothing sized by n, the tick
  having ranked its senders before the loop.

The topology is described only inside the module fixture below, never
while a module is imported (one process at a time may load libtpu), and
the compiles run in this process with the persistent cache off around
them (a described-device entry cannot be read back without a chip).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from gossip_simulator_tpu.backends.base import WINDOW_MS
from gossip_simulator_tpu.config import parse_args

I32 = jnp.int32
ROWS = 1 << 20  # real width: n >= 1M rows
HBM_BYTES = 16 * 2**30
SI_FLAGS = ["-graph", "kout", "-fanout", "6", "-crashrate", "0",
            "-coverage-target", "0.99"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any reason means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _deliver(sds):
    from gossip_simulator_tpu.ops import pallas_deliver as pd

    nk, cap, m = ROWS, 8, 65536
    return (lambda mb, c, d, k, s: pd.fused_chunk_step(
        mb, c, d, k, s, nk, cap, False, interpret=False),
        (sds((nk * cap + 1,)), sds((nk + 1,)), sds(()), sds((m,)),
         sds((m,))))


def _graph(sds):
    from gossip_simulator_tpu.ops import pallas_graph as pg

    return (lambda seed: pg.kout_pallas(ROWS, 6, 0, ROWS, seed),
            (sds(()),))


def _refused(msg):
    return pytest.mark.xfail(strict=True, reason=f"v5e compiler: {msg}")


@pytest.mark.parametrize("build", [
    pytest.param(_deliver, id="deliver",
                 marks=_refused("Cannot store scalars to VMEM")),
    pytest.param(_graph, id="graph"),
])
def test_pallas_family_compiles_for_v5e(build, one_chip, no_cache):
    fn, args = build(lambda shape: jax.ShapeDtypeStruct(
        shape, I32, sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_si_window_step_10m_fits_one_chip(one_chip, no_cache):
    from gossip_simulator_tpu.models import event, graphs
    from gossip_simulator_tpu.utils import rng as _rng

    cfg = parse_args(["-n", "10000000", "-backend", "jax", *SI_FLAGS])
    assert cfg.engine_resolved == "event"
    state = jax.eval_shape(lambda: event.init_state(
        cfg, *graphs.generate(cfg, graphs.graph_key(cfg))))
    key = jax.eval_shape(lambda: _rng.base_key(cfg.seed))
    compiled = event.make_window_fn(cfg, WINDOW_MS).lower(
        _on(state, one_chip), _on(key, one_chip)).compile()
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert 0 < peak < HBM_BYTES, peak


def _ring_sized(hlo_text, ring):
    """(op, name, count) for every while loop and dynamic-update-slice in
    the optimized HLO, with the number of ring-sized int32 arrays in its
    output (a loop's output is its whole carry)."""
    import re

    out = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = (.+?) "
                         r"(while|dynamic-update-slice)\(", hlo_text, re.M):
        dims = re.findall(r"s32\[([\d,]+)\]", m.group(2))
        count = sum(int(np.prod([int(x) for x in d.split(",")])) == ring
                    for d in dims)
        out.append((m.group(3), m.group(1), count))
    return out


def test_ring_run_scatters_into_the_ring_in_place(one_chip, no_cache):
    """A 2-D [d, n] ring made the compiler copy the whole ring to a flat
    buffer and back around the scatter-add in every compaction chunk: two
    copy loops that carry the ring twice and a second ring-sized
    dynamic-update-slice.  The flat ring leaves one ring per loop carry
    and one ring-sized dynamic-update-slice, the slot clear."""
    from gossip_simulator_tpu.models import epidemic, graphs
    from gossip_simulator_tpu.utils import rng as _rng

    cfg = parse_args(["-n", str(ROWS), "-backend", "jax", "-engine", "ring",
                      *SI_FLAGS])
    ring = epidemic.ring_depth(cfg) * ROWS
    state = jax.eval_shape(lambda: epidemic.init_state(
        cfg, *graphs.generate(cfg, graphs.graph_key(cfg))))
    key = jax.eval_shape(lambda: _rng.base_key(cfg.seed))
    count = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
    compiled = epidemic.make_run_to_coverage_fn(cfg).lower(
        _on(state, one_chip), _on(key, one_chip), count, count).compile()
    ops = _ring_sized(compiled.as_text(), ring)
    loops = [(name, c) for op, name, c in ops if op == "while" and c]
    assert loops and all(c == 1 for _, c in loops), loops
    updates = [name for op, name, c in ops
               if op == "dynamic-update-slice" and c]
    assert len(updates) == 1, updates
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * ring, temp


# Ops that only move an array the loop already holds (into another memory
# space, in slices): the compiler's choice, not work sized by the mask.
_MOVES = ("parameter", "get-tuple-element", "tuple", "bitcast",
          "copy-start", "copy-done", "slice-start", "slice-done")


def _chunk_loop(hlo_text, ring):
    """The chunk loop of a ring-engine program: the innermost while loop
    that carries the flat ring.  Returns (dtype, elements) of every array
    in its carry, and (op, name, dtype, elements) of every array its body
    computes, leaving out ring-sized arrays (the ring's in-place scatter)
    and the ops in _MOVES."""
    import re

    def arrays(shape):
        return [(dt, int(np.prod([int(x) for x in dims.split(",")])))
                for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]+)\]",
                                           shape)]

    comps = dict((m.group(1), m.group(2)) for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) .*?\{\n(.*?)^\}", hlo_text, re.M | re.S))
    inst = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][\w-]*)\(")

    def ring_loops(body):
        out = []
        for line in body.splitlines():
            m = inst.match(line)
            if m and m.group(3) == "while" and (
                    "s32", ring) in arrays(m.group(2)):
                out.append((m.group(2), re.search(r"body=%([^,\s]+)",
                                                  line).group(1)))
        return out

    loops = [lp for body in comps.values() for lp in ring_loops(body)]
    inner = [lp for lp in loops if not ring_loops(comps[lp[1]])]
    assert len(inner) == 1, inner
    carry, body = inner[0]
    made = []
    for line in comps[body].splitlines():
        m = inst.match(line)
        if not m or m.group(3) in _MOVES or "ConcatBitcast" in line:
            continue
        made += [(m.group(3), m.group(1), dt, e)
                 for dt, e in arrays(m.group(2)) if e != ring]
    return arrays(carry), made


def _assert_chunk_loop_sized_by_cap(hlo_text, ring, n):
    """Before the tick ranked its senders once, each chunk carried the
    bool[n] remaining mask, counted n/16 blocks of it and scattered a
    bool[n] hit mask to clear the chunk's rows."""
    carry, made = _chunk_loop(hlo_text, ring)
    masks = [a for a in carry if a[0] == "pred" and a[1] >= n // 16]
    assert not masks, masks
    big = [a for a in made if a[3] >= n // 16]
    assert not big, big


def test_ring_chunk_loop_is_sized_by_the_chunk(one_chip, no_cache):
    """The ring engine's run program at 1M rows (the default chunk,
    n/128): the chunk loop computes only arrays of the chunk's size."""
    from gossip_simulator_tpu.models import epidemic, graphs
    from gossip_simulator_tpu.utils import rng as _rng

    cfg = parse_args(["-n", str(ROWS), "-backend", "jax", "-engine", "ring",
                      *SI_FLAGS])
    state = jax.eval_shape(lambda: epidemic.init_state(
        cfg, *graphs.generate(cfg, graphs.graph_key(cfg))))
    key = jax.eval_shape(lambda: _rng.base_key(cfg.seed))
    count = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
    compiled = epidemic.make_run_to_coverage_fn(cfg).lower(
        _on(state, one_chip), _on(key, one_chip), count, count).compile()
    _assert_chunk_loop_sized_by_cap(
        compiled.as_text(), epidemic.ring_depth(cfg) * ROWS, ROWS)


def test_sharded_window_step_s4_has_all_to_all(topo, no_cache):
    from gossip_simulator_tpu.parallel import event_sharded
    from gossip_simulator_tpu.parallel.mesh import AXIS
    from gossip_simulator_tpu.utils import rng as _rng

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    assert mesh.shape[AXIS] == 4
    cfg = parse_args(["-n", "40000000", "-backend", "sharded",
                      "-exchange-pipeline", "double", *SI_FLAGS])
    state = jax.eval_shape(event_sharded.make_sharded_event_init(cfg, mesh))
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        state, event_sharded.event_state_specs(cfg))
    key = _on(jax.eval_shape(lambda: _rng.base_key(cfg.seed)),
              NamedSharding(mesh, PartitionSpec()))
    compiled = event_sharded.make_window_fn(cfg, mesh, WINDOW_MS).lower(
        state, key).compile()
    assert "all-to-all" in compiled.as_text()


def test_sharded_ring_run_scatters_into_the_ring_in_place(topo, no_cache):
    """The sharded ring engine's run program (the north-star cell's path,
    pipelined exchange) on the described 2x2 mesh at 1M rows per shard.
    A per-shard 2-D [d, n_local] ring made the compiler copy the ring to a
    flat buffer and back around the scatter-add in every compaction
    chunk; each shard's flat ring leaves one ring per loop carry and one
    ring-sized dynamic-update-slice, the slot clear."""
    from gossip_simulator_tpu.models import epidemic
    from gossip_simulator_tpu.parallel import sharded_step
    from gossip_simulator_tpu.parallel.mesh import AXIS
    from gossip_simulator_tpu.utils import rng as _rng

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    cfg = parse_args(["-n", str(4 * ROWS), "-backend", "sharded",
                      "-engine", "ring", "-exchange-pipeline", "double",
                      *SI_FLAGS])
    ring = epidemic.ring_depth(cfg) * ROWS
    state = jax.eval_shape(sharded_step.make_sharded_init(cfg, mesh))
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        state, sharded_step.sim_state_specs(cfg))
    rep = NamedSharding(mesh, PartitionSpec())
    key = _on(jax.eval_shape(lambda: _rng.base_key(cfg.seed)), rep)
    count = jax.ShapeDtypeStruct((), I32, sharding=rep)
    compiled = sharded_step.make_run_to_coverage_fn(cfg, mesh).lower(
        state, key, count, count).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    ops = _ring_sized(text, ring)
    loops = [(name, c) for op, name, c in ops if op == "while" and c]
    assert loops and all(c == 1 for _, c in loops), loops
    updates = [name for op, name, c in ops
               if op == "dynamic-update-slice" and c]
    assert len(updates) == 1, updates


def test_sharded_ring_chunk_loop_is_sized_by_the_chunk(topo, no_cache):
    """The sharded ring engine's run program (pipelined exchange) on the
    described 2x2 mesh at 1M rows per shard: each shard's chunk loop
    computes only arrays of the chunk's size.  The chunk is 2048 rows
    here, not the default n/128, so that the route's own per-chunk
    arrays (S x chunk x fanout lanes) stay under n/16 and every array
    sized by n stands out."""
    import dataclasses

    from gossip_simulator_tpu.models import epidemic
    from gossip_simulator_tpu.parallel import sharded_step
    from gossip_simulator_tpu.parallel.mesh import AXIS
    from gossip_simulator_tpu.utils import rng as _rng

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    cfg = dataclasses.replace(parse_args(
        ["-n", str(4 * ROWS), "-backend", "sharded", "-engine", "ring",
         "-exchange-pipeline", "double", *SI_FLAGS]), compact_chunk=2048)
    state = jax.eval_shape(sharded_step.make_sharded_init(cfg, mesh))
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        state, sharded_step.sim_state_specs(cfg))
    rep = NamedSharding(mesh, PartitionSpec())
    key = _on(jax.eval_shape(lambda: _rng.base_key(cfg.seed)), rep)
    count = jax.ShapeDtypeStruct((), I32, sharding=rep)
    compiled = sharded_step.make_run_to_coverage_fn(cfg, mesh).lower(
        state, key, count, count).compile()
    _assert_chunk_loop_sized_by_cap(
        compiled.as_text(), epidemic.ring_depth(cfg) * ROWS, ROWS)


def test_sharded_ring_seed_fits_hbm_at_the_north_star_shard(topo, no_cache):
    """The sharded ring engine's seed program at 25M rows per shard (the
    north-star cell's 100M over four chips).  The seed emits one row;
    routing a dense [n_local, k] wave for it asked the compiler for
    34 GB of temporaries, over twice a chip's HBM."""
    from gossip_simulator_tpu.parallel import sharded_step
    from gossip_simulator_tpu.parallel.mesh import AXIS
    from gossip_simulator_tpu.utils import rng as _rng

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    cfg = parse_args(["-n", "100000000", "-backend", "sharded",
                      "-engine", "ring", *SI_FLAGS])
    state = jax.eval_shape(sharded_step.make_sharded_init(cfg, mesh))
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        state, sharded_step.sim_state_specs(cfg))
    key = _on(jax.eval_shape(lambda: _rng.base_key(cfg.seed)),
              NamedSharding(mesh, PartitionSpec()))
    mem = sharded_step.make_seed_fn(cfg, mesh).lower(
        state, key).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
