"""The ring engine's flat delay ring (models/epidemic.py).

The ring is one int32[d * n] array, slot s at [s * n, (s + 1) * n), on
one device and on each shard of the sharded backend, whose global array
is the shards' rings side by side.  A deposit must land the same counts in
the same (slot, node) cells as a 2-D [d, n] ring would, invalid lanes
must land nowhere, and validation refuses a ring whose flat index would
leave int32, per device.  Snapshots carry the logical (d, n) shape
(tests/test_checkpoint.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import epidemic

I32 = jnp.int32
D, N, M = 5, 11, 96
S = 4  # shards of the sharded cases


def _lanes(case, rng, n=N, m=M):
    """(slots, dst, valid) for one deposit case, duplicates included."""
    slots = rng.integers(0, D, m)
    dst = rng.integers(0, n, m)
    valid = rng.random(m) < 0.7
    if case == "invalid_at_n":
        # A 2-D ring's sentinel dst = n, in every slot but the last: the
        # flat index slot * n + n is the next slot's node 0.
        slots = np.arange(m) % (D - 1)
        dst = np.full(m, n)
        valid = np.zeros(m, bool)
    elif case == "invalid_mixed":
        dst = np.where(valid, dst, rng.choice([-1, n, 0], m))
    elif case == "last_cell":
        slots[: m // 2] = D - 1
        dst[: m // 2] = n - 1
        valid[: m // 2] = True
    elif case == "all_invalid":
        valid[:] = False
    elif case == "slot_boundaries":
        # Valid lanes on the first and last node of every slot, invalid
        # lanes just past each slot's end and just before its start.
        t = np.arange(m) % D
        kind = (np.arange(m) // D) % 4
        slots = t
        dst = np.choose(kind, [np.zeros(m, int), np.full(m, n - 1),
                               np.full(m, n), np.full(m, -1)])
        valid = kind < 2
    return (jnp.asarray(slots, I32), jnp.asarray(dst, I32),
            jnp.asarray(valid))


CASES = ["random", "invalid_at_n", "invalid_mixed", "last_cell",
         "all_invalid"]


def _want(ring, slots, dst, valid):
    """The 2-D answer: +1 in each valid lane's (slot, node) cell."""
    want = ring.copy()
    s, t, v = (np.asarray(a) for a in (slots, dst, valid))
    np.add.at(want, (s[v], t[v]), 1)
    return want


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("case", CASES)
def test_flat_deposit_matches_2d(case, kernel):
    rng = np.random.default_rng(11)
    ring = rng.integers(0, 3, (D, N))
    slots, dst, valid = _lanes(case, rng)
    flat = epidemic.deposit_local(jnp.asarray(ring.reshape(-1), I32), dst,
                                  slots, valid, n=N, kernel=kernel)
    assert flat.shape == (D * N,)
    np.testing.assert_array_equal(np.asarray(flat).reshape(D, N),
                                  _want(ring, slots, dst, valid))


def test_flat_row_and_clear_match_2d():
    ring = np.arange(D * N, dtype=np.int32).reshape(D, N)
    flat = jnp.asarray(ring.reshape(-1))
    for slot in range(D):
        s = jnp.asarray(slot, I32)
        np.testing.assert_array_equal(
            np.asarray(epidemic.ring_row(flat, s, N)), ring[slot])
        want = ring.copy()
        want[slot] = 0
        np.testing.assert_array_equal(
            np.asarray(epidemic.ring_clear(flat, s, N)).reshape(D, N), want)


def _mesh():
    from gossip_simulator_tpu.parallel.mesh import AXIS, node_mesh

    return node_mesh(S), AXIS


@pytest.mark.parametrize("case", CASES + ["slot_boundaries"])
def test_sharded_flat_deposit_matches_2d_per_shard(case):
    """Each shard of an S = 4 mesh deposits its own lanes into its own
    flat ring (the sharded engine's layout, spec P(nodes)); every shard's
    ring equals the 2-D answer for its lanes."""
    from gossip_simulator_tpu.parallel.mesh import shard_map

    mesh, axis = _mesh()
    rng = np.random.default_rng(5)
    rings = rng.integers(0, 3, (S, D, N))
    lanes = [_lanes(case, rng) for _ in range(S)]
    slots, dst, valid = (jnp.concatenate([ln[i] for ln in lanes])
                         for i in range(3))

    def body(pending, slots, dst, valid):
        return epidemic.deposit_local(pending, dst, slots, valid, n=N)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axis),) * 4,
                           out_specs=P(axis)))
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P(axis)))  # noqa: E731
    out = np.asarray(fn(put(jnp.asarray(rings.reshape(-1), I32)),
                        put(slots), put(dst), put(valid)))
    for sh, (sl, ds, va) in enumerate(lanes):
        np.testing.assert_array_equal(
            out.reshape(S, D, N)[sh], _want(rings[sh], sl, ds, va))


def test_routed_deposit_lands_in_the_owner_shards_ring():
    """The sharded engine's route then deposit (sharded_step.
    _deposit_routed): each valid lane, sent from any shard to a global
    destination, adds one to its owner shard's flat ring at (slot, local
    row); invalid lanes, including ones aimed at shard and slot
    boundaries, add nothing, and nothing overflows."""
    from gossip_simulator_tpu.parallel import sharded_step
    from gossip_simulator_tpu.parallel.mesh import shard_map

    mesh, axis = _mesh()
    n = S * N
    cfg = Config(n=n, backend="sharded", engine="ring", graph="kout",
                 fanout=6, delayhigh=D, delaylow=1).validate()
    rng = np.random.default_rng(9)
    slots = rng.integers(0, D, S * M)
    dst = rng.integers(0, n, S * M)
    valid = rng.random(S * M) < 0.6
    edge = np.arange(S * M) % 3 == 0
    dst = np.where(edge, rng.choice([0, N - 1, N, n - 1], S * M), dst)

    def body(pending, dst, slots, valid):
        return sharded_step._deposit_routed(cfg, N, S, pending, dst, slots,
                                            valid, cap=M)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axis),) * 4,
                           out_specs=(P(axis), P())))
    put = lambda a: jax.device_put(jnp.asarray(a), NamedSharding(mesh, P(axis)))  # noqa: E731
    pending, ovf = fn(put(np.zeros(S * D * N, np.int32)),
                      put(dst.astype(np.int32)), put(slots.astype(np.int32)),
                      put(valid))
    assert int(ovf) == 0
    want = np.zeros((D, n), np.int64)
    np.add.at(want, (slots[valid], dst[valid]), 1)
    got = np.asarray(pending).reshape(S, D, N).transpose(1, 0, 2)
    np.testing.assert_array_equal(got.reshape(D, n), want)


@pytest.mark.parametrize("n,time_mode,ok", [
    (107_374_182, "ticks", True),      # 20 slots: d * n = 2**31 - 8
    (107_374_183, "ticks", False),     # d * n = 2**31 + 12
    (100_000_000, "ticks", True),      # the north-star shape
    (1_073_741_823, "rounds", True),   # 2 slots: d * n = 2**31 - 2
    (1_073_741_824, "rounds", False),  # d * n = 2**31
])
def test_ring_index_bound_validation(n, time_mode, ok):
    kw = dict(n=n, engine="ring", graph="kout", fanout=6, delayhigh=20,
              time_mode=time_mode)
    if ok:
        Config(backend="jax", **kw).validate()
        return
    with pytest.raises(ValueError, match="-delayhigh") as err:
        Config(backend="jax", **kw).validate()
    assert f"-n {n}" in str(err.value)
    # The sharded ring's bound is per shard, and the mesh is not known
    # until the stepper is built (test_sharded_ring_index_bound_per_shard).
    Config(backend="sharded", **kw).validate()


@pytest.mark.parametrize("shards,n_local,ok", [
    (4, 107_374_182, True),    # d * n_local = 2**31 - 8 on each shard
    (4, 107_374_183, False),   # 2**31 + 12 per shard
    (4, 25_000_000, True),     # the north star, 100M over 4 chips
    (1, 107_374_183, False),   # one shard holds the whole ring
])
def test_sharded_ring_index_bound_per_shard(shards, n_local, ok):
    """The sharded backend refuses a ring whose per-shard flat index
    leaves int32 when it builds its mesh, naming the flags; building the
    stepper allocates nothing."""
    from gossip_simulator_tpu.backends.sharded import ShardedStepper

    n = shards * n_local
    cfg = Config(n=n, backend="sharded", engine="ring", graph="kout",
                 fanout=6, delayhigh=20).validate()
    if ok:
        ShardedStepper(cfg, n_devices=shards)
        return
    with pytest.raises(ValueError, match="-delayhigh") as err:
        ShardedStepper(cfg, n_devices=shards)
    assert f"-n {n}" in str(err.value)
    if shards > 1:
        assert f"{n_local} per shard" in str(err.value)
