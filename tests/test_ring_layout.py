"""The single-device ring engine's flat delay ring (models/epidemic.py).

The ring is one int32[d * n] array, slot s at [s * n, (s + 1) * n); the
sharded engine keeps a 2-D [d, n_local] ring per shard.  Both forms must
deposit the same counts into the same (slot, node) cells, and validation
refuses a ring whose flat index would leave int32.  Snapshots carry the
logical (d, n) shape (tests/test_checkpoint.py).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.models import epidemic

I32 = jnp.int32
D, N, M = 5, 11, 96


def _lanes(case, rng):
    """(slots, dst, valid) for one deposit case, duplicates included."""
    slots = rng.integers(0, D, M)
    dst = rng.integers(0, N, M)
    valid = rng.random(M) < 0.7
    if case == "invalid_at_n":
        # The 2-D form's sentinel dst = n, in every slot but the last: the
        # flat index slot * n + n is the next slot's node 0.
        slots = np.arange(M) % (D - 1)
        dst = np.full(M, N)
        valid = np.zeros(M, bool)
    elif case == "invalid_mixed":
        dst = np.where(valid, dst, rng.choice([-1, N, 0], M))
    elif case == "last_cell":
        slots[: M // 2] = D - 1
        dst[: M // 2] = N - 1
        valid[: M // 2] = True
    elif case == "all_invalid":
        valid[:] = False
    return (jnp.asarray(slots, I32), jnp.asarray(dst, I32),
            jnp.asarray(valid))


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("case", ["random", "invalid_at_n", "invalid_mixed",
                                  "last_cell", "all_invalid"])
def test_flat_deposit_matches_2d(case, kernel):
    rng = np.random.default_rng(11)
    ring = rng.integers(0, 3, (D, N))
    slots, dst, valid = _lanes(case, rng)
    want = ring.copy()
    s, t, v = (np.asarray(a) for a in (slots, dst, valid))
    np.add.at(want, (s[v], t[v]), 1)
    two_d = epidemic.deposit_local(jnp.asarray(ring, I32), dst, slots, valid,
                                   kernel=kernel)
    flat = epidemic.deposit_local(jnp.asarray(ring.reshape(-1), I32), dst,
                                  slots, valid, kernel=kernel, n=N)
    assert flat.shape == (D * N,)
    np.testing.assert_array_equal(np.asarray(two_d), want)
    np.testing.assert_array_equal(np.asarray(flat).reshape(D, N), want)


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
    # The sharded engine's per-shard rings stay 2-D: no such bound.
    Config(backend="sharded", **kw).validate()
