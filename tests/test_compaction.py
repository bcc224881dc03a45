"""Wavefront compaction (epidemic.deposit_compact / sharded chunked route)
must be BIT-IDENTICAL to the dense path: drop masks and delay slots are
row-keyed (utils/rng.row_keys), so the compacted gather draws exactly the
values the dense path would for the same rows."""

import numpy as np
import pytest

from gossip_simulator_tpu.config import Config
from gossip_simulator_tpu.driver import run_simulation
from gossip_simulator_tpu.utils.metrics import ProgressPrinter


def _pair(backend, **kw):
    # engine="ring": compaction is a ring-engine feature; the auto default
    # would route these SI/ticks configs to the event engine (which ignores
    # `compact`) and make the comparison vacuous.
    base = dict(n=4000, graph="kout", fanout=6, crashrate=0.01, seed=5,
                backend=backend, engine="ring", progress=False, **kw)
    on = run_simulation(Config(**base, compact="on").validate(),
                        printer=ProgressPrinter(False))
    off = run_simulation(Config(**base, compact="off").validate(),
                         printer=ProgressPrinter(False))
    return on, off


def test_jax_compact_identical_to_dense():
    on, off = _pair("jax")
    assert on.stats == off.stats


def test_sharded_compact_identical_to_dense():
    on, off = _pair("sharded")
    assert on.stats == off.stats


def test_sir_compact_identical():
    on, off = _pair("jax", protocol="sir", removal_rate=0.5, max_rounds=3000,
                    coverage_target=0.8)
    assert on.stats == off.stats


def test_auto_resolution():
    assert Config(time_mode="ticks").compact_resolved
    assert not Config(time_mode="rounds").compact_resolved
    assert not Config(protocol="pushpull").compact_resolved
    assert Config(time_mode="rounds", compact="on").compact_resolved


def test_multi_chunk_identical_jax():
    # compact_chunk=64 forces chunks > 1 at the epidemic peak, covering the
    # remaining-mask carry across chunk boundaries.
    on, off = _pair("jax", compact_chunk=64)
    assert on.stats == off.stats


CASES = ["sparse", "clustered", "dense", "empty", "all", "tail", "head"]
GRID = [(5000, 64), (5000, 4999), (20000, 777), (8192, 256)]


def _case_mask(case, n, cap):
    rng = np.random.default_rng((CASES.index(case) + 1) * 1_000_003 + n + cap)
    mask = np.zeros(n, bool)
    if case == "sparse":
        mask[rng.choice(n, size=37, replace=False)] = True
    elif case == "clustered":
        mask[1234:1234 + 3 * cap] = True
    elif case == "dense":
        mask = rng.random(n) < 0.3
    elif case == "all":
        mask[:] = True
    elif case == "tail":
        mask[-5:] = True
    elif case == "head":
        mask[:5] = True
    return mask


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("n,cap", GRID)
def test_first_true_indices_two_level(case, n, cap):
    """The two-level block path (taken at n > 4096) must match
    jnp.nonzero(size=cap, fill_value=n) exactly -- first <=cap True indices
    ascending, padded with n.  Covers the production path bench runs at
    n=1e7, which the simulation tests (small n) never reach."""
    import jax.numpy as jnp

    from gossip_simulator_tpu.models.epidemic import first_true_indices

    mask = _case_mask(case, n, cap)
    got = np.asarray(first_true_indices(jnp.asarray(mask), cap))
    want = np.asarray(
        jnp.nonzero(jnp.asarray(mask), size=cap, fill_value=n)[0])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("n,cap", GRID + [
    (20003, 777),  # n % 32 != 0: a partial last word
    (3000, 64),    # n <= 4096, where first_true_indices takes nonzero
    (6000, 6000),  # cap >= n: one chunk takes every row
])
def test_chunk_plan_matches_nonzero(case, n, cap):
    """select.chunk_plan ranks a tick's senders once; chunk j of
    plan_chunk must be exactly the Trues of rank [j*cap, (j+1)*cap) in
    ascending order, padded with n -- the rows the per-chunk
    first_true_indices loop took (so the row-keyed draws, the deposit
    order and the sharded route's buckets are unchanged).  Walks every
    chunk a mask of n rows can need, past the last partial one: the
    sharded loop runs the mesh's largest chunk count on every shard."""
    import jax
    import jax.numpy as jnp

    from gossip_simulator_tpu.ops.select import chunk_plan, plan_chunk

    mask = _case_mask(case, n, cap)
    want = np.nonzero(mask)[0]
    plan = chunk_plan(jnp.asarray(mask), cap)
    assert int(plan.count) == want.size
    take = jax.jit(lambda j, c: plan_chunk(plan, j, c))
    cursor = jnp.zeros((), jnp.int32)
    for j in range(-(-n // cap)):
        got, cursor = take(j, cursor)
        rows = want[j * cap:(j + 1) * cap]
        np.testing.assert_array_equal(
            np.asarray(got), np.pad(rows, (0, cap - rows.size),
                                    constant_values=n), err_msg=f"chunk {j}")


def test_multi_chunk_identical_sharded():
    # n_local=500 with chunk 32: peak wave needs several chunks, each with
    # its own all_to_all (pmax-agreed trip count across shards).
    on, off = _pair("sharded", compact_chunk=32)
    assert on.stats == off.stats
    assert on.stats.exchange_overflow == 0


def test_pushpull_compact_identical():
    """Round 4: the wave-compacted push-pull round (push over infected
    rows, pull over surviving susceptible rows) must be bit-identical to
    the dense row-keyed form -- the draws are row-keyed so compaction
    samples exactly the dense path's values."""
    on, off = _pair("jax", protocol="pushpull", coverage_target=0.95)
    assert on.stats == off.stats


def test_pushpull_compact_identical_chunked():
    """Multi-chunk batches (chunk 64 at n=4000 forces many chunks at the
    peak) must carry ranks/remaining across chunk boundaries."""
    on, off = _pair("jax", protocol="pushpull", coverage_target=0.95,
                    compact_chunk=64)
    assert on.stats == off.stats
