"""Shared selection ops: compaction index extraction.

`first_true_indices` pulls the first <=cap Trues out of a mask; the
mailbox delivery compaction (ops/mailbox.py), the ticks-mode overlay and
push-pull's row compaction call it on masks they change between calls.
`chunk_plan` / `plan_chunk` serve the ring engine's sender compaction
(models/epidemic.py, parallel/sharded_step.py), whose mask stays fixed
for the whole chunk loop: the tick ranks its senders once, and chunk j
takes the senders of rank [j*cap, (j+1)*cap)."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

I32 = jnp.int32
U32 = jnp.uint32
WORD = 32  # mask bits per packed word
ROW = 128  # words per row of the two-level prefix sum (one lane row)


def first_true_indices(mask: jnp.ndarray, cap: int,
                       blk: int | None = None) -> jnp.ndarray:
    """First <=cap indices of True in `mask`, ascending, padded with n.

    Drop-in for ``jnp.nonzero(mask, size=cap, fill_value=n)[0]``, which XLA
    lowers to a full-length cumsum + scatter (~150 ms at n=1e7 on TPU v5e --
    the measured hot op of the compact tick).  Two-level version: one O(n)
    block-count pass, a nonzero over the n/blk block counts, then gather +
    in-block scan of only the first `cap` nonempty blocks.

    Yield contract (what deposit_compact's fixed chunk count relies on):
    if cap blocks are selected each holds >=1 True, and if every nonempty
    block is selected (nb <= cap) all Trues are seen -- either way the call
    yields min(cap, count) indices.

    `blk` balances the two scans: the block-count nonzero touches n/blk
    elements, the candidate gather touches min(nb, cap) * blk; blk ~
    sqrt(n/cap) equalizes them (both ~sqrt(n*cap)), clamped to [8, 256].
    """
    n = mask.shape[0]
    if n <= 4096 or cap >= n:
        return jnp.nonzero(mask, size=cap, fill_value=n)[0].astype(I32)
    if blk is None:
        blk = 8
        while blk * blk * cap < n and blk < 256:
            blk *= 2
    nb = -(-n // blk)
    pad = nb * blk - n
    m = jnp.pad(mask, (0, pad)) if pad else mask
    m = m.reshape(nb, blk)
    bc = m.sum(axis=1, dtype=I32)
    capb = min(nb, cap)
    bidx = jnp.nonzero(bc > 0, size=capb, fill_value=nb)[0].astype(I32)
    rows = m.at[bidx].get(mode="fill", fill_value=False)
    bcnt = bc.at[bidx].get(mode="fill", fill_value=0)
    off = jnp.cumsum(bcnt) - bcnt  # exclusive: output offset of each block
    local = jnp.cumsum(rows.astype(I32), axis=1) - 1
    pos = off[:, None] + local
    gidx = bidx[:, None] * blk + jnp.arange(blk, dtype=I32)[None, :]
    take = rows & (pos < cap)
    out = jnp.full((cap,), n, I32)
    return out.at[jnp.where(take, pos, cap)].set(
        jnp.where(take, gidx, n), mode="drop")


class ChunkPlan(NamedTuple):
    """A tick's senders, ranked once for its chunk loop (chunk_plan): the
    mask as uint32 words of 32 consecutive rows (`words`, one 0 word
    appended), each word's exclusive sender rank (`start`, the sender
    `count` appended) and the nonempty words in order (`nonempty`, padded
    with the index of the appended word)."""
    n: int
    cap: int
    count: jnp.ndarray
    words: jnp.ndarray
    start: jnp.ndarray
    nonempty: jnp.ndarray


def _exclusive_cumsum(x):
    """Exclusive prefix sum of int32 x (length a multiple of ROW), taken as
    a lane-row scan plus a scan of the row totals; also returns the total."""
    r = x.reshape(-1, ROW)
    tot = r.sum(axis=1, dtype=I32)
    pre = jnp.cumsum(r, axis=1, dtype=I32) - r \
        + (jnp.cumsum(tot, dtype=I32) - tot)[:, None]
    return pre.reshape(-1), tot.sum(dtype=I32)


def chunk_plan(mask: jnp.ndarray, cap: int) -> ChunkPlan:
    """Rank the Trues of `mask` once, for plan_chunk to hand out `cap` at a
    time.  Everything here is sized by n and runs once per tick; nothing
    plan_chunk does is: the mask packs into 32-row words, lane-dense, then
    a popcount per word, an exclusive rank per word (two-level scan) and
    the list of nonempty words (one scatter of n/32 lanes)."""
    n = mask.shape[0]
    nw = -(-n // (WORD * ROW)) * ROW
    # Rows of 128 lanes (the mask's own tiling, so the reshape is free);
    # each lane shifts to its bit, and each 32-lane quarter of a row sums
    # to one word.  A [nw, 32] reshape would pad every row to 128 lanes.
    lanes = jnp.pad(mask, (0, nw * WORD - n)).reshape(-1, ROW).astype(U32) \
        << (jnp.arange(ROW, dtype=U32) % WORD)
    words = jnp.stack([lanes[:, q * WORD:(q + 1) * WORD].sum(axis=1,
                                                             dtype=U32)
                       for q in range(ROW // WORD)], axis=1).reshape(-1)
    start, count = _exclusive_cumsum(
        jax.lax.population_count(words).astype(I32))
    full = words != 0
    rank, _ = _exclusive_cumsum(full.astype(I32))
    nonempty = jnp.full((nw + cap + 1,), nw, I32).at[
        jnp.where(full, rank, nw + cap + 1)].set(
            jnp.arange(nw, dtype=I32), mode="drop")
    return ChunkPlan(n, cap, count, jnp.pad(words, (0, 1)),
                     jnp.concatenate([start, count[None]]), nonempty)


def _select_bit(word, t):
    """Position of the t-th (0-based) set bit of each uint32 `word`: a
    binary descent on the popcounts of its halves."""
    pos = jnp.zeros(word.shape, U32)
    for width in (16, 8, 4, 2, 1):
        low = jax.lax.population_count(
            (word >> pos) & U32((1 << width) - 1)).astype(I32)
        up = t >= low
        t = jnp.where(up, t - low, t)
        pos = jnp.where(up, pos + U32(width), pos)
    return pos.astype(I32)


def plan_chunk(plan: ChunkPlan, j, cursor):
    """Chunk j of a chunk_plan: the Trues of rank [j*cap, (j+1)*cap),
    ascending, padded with n -- exactly what ``first_true_indices`` returns
    on the mask with the first j*cap Trues cleared.  `cursor` is the
    position in `plan.nonempty` of the word holding rank j*cap (0 for
    chunk 0); returns ``(indices, cursor for chunk j+1)``.  All work is
    sized by cap: the cap nonempty words from the cursor on hold at least
    the chunk's cap ranks, so each rank's word is found in that window
    (scatter-max of the words' first lanes, then a running max)."""
    n, cap = plan.n, plan.cap
    r0 = j * cap
    # cap + 1 words: the last is where chunk j+1 starts when each of the
    # cap before it holds a single rank.
    win = jax.lax.dynamic_slice(plan.nonempty, (cursor,), (cap + 1,))
    first = plan.start[win] - r0  # each word's first rank, chunk-relative
    w = jnp.arange(cap + 1, dtype=I32)
    later = w > 0
    owner = jax.lax.cummax(jnp.zeros((cap,), I32).at[
        jnp.where(later & (first < cap), first, cap)].max(w, mode="drop"))
    word = win[owner]
    k = jnp.arange(cap, dtype=I32)
    row = word * WORD + _select_bit(plan.words[word], k - first[owner])
    nxt = cursor + jnp.sum(later & (first <= cap), dtype=I32)
    return jnp.where(r0 + k < plan.count, row, n), nxt
