"""Vectorized mailbox delivery: the array-program replacement for the
reference's per-node buffered channels (simulator.go:51-54).

The reference gives every node four mailboxes (buffered Go channels) and
delivers each message with a goroutine.  Here a whole round's messages are
three flat arrays ``(src, dst, valid)``; delivery is a sort by destination,
a per-destination rank computation, and one scatter into a fixed-capacity
``[n, cap]`` mailbox -- O(M log M) total, entirely on device, no dynamic
shapes.  Rank-overflow beyond `cap` is counted and dropped (the channel-full
backpressure case; with cap=16 and uniform destinations the probability is
negligible -- see Config.mailbox_cap_resolved).

All functions are jit-safe and shard-agnostic: for the sharded backend the
same `deliver` runs per shard after messages are routed with all_to_all
(parallel/exchange.py).
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from gossip_simulator_tpu.ops.select import first_true_indices

_warned_dense_fallback = False


def flat_addressing_fits(n: int, cap: int) -> bool:
    """True iff the [n, cap] mailbox can use flat int32 addressing (the fast
    sort + 1-D-scatter delivery paths; index n*cap is the trash cell).  The
    auto mailbox cap (Config.mailbox_cap_for) shrinks 16 -> 8 right where
    its CONSUMER's gate stops fitting -- past n ~ 1.34e8 for plain
    deliver() surfaces (single [n, cap] arrays, flat to n ~ 2.7e8 at
    cap 8), past n ~ 6.7e7 for stacked=True consumers (the ticks
    overlay's deliver_pair [2n, cap] buffer, one-pass to n ~ 1.34e8 at
    cap 8)."""
    return (n + 1) * cap < 2**31


def ring_append(rings, cnt, dropped, payloads, wslot, valid, dw: int,
                cap: int, kernel: str = "xla"):
    """Append one entry per True in `valid` into its `wslot` window slot of
    the packed ring(s): one-hot reservation ranks (emission order, no
    gathers -- dw is tiny), bounds-checked against the slot capacity, with
    overflow counted in `dropped` and overflowed writes diverted to the
    dw*cap trash cell (no reliance on flat OOB-drop scatters; see
    epidemic.deposit_local).  The same masked-cumsum rank pattern now
    also buckets the cross-shard exchange (round 6:
    parallel/exchange.route_multi ranks over the <= RANK_MAX_SHARDS
    destination columns instead of paying a stable sort per batch).

    `rings`/`payloads` are equal-length tuples -- every ring gets the same
    flat positions, so multi-array entries (e.g. the overlay's (dst, pay)
    pair) stay aligned.  A ring may carry a trailing payload axis (the
    multi-rumor (L, W) word ladder next to an (L,) id ring): its payload is
    (M, W) and the shared flat positions scatter whole rows.  Shared by
    parallel/event_sharded._ring_append and models/overlay_ticks;
    models/event.append_messages keeps its own multi-entry-per-row
    reservation variant.

    `kernel="pallas"` routes to the fused single-pass form
    (ops/pallas_deliver.fused_ring_append) -- bit-identical slot writes,
    counts, and drop totals (the -deliver-kernel gate; see the module
    docstring there for the equivalence argument)."""
    if kernel == "pallas":
        from gossip_simulator_tpu.ops import pallas_deliver
        return pallas_deliver.fused_ring_append(
            rings, cnt, dropped, payloads, wslot, valid, dw, cap)
    oh = ((wslot[:, None] == jnp.arange(dw, dtype=jnp.int32)[None, :])
          & valid[:, None]).astype(jnp.int32)
    rank = (jnp.cumsum(oh, axis=0) * oh).sum(axis=1) - 1
    base = (cnt[0][None, :] * oh).sum(axis=1)
    pos = base + rank
    ok = valid & (pos < cap)
    flat = jnp.where(ok, wslot * cap + pos, dw * cap)  # in-bounds trash cell
    rings = tuple(
        r.at[flat].set(jnp.where(ok[:, None] if p.ndim == 2 else ok, p, 0))
        for r, p in zip(rings, payloads))
    cnt = cnt + (oh * ok[:, None]).sum(axis=0)[None, :]
    dropped = dropped + (valid & ~ok).sum(dtype=jnp.int32)
    return rings, cnt, dropped


def deposit_sum(acc, dst, rows, valid, kernel: str = "xla"):
    """Sum-combine delivery for the numeric gossip family (models/pushsum):
    acc[dst[i]] += rows[i] for every True in `valid` -- the associative
    scatter-ADD sibling of the SI drain's first-touch-wins OR.  Integer adds
    commute, so arrival order (routing, chunking, shard count) never moves
    the result -- the property the pushsum S=1 == S=8 bit-identity pin rests
    on.  `acc` is (n, C) int32 fixed-point limbs; `rows` is (m, C).

    `kernel="pallas"` routes through the fused deposit
    (ops/pallas_deliver.fused_deposit_rows, the multi-rumor deposit's
    in-register combine, here with a 1-deep slot axis) -- same combine mode
    table as the OR path, gated by -deliver-kernel."""
    n = acc.shape[0]
    d = jnp.where(valid, dst, n)
    if kernel == "pallas":
        from gossip_simulator_tpu.ops import pallas_deliver
        return pallas_deliver.fused_deposit_rows(
            acc[None], jnp.zeros_like(d), d, rows)[0]
    return acc.at[d].add(jnp.where(valid[:, None], rows, 0), mode="drop")


def segment_ranks(sorted_keys: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its run of equal values (input sorted).

    One cummax pass: each element's run start is the latest index where a
    new run began.  (A searchsorted(self, self) binary search does the same
    job but costs ~25 random-access probes per element -- measured seconds
    at 18M entries on v5e.)"""
    m = sorted_keys.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.int32)
    idx = jnp.arange(m, dtype=jnp.int32)
    newseg = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]])
    segstart = jax.lax.cummax(jnp.where(newseg, idx, 0))
    return idx - segstart


def deliver(src: jnp.ndarray | None, dst: jnp.ndarray, valid: jnp.ndarray,
            n: int, cap: int, compact_chunk: int | None = None,
            src_cols: int | None = None, src_mod: int | None = None,
            kernel: str = "xla"):
    """Deliver messages into per-destination mailboxes.

    Args:
        src, dst: int32[M] message source/destination node ids (dst in [0,n)).
        valid: bool[M] mask of real messages.
        n: number of (local) nodes.
        cap: mailbox capacity per node.
        src_cols: if set, `src` may be None and sender ids are DERIVED as
            flat_index // src_cols -- for callers delivering a flattened
            (n, src_cols) emission matrix whose sender id is the row.
            The chunked path then skips both the caller's n*src_cols-wide
            broadcast materialization (4*n*src_cols bytes; 720 MB at the
            10M-node overlay) and the per-chunk gather from it.
        src_mod: like src_cols but for SLOT-major flattened (slots, n)
            matrices -- sender ids derive as flat_index % src_mod.
        compact_chunk: if set (and flat int32 addressing fits,
            (n+1)*cap < 2^31 -- past that the dense 2-D path runs and this
            is silently ignored), compact the valid messages (two-level
            first_true_indices) into <=chunk-sized batches before sorting --
            the overlay's emission lists are (n, ~18) arrays that are ~99%
            empty once membership settles, and the delivery sort otherwise
            pays for every empty slot.  Bit-identical to the single-pass
            form: chunks are ascending index ranges, so the global stable
            order is preserved, and per-node ranks continue across chunks
            via a total-arrivals counter.
        kernel: "xla" (the sort + rank + scatter chain below) or "pallas"
            (the fused single-pass kernel, ops/pallas_deliver) -- the
            -deliver-kernel gate, bit-identical mailboxes/counts/drops.
            The dense 2-D fallback (flat addressing overflow) always runs
            the XLA form.

    Returns:
        mbox: int32[n, cap] -- sender ids, -1 padded.  Slot order is arrival
            order after a stable sort, i.e. deterministic.
        count: int32[n] -- messages delivered per node (<= cap).
        dropped: int32[] -- messages beyond capacity (counted, not delivered).

    The sort carries the payload directly (one stable 2-operand lax.sort)
    instead of argsort+gather, and the mailbox scatter is flat 1-D with an
    explicit in-bounds trash cell -- 2-D index scatters are ~15x slower on
    this platform (see the NOTE in epidemic.deposit_local; the trash cell
    avoids relying on OOB-drop semantics).
    """
    m = dst.shape[0]
    if src is None and src_cols is None and src_mod is None:
        # Caught here rather than as `int // None` in the derivation below
        # (advisor r3: the non-compact path otherwise raised an opaque
        # TypeError).
        raise ValueError("deliver: src=None requires src_cols or src_mod")
    if compact_chunk is not None and compact_chunk < m:
        if flat_addressing_fits(n, cap):
            return _deliver_compact(src, dst, valid, n, cap, compact_chunk,
                                    src_cols=src_cols, src_mod=src_mod,
                                    kernel=kernel)
        # Flat int32 addressing no longer fits: the requested compaction is
        # ignored and the full-length sort + 2-D scatter path below runs
        # (~15x slower per the NOTE).  Without a signal this reads as an
        # unexplained performance cliff at n >= ~1.35e8, so say it once.
        global _warned_dense_fallback
        if not _warned_dense_fallback:
            _warned_dense_fallback = True
            warnings.warn(
                f"mailbox.deliver: (n+1)*cap = {(n + 1) * cap} >= 2^31 -- "
                "compact_chunk is ignored and overlay delivery falls back "
                "to the dense sort + 2-D scatter path (~15x slower); "
                "reduce -mailbox-cap or shard the node axis",
                stacklevel=2)
    if src is None:
        src = (jnp.arange(m, dtype=jnp.int32) % src_mod
               if src_cols is None
               else jnp.arange(m, dtype=jnp.int32) // src_cols)
    key = jnp.where(valid, dst, n).astype(jnp.int32)
    if kernel == "pallas" and flat_addressing_fits(n, cap):
        # One full-width fused chunk with an empty carry reproduces the
        # single-pass result exactly: the fused step's count is TOTAL
        # arrivals (can exceed cap), so clamp to match the ok-only count
        # below -- both equal min(arrivals, cap) per destination.
        mbox, cnt, dropped = _compact_chunk_step(
            jnp.full((n * cap + 1,), -1, dtype=jnp.int32),
            jnp.zeros((n + 1,), dtype=jnp.int32),
            jnp.zeros((), jnp.int32), key, src.astype(jnp.int32), n, cap,
            rank_major=False, kernel=kernel)
        return (mbox[:n * cap].reshape(n, cap),
                jnp.minimum(cnt[:n], cap), dropped)
    sd, ss = jax.lax.sort((key, src.astype(jnp.int32)), num_keys=1,
                          is_stable=True)
    rank = segment_ranks(sd)
    ok = (sd < n) & (rank < cap)
    if flat_addressing_fits(n, cap):
        flat = jnp.where(ok, sd * cap + rank, n * cap)  # in-bounds trash cell
        mbox = jnp.full((n * cap + 1,), -1, dtype=jnp.int32)
        mbox = mbox.at[flat].set(
            jnp.where(ok, ss, -1))[:n * cap].reshape(n, cap)
    else:
        # Flat addressing would overflow int32 (n*cap >= 2^31, e.g. the
        # overlay phase at n >= ~1.35e8 with the default cap 16): fall back
        # to the 2-D scatter -- slower, but these sizes hit it rarely.
        rows = jnp.where(ok, sd, n)
        cols = jnp.where(ok, rank, 0)
        mbox = jnp.full((n, cap), -1, dtype=jnp.int32)
        mbox = mbox.at[rows, cols].set(jnp.where(ok, ss, -1), mode="drop")
    count = jnp.zeros((n + 1,), dtype=jnp.int32).at[
        jnp.where(ok, sd, n)].add(1)[:n]
    dropped = ((sd < n) & (rank >= cap)).sum(dtype=jnp.int32)
    return mbox, count, dropped


def _deliver_prefix_keyed(src, key_full, live, nk, cap, chunk,
                          carry=None, rank_major=False, spill=None,
                          kernel="xla"):
    """Chunked delivery of a prepacked-key stream whose valid entries are a
    known-length PREFIX (`live`, an int32 scalar): chunks are plain
    ascending index ranges with NO per-chunk compaction scan --
    first_true_indices of a prefix mask IS the ascending range, so this is
    bit-identical to _deliver_compact_keyed on that mask (lanes at or past
    `live` carry the caller's nk sentinel and land in the trash cell
    either way) at zero scan cost.  The ticks overlay's drain is the
    consumer: its stable toff sort packs every live entry into a prefix of
    known length (the ring count), and the per-chunk scans were the
    dominant term of the 10M delivery sweep (ticks_delivery_chunk's 64k
    3.40 -> 2M 2.18 s/window gradient was scan amortization).  Returns
    like _deliver_compact_keyed."""
    chunks = (live + chunk - 1) // chunk

    def body(i, bcarry):
        if spill is not None:
            mbox, count, dropped, pairs, scnt = bcarry
        else:
            mbox, count, dropped = bcarry
        idx = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = src.at[idx].get(mode="fill", fill_value=-1)
        key = key_full.at[idx].get(mode="fill", fill_value=nk)
        if spill is not None:
            mbox, count, dropped, (pairs, scnt) = _compact_chunk_step(
                mbox, count, dropped, key, s, nk, cap, rank_major,
                spill=(pairs, scnt), kernel=kernel)
            return mbox, count, dropped, pairs, scnt
        return _compact_chunk_step(mbox, count, dropped, key, s, nk, cap,
                                   rank_major, kernel=kernel)

    if carry is None:
        carry = (jnp.full((nk * cap + 1,), -1, dtype=jnp.int32),
                 jnp.zeros((nk + 1,), dtype=jnp.int32),
                 jnp.zeros((), jnp.int32))
    if spill is not None:
        out = jax.lax.fori_loop(0, chunks, body, carry + spill)
        return out[0], out[1], out[2], (out[3], out[4])
    return jax.lax.fori_loop(0, chunks, body, carry)


def deliver_pair(src, dst, typ, evalid, n: int, cap: int,
                 compact_chunk: int | None = None, flat: bool = False,
                 prefix_len=None, spill_in=None, spill=None,
                 kernel: str = "xla"):
    """Deliver a two-TYPE message stream into two mailbox sets in ONE
    sorted pass: key (typ, dst) packed as typ*n + dst, shared compaction,
    one stable sort, one scatter into a stacked [2n, cap] buffer split
    afterwards.  Bit-identical mailboxes to two deliver() calls with
    valid = evalid & (typ == t): the stable sort keeps within-(typ, dst)
    arrival order, and removing the other type's entries from a stably
    ordered stream does not reorder the survivors -- at roughly half the
    per-chunk op count (ONE full-width compaction scan / sort / scatter /
    count-add where two delivers each paid their own).

    Requires flat addressing for the stacked buffer, (2n+1)*cap < 2^31;
    past that it falls back to two deliver() calls (which carry their own
    dense-fallback warning).  Returns (mbox_t0, mbox_t1, dropped).

    With `flat` (the ticks engine's memory band): never materializes the
    (n, cap) 2-D shapes, whose narrow minor dim TPU tiling pads 16-25x
    (the round-4/5 compile-OOM class) -- returns the RANK-MAJOR stacked
    buffer instead: (mbox int32[2n*cap + 1], load_t0, load_t1, dropped),
    where mailbox slot r of type t is the CONTIGUOUS range
    [r*2n + t*n, r*2n + (t+1)*n) and load_t* are the max per-node counts
    (clamped to cap).  Cell contents are identical to the 2-D form.

    `prefix_len` (int32 scalar) asserts the valid entries are a packed
    prefix of that length (the ticks drain's post-sort layout): the
    chunked path then runs plain ascending ranges with no compaction
    scans (_deliver_prefix_keyed; bit-identical to the masked form).

    `spill_in` / `spill` mirror deliver_columns' overflow spill (round 7,
    the ticks overlay's lossless-membership band): `spill_in` is a
    (2, S(+1)) (pay, packed-key) pair list re-delivered FIRST through the
    same carry (delayed messages arrive before this window's); `spill` is
    a (pairs, cnt) accumulator collecting THIS delivery's capacity
    overflow as (pay, typ*n + dst) pairs instead of dropping -- the
    return gains the final pairs array.  Requires the chunked path (the
    single-pass branch routes through it with one full-width chunk)."""
    m = src.shape[0]
    n2 = 2 * n
    if not flat_addressing_fits(2 * n + 1, cap):
        assert not flat, "flat deliver_pair requires stacked addressing"
        assert spill is None and spill_in is None, \
            "deliver_pair spill requires stacked flat addressing"
        m0, _, d0 = deliver(src, dst, evalid & (typ == 0), n, cap,
                            compact_chunk, kernel=kernel)
        m1, _, d1 = deliver(src, dst, evalid & (typ == 1), n, cap,
                            compact_chunk, kernel=kernel)
        return m0, m1, d0 + d1
    key_full = jnp.where(evalid, typ * n + dst, n2).astype(jnp.int32)
    spilling = spill is not None or spill_in is not None
    if spilling:
        # Spill needs the carry-chained chunk machinery; a chunk covering
        # the whole stream reproduces the single-pass result exactly.
        chunk = min(compact_chunk or m, m)
        carry = None
        if spill_in is not None:
            carry = (jnp.full((n2 * cap + 1,), -1, dtype=jnp.int32),
                     jnp.zeros((n2 + 1,), dtype=jnp.int32),
                     jnp.zeros((), jnp.int32))
            carry, spill = deliver_spill_pairs(carry, spill_in, n2, cap,
                                               rank_major=flat, spill=spill,
                                               kernel=kernel)
        if prefix_len is not None:
            out = _deliver_prefix_keyed(src, key_full, prefix_len, n2, cap,
                                        chunk, carry=carry, rank_major=flat,
                                        spill=spill, kernel=kernel)
        else:
            out = _deliver_compact_keyed(src, key_full, evalid, n2, cap,
                                         chunk, carry=carry,
                                         rank_major=flat, spill=spill,
                                         kernel=kernel)
        if spill is not None:
            mbox, count, dropped, spill_out = out
        else:
            mbox, count, dropped = out
            spill_out = None
        res = ((mbox,
                jnp.minimum(count[:n].max(initial=0), cap),
                jnp.minimum(count[n:n2].max(initial=0), cap), dropped)
               if flat else
               (mbox[:n * cap].reshape(n, cap),
                mbox[n * cap:n2 * cap].reshape(n, cap), dropped))
        return res + (spill_out,) if spill_out is not None else res
    if compact_chunk is not None and compact_chunk < m:
        if prefix_len is not None:
            mbox, count, dropped = _deliver_prefix_keyed(
                src, key_full, prefix_len, n2, cap, compact_chunk,
                rank_major=flat, kernel=kernel)
        else:
            mbox, count, dropped = _deliver_compact_keyed(
                src, key_full, evalid, n2, cap, compact_chunk,
                rank_major=flat, kernel=kernel)
    elif kernel == "pallas":
        # One full-width fused chunk with an empty carry == the
        # single-pass sort form (same count semantics: every lane adds,
        # sentinel included).
        mbox, count, dropped = _compact_chunk_step(
            jnp.full((n2 * cap + 1,), -1, dtype=jnp.int32),
            jnp.zeros((n2 + 1,), dtype=jnp.int32), jnp.zeros((), jnp.int32),
            key_full, src.astype(jnp.int32), n2, cap, rank_major=flat,
            kernel=kernel)
    else:
        sd, ss = jax.lax.sort((key_full, src.astype(jnp.int32)),
                              num_keys=1, is_stable=True)
        rank = segment_ranks(sd)
        ok = (sd < n2) & (rank < cap)
        if flat:
            fidx = jnp.where(ok, rank * n2 + sd, n2 * cap)
        else:
            fidx = jnp.where(ok, sd * cap + rank, n2 * cap)
        mbox = jnp.full((n2 * cap + 1,), -1, dtype=jnp.int32)
        mbox = mbox.at[fidx].set(jnp.where(ok, ss, -1))
        count = jnp.zeros((n2 + 1,), dtype=jnp.int32).at[
            jnp.where(sd < n2, sd, n2)].add(1)
        dropped = ((sd < n2) & (rank >= cap)).sum(dtype=jnp.int32)
    if flat:
        return (mbox,
                jnp.minimum(count[:n].max(initial=0), cap),
                jnp.minimum(count[n:n2].max(initial=0), cap), dropped)
    mbox = mbox[:n2 * cap]
    return (mbox[:n * cap].reshape(n, cap),
            mbox[n * cap:n2 * cap].reshape(n, cap), dropped)


def _compact_chunk_step(mbox, count, dropped, key, s, nk, cap,
                        rank_major, spill=None, kernel="xla"):
    """ONE compaction chunk's delivery: stable sort by key, rank
    continuation via the total-arrivals counter, capacity-checked flat
    scatter (trash cell at nk*cap), count/drop updates.  THE shared body
    behind _deliver_compact_keyed and make_hosted_column_delivery -- the
    split round's bit-identity with the fused delivery is structural,
    not a maintained copy.  `key` must already be nk-sentineled for
    invalid lanes; `s` is the payload (sender ids).

    `spill`, when given as `(pairs int32[2, scap + 1], cnt int32[])`,
    collects capacity-overflowed messages as (src, dst) pairs instead of
    dropping them -- the caller re-delivers them next round, reproducing
    the reference's channel-full backpressure (senders block; membership
    traffic is delayed, never lost -- simulator.go:51-54).  Only messages
    past the SPILL capacity fall through to `dropped` (counted, never
    silent).  Returns (mbox, count, dropped[, spill]).

    `kernel="pallas"` replaces the whole sort -> segment_ranks -> scatter
    chain with the fused single-pass kernel (ops/pallas_deliver.
    fused_chunk_step): every chunked delivery path in the repo funnels
    through this one body, so the -deliver-kernel gate lives HERE and the
    fused/XLA bit-identity is structural for all of them.  Mailboxes,
    counts, and drop totals are bit-identical; the only at-rest divergence
    is the spill pair buffer's internal order (arrival vs sorted -- a
    within-destination-order-preserving permutation, so re-delivery
    produces identical mailboxes; see README divergence table)."""
    if kernel == "pallas":
        from gossip_simulator_tpu.ops import pallas_deliver
        return pallas_deliver.fused_chunk_step(
            mbox, count, dropped, key, s, nk, cap, rank_major, spill=spill)
    sd, ss = jax.lax.sort((key, s.astype(jnp.int32)), num_keys=1,
                          is_stable=True)
    rank = segment_ranks(sd) + count[jnp.minimum(sd, nk)]
    ok = (sd < nk) & (rank < cap)
    if rank_major:
        flat = jnp.where(ok, rank * nk + sd, nk * cap)
    else:
        flat = jnp.where(ok, sd * cap + rank, nk * cap)
    mbox = mbox.at[flat].set(jnp.where(ok, ss, -1))
    count = count.at[jnp.where(sd < nk, sd, nk)].add(1)
    ovf = (sd < nk) & (rank >= cap)
    if spill is None:
        return mbox, count, dropped + ovf.sum(dtype=jnp.int32)
    pairs, scnt = spill
    scap = pairs.shape[1] - 1
    pos = scnt + jnp.cumsum(ovf.astype(jnp.int32)) - 1
    fit = ovf & (pos < scap)
    tgt = jnp.where(fit, pos, scap)  # trash column
    pairs = pairs.at[0, tgt].set(jnp.where(fit, ss, -1))
    pairs = pairs.at[1, tgt].set(jnp.where(fit, sd, -1))
    dropped = dropped + (ovf & ~fit).sum(dtype=jnp.int32)
    return mbox, count, dropped, (pairs, scnt + fit.sum(dtype=jnp.int32))


def _deliver_compact_keyed(src, key_full, valid, nk, cap, chunk,
                           src_cols=None, src_mod=None, carry=None,
                           rank_major=False, spill=None, kernel="xla"):
    """Chunked-compacted delivery on a prepacked key in [0, nk) with nk
    the invalid sentinel -- the ONE chunked work-horse behind
    _deliver_compact (key = dst), deliver_pair (key = typ*n + dst) and
    deliver_columns (per column, src_cols=1).  With `src_cols`, sender
    ids derive as idx // src_cols (deliver's matrix-row contract; 1
    makes the sender the lane index itself) instead of gathering `src`.
    `carry`, when given, is a previous call's (mbox, count, dropped) so
    chained calls continue per-node ranks exactly like the chunk
    continuation within one call.  Returns the flat (nk*cap + 1) mailbox
    incl. trash cell, the TOTAL-arrivals count array (nk + 1), and the
    drop count.

    `rank_major` packs cell (key, rank) at rank*nk + key instead of
    key*cap + rank: mailbox slot r is then the CONTIGUOUS range
    [r*nk, (r+1)*nk) -- consumers can dynamic_slice a whole slot without
    ever materializing an (nk, cap) 2-D array, whose narrow minor dim
    TPU tile layouts pad to 128 lanes (observed 16x: s32[1e8, 8] tiled
    T(8,128) would be a 51 GB allocation -- the round-4 100M overlay
    compile OOM).  Same cells, same values, different addressing."""
    m = valid.shape[0]
    total = valid.sum(dtype=jnp.int32)
    chunks = (total + chunk - 1) // chunk

    def body(i, bcarry):
        if spill is not None:
            mbox, count, dropped, pairs, scnt, remaining = bcarry
        else:
            mbox, count, dropped, remaining = bcarry
        idx = first_true_indices(remaining, chunk)
        hit = jnp.zeros((m,), bool).at[idx].set(True, mode="drop")
        remaining = remaining & ~hit
        v = idx < m
        if src_cols is not None:
            s = jnp.where(v, idx // src_cols, -1)
        elif src_mod is not None:
            s = jnp.where(v, idx % src_mod, -1)
        else:
            s = src.at[idx].get(mode="fill", fill_value=-1)
        key = key_full.at[idx].get(mode="fill", fill_value=nk)
        key = jnp.where(v, key, nk)
        if spill is not None:
            mbox, count, dropped, (pairs, scnt) = _compact_chunk_step(
                mbox, count, dropped, key, s, nk, cap, rank_major,
                spill=(pairs, scnt), kernel=kernel)
            return mbox, count, dropped, pairs, scnt, remaining
        mbox, count, dropped = _compact_chunk_step(
            mbox, count, dropped, key, s, nk, cap, rank_major,
            kernel=kernel)
        return mbox, count, dropped, remaining

    if carry is None:
        carry = (jnp.full((nk * cap + 1,), -1, dtype=jnp.int32),
                 jnp.zeros((nk + 1,), dtype=jnp.int32),
                 jnp.zeros((), jnp.int32))
    if spill is not None:
        out = jax.lax.fori_loop(0, chunks, body, carry + spill + (valid,))
        return out[0], out[1], out[2], (out[3], out[4])
    mbox, count, dropped, _ = jax.lax.fori_loop(
        0, chunks, body, carry + (valid,))
    return mbox, count, dropped


def deliver_spill_pairs(carry, pairs, n: int, cap: int, rank_major: bool,
                        spill=None, kernel="xla"):
    """Deliver an explicit (src, dst) pair list -- last round's
    capacity-overflow spill -- as ONE sorted chunk step, chained BEFORE
    the round's emission matrices through the same carry (delayed
    messages arrive first, a deterministic order).  `pairs` is
    int32[2, S(+1)] with -1-padded dst; an all-empty spill costs one
    S-wide sort.  Re-overflowed messages go into `spill` again (or are
    counted dropped when spill is None)."""
    mbox, count, dropped = carry
    dst = pairs[1]
    key = jnp.where(dst >= 0, dst, n).astype(jnp.int32)
    out = _compact_chunk_step(mbox, count, dropped, key, pairs[0], n, cap,
                              rank_major, spill=spill, kernel=kernel)
    if spill is None:
        return out, None
    return out[:3], out[3]


def deliver_columns(dst_mat: jnp.ndarray, n: int, cap: int, chunk: int,
                    flat: bool = False, carry=None, spill_in=None,
                    spill=None, kernel: str = "xla"):
    """Per-SLOT chunked delivery of a (slots, n) emission matrix whose
    sender id is the lane (column) index.

    The flattened form scans the full slots*n mask per compaction chunk
    (~76 ms/chunk at the 10M-node overlay's 180M lanes, 84% of the
    round); scanning per SLOT row costs n lanes per chunk instead -- the
    same entries at ~1/slots the scan width -- and the sender id is the
    lane index itself (no src gather, no broadcast).  Arrival order is
    therefore SLOT-major (slot, then node): a deterministic re-choice of
    the engine's canonical mailbox order, not a fidelity change -- the
    reference's own arrival order is goroutine-racy (simulator.go:51-54),
    so any fixed order is equally faithful; the golden trajectory pins
    the one chosen here.  Per-node ranks continue across slots and
    chunks via the total-arrivals counter, and slots with zero emissions
    cost one n-wide popcount.

    With `flat` (the large-n path), returns the RANK-MAJOR flat mailbox
    (see _deliver_compact_keyed: mailbox slot r is the contiguous range
    [r*n, (r+1)*n)) plus the max per-node load, never materializing the
    16x-padded (n, cap) tile layout: (mbox_flat int32[n*cap + 1],
    max_load int32[], dropped).  Otherwise (mbox int32[n, cap], dropped).
    Cell contents are identical either way.

    `dst_mat` may be a tuple of matrices: their slot rows chain in order
    through the same carry (the overlay's reply buffers followed by the
    bootstrap vector reshaped (1, n)).  `carry` optionally supplies the
    initial (mbox, count, dropped) -- the overlay passes allocation-
    sequenced buffers so consecutive deliveries can share memory.

    `spill_in` (int32[2, S] pairs) delivers last round's overflow spill
    FIRST through the same carry; `spill` (a (pairs, cnt) accumulator)
    collects THIS delivery's overflow instead of dropping it (see
    _compact_chunk_step) -- the return gains the final accumulator."""
    mats = dst_mat if isinstance(dst_mat, (tuple, list)) else (dst_mat,)
    return _deliver_columns_impl(mats, n, cap, chunk, flat, carry,
                                 spill_in=spill_in, spill=spill,
                                 kernel=kernel)


def _deliver_columns_impl(mats, n, cap, chunk, flat, carry, spill_in=None,
                          spill=None, kernel="xla"):
    if carry is None:
        carry = (jnp.full((n * cap + 1,), -1, dtype=jnp.int32),
                 jnp.zeros((n + 1,), dtype=jnp.int32),
                 jnp.zeros((), jnp.int32))
    if spill_in is not None:
        carry, spill = deliver_spill_pairs(carry, spill_in, n, cap,
                                           rank_major=flat, spill=spill,
                                           kernel=kernel)
    for mat in mats:
        for c in range(mat.shape[0]):
            dcol = mat[c]
            # src_cols=1: the sender id is the lane index itself; the
            # chained carry continues per-node ranks across slots exactly
            # like the chunk continuation within one call.
            out = _deliver_compact_keyed(None, dcol, dcol >= 0, n, cap,
                                         chunk, src_cols=1, carry=carry,
                                         rank_major=flat, spill=spill,
                                         kernel=kernel)
            if spill is not None:
                carry, spill = out[:3], out[3]
            else:
                carry = out
    mbox, count, dropped = carry
    if flat:
        res = (mbox, jnp.minimum(count[:n].max(initial=0), cap), dropped)
    else:
        res = (mbox[:n * cap].reshape(n, cap), dropped)
    return res + (spill,) if spill is not None else res


def make_hosted_column_delivery(n: int, cap: int, chunk,
                                per_call_chunks: int = 256,
                                spill_cap: int = 0, kernel: str = "xla"):
    """deliver_columns(flat=True) as a HOST-driven sequence of bounded
    device calls -- the memory-scale overlay's delivery (overlay.
    make_split_round_fn).  One fused delivery of a full emission row is
    minutes of chunks at n=1e8 (the bootstrap burst is ~1526 64k-chunks),
    so the chunk loop runs a bounded number of trips per jitted call with
    the carry donated across calls.  Rows with zero emissions cost one
    jitted popcount -- CHEAPER than the fused form's full scan -- or
    NOTHING when the caller already knows the row's total (run's
    `row_totals`, the round-7 dead-row skip: the overlay pieces count
    each slot's emissions at write time, so settled rounds never touch
    the ~16 dead n-wide rows at all).

    `chunk` is an int or an ascending WIDTH LADDER (round 7,
    overlay.hosted_chunk_widths): each row picks the narrowest ladder
    width that covers its live total in one chunk, falling back to the
    fattest for burst rows -- fat chunks amortize the per-chunk flat
    scatter floors that dominate dense rows (profile_overlay.py measures
    the per-width constants), narrow ones keep settled rows at the swept
    small-chunk optimum.  Chunk width never changes results (ascending
    ranges + rank continuation -- deliver's compact_chunk contract), so
    the schedule is pure perf; each width's kernels compile lazily on
    first use.  The per-call trip budget scales inversely with width
    (constant lanes per call), keeping every call inside the bound set
    at the base width (overlay_ticks.run_call_budget).

    Bit-identical to deliver_columns(..., flat=True): same chunk body,
    same ascending-index order, same rank continuation (pinned by the
    split==fused trajectory test).  Returns fn(mats) ->
    (mbox_flat int32[n*cap + 1] rank-major, max_load, dropped).

    With `spill_cap` > 0: run(mats, spill_in) first re-delivers last
    round's overflow pairs, every chunk collects overflow into a
    (2, spill_cap + 1) accumulator instead of dropping (see
    _compact_chunk_step), and the return gains the final pairs array --
    the memory-scale overlay's lossless-membership path."""
    widths = tuple(sorted({int(w) for w in
                           (chunk if isinstance(chunk, (tuple, list))
                            else (chunk,))}))
    base_chunk = widths[0]
    per_call_lanes = per_call_chunks * base_chunk
    count_valid = jax.jit(lambda d: (d >= 0).sum(dtype=jnp.int32))
    finish = jax.jit(
        lambda count: jnp.minimum(count[:n].max(initial=0), cap))
    spilling = spill_cap > 0

    def _chunk_body(mbox, count, dropped, idx, dcol, spill=None):
        v = idx < n
        s = jnp.where(v, idx, -1)  # sender = lane index (src_cols=1)
        key = dcol.at[idx].get(mode="fill", fill_value=n)
        key = jnp.where(v, key, n)
        return _compact_chunk_step(mbox, count, dropped, key, s, n, cap,
                                   rank_major=True, spill=spill,
                                   kernel=kernel)

    def _make_ksteps(chunk_w: int):
        @functools.partial(jax.jit,
                           donate_argnums=(0, 1, 2, 3, 4, 5) if spilling
                           else (0, 1, 2, 3))
        def kstep(mbox, count, dropped, *rest):
            if spilling:
                pairs, scnt, remaining, dcol, trips = rest
            else:
                remaining, dcol, trips = rest

            def body(i, carry):
                if spilling:
                    mbox, count, dropped, pairs, scnt, remaining = carry
                else:
                    mbox, count, dropped, remaining = carry
                idx = first_true_indices(remaining, chunk_w)
                hit = jnp.zeros((n,), bool).at[idx].set(True, mode="drop")
                remaining = remaining & ~hit
                if spilling:
                    mbox, count, dropped, (pairs, scnt) = _chunk_body(
                        mbox, count, dropped, idx, dcol,
                        spill=(pairs, scnt))
                    return mbox, count, dropped, pairs, scnt, remaining
                mbox, count, dropped = _chunk_body(mbox, count, dropped,
                                                   idx, dcol)
                return mbox, count, dropped, remaining

            init = ((mbox, count, dropped, pairs, scnt, remaining)
                    if spilling else (mbox, count, dropped, remaining))
            return jax.lax.fori_loop(0, trips, body, init)

        @functools.partial(jax.jit,
                           donate_argnums=(0, 1, 2, 3, 4) if spilling
                           else (0, 1, 2))
        def kstep_dense(mbox, count, dropped, *rest):
            """Fully-valid row (every lane emits -- the bootstrap burst):
            chunks are plain ascending ranges, no compaction scan at all.
            Bit-identical to kstep on an all-true mask (first_true_indices
            of all-true IS the ascending range)."""
            if spilling:
                pairs, scnt, dcol, start, trips = rest
            else:
                dcol, start, trips = rest

            def body(i, carry):
                if spilling:
                    mbox, count, dropped, pairs, scnt = carry
                else:
                    mbox, count, dropped = carry
                idx = start + i * chunk_w + jnp.arange(chunk_w,
                                                       dtype=jnp.int32)
                idx = jnp.minimum(idx, n)  # tail: clamp to the n sentinel
                if spilling:
                    mbox, count, dropped, (pairs, scnt) = _chunk_body(
                        mbox, count, dropped, idx, dcol,
                        spill=(pairs, scnt))
                    return mbox, count, dropped, pairs, scnt
                return _chunk_body(mbox, count, dropped, idx, dcol)

            init = ((mbox, count, dropped, pairs, scnt) if spilling
                    else (mbox, count, dropped))
            return jax.lax.fori_loop(0, trips, body, init)

        return kstep, kstep_dense

    ksteps: dict = {}  # width -> (kstep, kstep_dense), compiled lazily

    def _fns(chunk_w: int):
        if chunk_w not in ksteps:
            ksteps[chunk_w] = _make_ksteps(chunk_w)
        return ksteps[chunk_w]

    def _pick_width(total: int) -> int:
        """Narrowest ladder width covering `total` in ONE chunk, else the
        fattest (burst rows: fat chunks amortize the flat scatter floor)."""
        for w in widths:
            if total <= w:
                return w
        return widths[-1]

    remaining_jit = jax.jit(lambda d: d >= 0)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
    def kspill_in(mbox, count, dropped, pairs, scnt, spill_pairs):
        carry, sp = deliver_spill_pairs((mbox, count, dropped),
                                        spill_pairs, n, cap,
                                        rank_major=True,
                                        spill=(pairs, scnt), kernel=kernel)
        return carry + sp

    def run(mats, spill_in=None, row_totals=None):
        mbox = jnp.full((n * cap + 1,), -1, dtype=jnp.int32)
        count = jnp.zeros((n + 1,), dtype=jnp.int32)
        dropped = jnp.zeros((), jnp.int32)
        if spilling:
            pairs = jnp.full((2, spill_cap + 1), -1, dtype=jnp.int32)
            scnt = jnp.zeros((), jnp.int32)
            if spill_in is not None:
                mbox, count, dropped, pairs, scnt = kspill_in(
                    mbox, count, dropped, pairs, scnt, spill_in)
                jax.block_until_ready(mbox)
        ri = 0
        for mat in mats:
            for c in range(mat.shape[0]):
                dcol = mat[c]
                if row_totals is not None:
                    # Caller-supplied exact total (counted at emission
                    # time): zero rows skip without touching the array.
                    total = int(row_totals[ri])
                else:
                    total = int(jax.device_get(count_valid(dcol)))
                ri += 1
                if total == 0:
                    continue
                cw = _pick_width(total)
                kstep, kstep_dense = _fns(cw)
                chunks = -(-total // cw)
                per_call = max(1, per_call_lanes // cw)
                if total == int(dcol.shape[0]):
                    # Fully-valid row (the bootstrap burst): ascending
                    # ranges, no compaction scans.
                    done = 0
                    while done < chunks:
                        t = min(per_call, chunks - done)
                        if spilling:
                            mbox, count, dropped, pairs, scnt = kstep_dense(
                                mbox, count, dropped, pairs, scnt, dcol,
                                jnp.int32(done * cw), jnp.int32(t))
                        else:
                            mbox, count, dropped = kstep_dense(
                                mbox, count, dropped, dcol,
                                jnp.int32(done * cw), jnp.int32(t))
                        jax.block_until_ready(mbox)
                        done += t
                    continue
                remaining = remaining_jit(dcol)
                done = 0
                while done < chunks:
                    t = min(per_call, chunks - done)
                    if spilling:
                        (mbox, count, dropped, pairs, scnt,
                         remaining) = kstep(mbox, count, dropped, pairs,
                                            scnt, remaining, dcol,
                                            jnp.int32(t))
                    else:
                        mbox, count, dropped, remaining = kstep(
                            mbox, count, dropped, remaining, dcol,
                            jnp.int32(t))
                    jax.block_until_ready(mbox)
                    done += t
                del remaining
        if spilling:
            return mbox, finish(count), dropped, pairs
        return mbox, finish(count), dropped

    return run


def _deliver_compact(src, dst, valid, n, cap, chunk, src_cols=None,
                     src_mod=None, kernel="xla"):
    """Chunked-compacted deliver (see deliver's compact_chunk)."""
    key_full = jnp.where(valid, dst, n).astype(jnp.int32)
    mbox, count, dropped = _deliver_compact_keyed(
        src, key_full, valid, n, cap, chunk, src_cols=src_cols,
        src_mod=src_mod, kernel=kernel)
    return (mbox[:n * cap].reshape(n, cap),
            jnp.minimum(count[:n], cap), dropped)
