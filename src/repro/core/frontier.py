"""Frontier representations and the send-buffer builder (paper fig. 2).

Two statically-shaped frontier representations:

  * dense bitmap — (shard, S) mask; expansion scatters into a full-length
    (n+1, S) candidate mask.  TPU-native: expansion is a gather + scatter-
    max (or the blocked MXU kernel), and the exchange is a fixed-size
    collective.  Best when the frontier is a large fraction of V.

  * sparse queue — the paper's per-destination buffers (``tBuf_{ij}`` /
    ``SendBuf_j``, fig. 2 lines 8-19): a (p, cap) block of candidate global
    vertex ids bucketed by owner.  Payload scales with the frontier, not
    with n.  Best for the narrow first/last BFS levels.

The sparse queue level reads the frontier's out-edges through a per-shard
CSR view of the edge block (``csr_view``: row offsets plus the targets in
source order, built once per graph on the device).  ``compact_frontier_
edges`` gathers the frontier's edge targets into a static candidate
buffer sized by the plan's queue cutoff, so a level's passes scale with
the frontier rather than with every edge slot of the shard.

``build_queue_buckets`` takes that compacted candidate buffer and
implements the paper's §5.1 optimization (1): with ``local_update=True``,
candidates owned by the computing shard are applied straight to the local
bitmap and *excluded* from the send buffers ("added conditional check to
see if current processor is owner ... resulted into relatively lower
buffer size").

The 2-D edge partition reuses both representations per phase:
``pack_frontier_ids``/``unpack_row_frontier`` make the expand-phase row
allgather sparse (ship active ids, not the bitmap), and
``build_queue_buckets_2d`` buckets fold-layout candidates by column-owner
row rank — the §5.1 local-update exclusion and dense-escalation-on-
overflow contracts carry over unchanged.

``pack_bits``/``unpack_bits`` are the *packed-bitset* wire format of the
dense phases (Lv et al.'s "Compression and Sieve", Buluç & Madduri's
word-packed frontiers): 32 mask bytes collapse into one ``uint32`` word,
so every dense collective ships 8× fewer bytes and merges with bitwise
OR instead of a byte-wise max.  Packing is *blocked* — each owner's
segment packs into its own ``ceil(m/32)`` words — so block boundaries
stay word-aligned for any shard size and the per-shard slices of the
collectives (all-to-all splits, allgather offsets) remain static.  The
pad bits of a block's last word are zero by construction and OR-merges
preserve zeros, so padding can never leak a phantom candidate across the
merge (regression-pinned in tests/test_wire_format.py).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core.partition import Partition1D

INF = jnp.int32(2 ** 30)  # unreached sentinel (shared with bfs/engine/ref)


def init_dist_frontier(sources: jnp.ndarray, n: int, n_logical: int):
    """Device-side source injection: scatter an ``(S,)`` int32 id vector
    into fresh ``(n, S)`` distance / frontier-bitmap arrays.

    Slots with ``sources[j] < 0`` (or >= n_logical) are *empty* — their
    column stays all-INF / all-zero and terminates immediately.  Because
    the scatter runs under jit from a traced operand, a compiled BFS
    engine accepts arbitrary new source sets with zero retraces and no
    host-side (n, S) materialization.
    """
    s = sources.shape[0]
    cols = jnp.arange(s)
    ok = (sources >= 0) & (sources < n_logical)
    idx = jnp.clip(sources, 0, n - 1)
    # min/max scatters are no-ops for masked-off slots even when their
    # clipped indices collide with a live source's row.
    dist0 = jnp.full((n, s), INF, jnp.int32).at[idx, cols].min(
        jnp.where(ok, jnp.int32(0), INF))
    frontier0 = jnp.zeros((n, s), jnp.uint8).at[idx, cols].max(
        ok.astype(jnp.uint8))
    return dist0, frontier0


def expand_dense(frontier: jnp.ndarray, src_local: jnp.ndarray,
                 dst_global: jnp.ndarray, n: int) -> jnp.ndarray:
    """Top-down edge expansion into a full-length candidate mask.

    frontier: (shard, S) uint8.  src_local/dst_global: (E,) int32 padded
    COO (dst -1 = padding).  Returns (n, S) uint8 candidates.
    """
    valid = dst_global >= 0
    fvals = frontier[src_local] * valid[:, None].astype(frontier.dtype)  # (E, S)
    idx = jnp.where(valid, dst_global, n)
    cand = jnp.zeros((n + 1, frontier.shape[1]), dtype=frontier.dtype)
    cand = cand.at[idx].max(fvals)
    return cand[:n]


def expand_dense_2d(frontier_row: jnp.ndarray, src_rowlocal: jnp.ndarray,
                    dst_fold: jnp.ndarray, fold_len: int) -> jnp.ndarray:
    """2-D edge expansion into the *transposed* fold-phase layout.

    frontier_row: (c*b, S) uint8 — this grid row's frontier segment (the
    expand-phase allgather output).  src_rowlocal/dst_fold: (E,) int32
    padded COO local to this device's adjacency block; ``dst_fold`` indexes
    candidates as ``row_rank(owner(dst)) * b + local_id(dst)`` (-1 =
    padding) so the column all-to-all of the fold phase delivers each
    length-``b`` slice straight to its owner.  Returns (fold_len, S) uint8
    with ``fold_len = r*b``.
    """
    valid = dst_fold >= 0
    fvals = frontier_row[src_rowlocal] * valid[:, None].astype(
        frontier_row.dtype)                                        # (E, S)
    idx = jnp.where(valid, dst_fold, fold_len)
    cand = jnp.zeros((fold_len + 1, frontier_row.shape[1]),
                     dtype=frontier_row.dtype)
    cand = cand.at[idx].max(fvals)
    return cand[:fold_len]


# ---------------------------------------------------------------------------
# Packed-bitset wire format (dense phases)
# ---------------------------------------------------------------------------

def packed_words(n_bits: int) -> int:
    """Words needed to hold ``n_bits`` mask bits (ceil(n_bits / 32))."""
    return -(-n_bits // 32)


def pack_bits(mask: jnp.ndarray, n_blocks: int = 1) -> jnp.ndarray:
    """Pack a ``(n_blocks * m, S)`` 0/1 mask into ``(n_blocks * W, S)``
    uint32 words, ``W = ceil(m / 32)``.

    Each length-``m`` block packs independently (bit ``i`` of word
    ``b*W + i//32`` is row ``b*m + i``), so block boundaries are always
    word-aligned regardless of ``m % 32`` — the per-owner slices of a
    packed collective stay static.  A block's trailing pad bits are zero.
    """
    total, s = mask.shape
    m = total // n_blocks
    assert m * n_blocks == total, (total, n_blocks)
    w = packed_words(m)
    x = (mask > 0).astype(jnp.uint32).reshape(n_blocks, m, s)
    if w * 32 != m:
        x = jnp.pad(x, ((0, 0), (0, w * 32 - m), (0, 0)))
    x = x.reshape(n_blocks, w, 32, s)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = (x << shifts[None, None, :, None]).sum(axis=2, dtype=jnp.uint32)
    return words.reshape(n_blocks * w, s)


def unpack_bits(words: jnp.ndarray, m: int, n_blocks: int = 1) -> jnp.ndarray:
    """Inverse of ``pack_bits``: ``(n_blocks * W, S)`` uint32 words back to
    a ``(n_blocks * m, S)`` uint8 0/1 mask.  Each block's trailing pad
    bits (rows ``m .. W*32``) are dropped, never surfaced as vertices.
    """
    total_w, s = words.shape
    w = total_w // n_blocks
    assert w * n_blocks == total_w, (total_w, n_blocks)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words.reshape(n_blocks, w, 1, s) >> shifts[None, None, :, None]
            ) & jnp.uint32(1)
    bits = bits.reshape(n_blocks, w * 32, s)[:, :m, :]
    return bits.reshape(n_blocks * m, s).astype(jnp.uint8)


def expand_dense_2d_packed(frontier_words: jnp.ndarray,
                           src_rowlocal: jnp.ndarray,
                           dst_fold: jnp.ndarray, fold_len: int,
                           m: int) -> jnp.ndarray:
    """2-D top-down expansion straight from the *packed* row frontier.

    ``frontier_words`` is the expand-phase allgather output kept packed:
    ``(c * W, S)`` uint32, block ``k`` = row peer ``k``'s ``pack_bits``
    output over its ``m``-vertex chunk (``W = packed_words(m)``).  Each
    edge gathers one word and extracts its source's bit, so the
    ``(c*b, S)`` row-frontier byte mask is never materialized between the
    collective and the edge scatter — the fused-tail twin of
    ``expand_bottom_up_packed`` for the expand phase.  Output matches
    ``expand_dense_2d(unpack_bits(frontier_words, m, c), ...)`` bitwise.
    """
    valid = dst_fold >= 0
    src = jnp.where(valid, src_rowlocal, 0)
    blk = src // m
    loc = src - blk * m
    widx = blk * packed_words(m) + loc // 32
    wvals = frontier_words[widx]                               # (E, S)
    bit = (loc % 32).astype(jnp.uint32)
    vals = ((wvals >> bit[:, None]) & jnp.uint32(1)).astype(jnp.uint8)
    vals = vals * valid[:, None].astype(jnp.uint8)
    idx = jnp.where(valid, dst_fold, fold_len)
    cand = jnp.zeros((fold_len + 1, frontier_words.shape[1]),
                     jnp.uint8).at[idx].max(vals)
    return cand[:fold_len]


def expand_bottom_up_packed(frontier_words: jnp.ndarray,
                            in_src_global: jnp.ndarray,
                            in_dst_local: jnp.ndarray, shard: int,
                            words_per_block: int) -> jnp.ndarray:
    """Bottom-up expansion straight from the *packed* replicated frontier.

    ``frontier_words`` is the allgather of every shard's packed frontier
    (``(p * W, S)`` uint32, block ``k`` = shard ``k``'s ``pack_bits``
    output).  Each in-edge gathers one word and extracts its source's bit
    — the ``(n, S)`` byte mask is never materialized, so the 8× wire
    saving of the packed gather is not given back to an unpack.  Same
    both-endpoints masking contract as ``expand_bottom_up``.
    """
    valid = ((in_src_global >= 0)
             & (in_dst_local >= 0) & (in_dst_local < shard))
    src = jnp.where(valid, in_src_global, 0)
    blk = src // shard
    loc = src - blk * shard
    widx = blk * words_per_block + loc // 32
    wvals = frontier_words[widx]                               # (E, S)
    bit = (loc % 32).astype(jnp.uint32)
    vals = ((wvals >> bit[:, None]) & jnp.uint32(1)).astype(jnp.uint8)
    vals = vals * valid[:, None].astype(jnp.uint8)
    idx = jnp.where(valid, in_dst_local, shard)
    cand = jnp.zeros((shard + 1, frontier_words.shape[1]),
                     jnp.uint8).at[idx].max(vals)
    return cand[:shard]


def expand_bottom_up(frontier_global: jnp.ndarray, in_src_global: jnp.ndarray,
                     in_dst_local: jnp.ndarray, shard: int) -> jnp.ndarray:
    """Bottom-up: each local vertex checks whether any in-neighbor is in
    the (replicated) frontier.  Returns (shard, S) uint8 candidates.

    An in-edge is live only when *both* endpoints are in range: a padded
    slot whose destination is the ``-1`` sentinel but whose source field
    happens to hold a valid id would otherwise wrap (``.at[-1]``) and
    scatter into the shard's last row — regression-pinned in
    tests/test_core_bfs.py.
    """
    valid = ((in_src_global >= 0)
             & (in_dst_local >= 0) & (in_dst_local < shard))
    src = jnp.where(valid, in_src_global, 0)
    vals = frontier_global[src] * valid[:, None].astype(frontier_global.dtype)
    idx = jnp.where(valid, in_dst_local, shard)
    cand = jnp.zeros((shard + 1, frontier_global.shape[1]),
                     dtype=frontier_global.dtype)
    cand = cand.at[idx].max(vals)
    return cand[:shard]


def csr_view(src_local: jnp.ndarray, dst_global: jnp.ndarray, shard: int):
    """CSR view of one shard's padded out-edge block.

    src_local/dst_global: (E,) int32 padded COO (dst -1 = padding).
    Returns ``(row_ptr (shard + 1,) int32, csr_dst (E,) int32)``: the
    targets of local vertex ``v`` are ``csr_dst[row_ptr[v]:row_ptr[v+1]]``
    in their block order (a stable sort), and the padding slots come last
    (``row_ptr[shard]`` is the shard's real edge count).
    """
    key = jnp.where(dst_global >= 0, src_local, shard)
    order = jnp.argsort(key, stable=True)
    row_ptr = jnp.searchsorted(key[order],
                               jnp.arange(shard + 1, dtype=key.dtype))
    return row_ptr.astype(jnp.int32), dst_global[order]


def frontier_out_degrees(frontier_col: jnp.ndarray,
                         row_ptr: jnp.ndarray) -> jnp.ndarray:
    """(shard,) int32 out-degree of each frontier vertex, 0 off the
    frontier; its sum is the shard's frontier edge count."""
    return (frontier_col > 0).astype(jnp.int32) * (row_ptr[1:] - row_ptr[:-1])


def compact_frontier_edges(fdeg: jnp.ndarray, row_ptr: jnp.ndarray,
                           csr_dst: jnp.ndarray, slots: int):
    """The frontier's out-edge targets, packed into ``slots`` leading
    slots: ``(targets (slots,) int32, active (slots,) bool)``, -1 and
    False past the frontier's ``fdeg.sum()`` edges, which must not exceed
    ``slots``.

    Frontier vertex ``v`` fills the slots from ``off[v]`` (the exclusive
    prefix sum of ``fdeg``), and slot ``j`` of it reads edge
    ``row_ptr[v] + j - off[v]``.  ``row_ptr[v] - off[v]`` counts the
    edges of the non-frontier vertices before ``v``, so it never
    decreases with ``v``: scattering it to each ``off[v]`` and taking a
    running max hands every slot its vertex's value with one scatter and
    one scan, no per-slot search.
    """
    end = _blocked_scan(fdeg, jnp.cumsum, jnp.add)
    off = end - fdeg
    pos = jnp.where(fdeg > 0, off, slots)
    base = jnp.zeros((slots + 1,), jnp.int32).at[pos].max(
        row_ptr[:-1] - off)[:slots]
    j = jnp.arange(slots, dtype=jnp.int32)
    active = j < end[-1]
    edge = jnp.where(active, _blocked_scan(base, lax.cummax, jnp.maximum) + j,
                     0)
    return jnp.where(active, csr_dst[edge], -1), active


def _blocked_scan(x: jnp.ndarray, scan, combine, block: int = 1024):
    """Inclusive ``scan`` (``jnp.cumsum`` / ``lax.cummax`` and their
    ``combine``) of a 1-D array of non-negative ints, run as rows of
    ``block`` plus one scan over the row totals.  Same result as
    ``scan(x)``; for a TPU v5e target XLA compiles these short scans in
    about a second, one scan a million elements long in tens."""
    n = x.shape[0]
    rows = -(-n // block)
    y = scan(jnp.pad(x, (0, rows * block - n)).reshape(rows, block), axis=1)
    carry = scan(y[:, -1])
    carry = jnp.concatenate([jnp.zeros((1,), x.dtype), carry[:-1]])
    return combine(y, carry[:, None]).reshape(-1)[:n]


def _dedupe_owner(ids: jnp.ndarray, active: jnp.ndarray, owner: jnp.ndarray,
                  sentinel: int, n_owners: int) -> jnp.ndarray:
    """Mask ``owner`` to ``n_owners`` for every duplicate active id.

    Drop duplicate targets before they hit the wire: sort by target, keep
    first occurrence.  (Beyond-paper: the paper ships dupes and dedupes at
    the owner via the d[u]=inf check.)  ``sentinel`` must be the *padded*
    id-space size: every storable id is strictly below it, so it can never
    collide with a padding id at the last shard boundary — the old
    ``padded_size + 1`` sentinel also sat outside the id range but
    overflows int32 when the padded size is itself ``INT32_MAX``
    (regression-pinned in tests/test_partition_and_registry.py).
    """
    e = ids.shape[0]
    tgt = jnp.where(active, ids, jnp.int32(sentinel))
    order = jnp.argsort(tgt)
    sorted_tgt = tgt[order]
    first = jnp.concatenate([jnp.array([True]),
                             sorted_tgt[1:] != sorted_tgt[:-1]])
    keep = jnp.zeros((e,), bool).at[order].set(first)
    return jnp.where(keep, owner, n_owners)


def _pack_buckets(ids: jnp.ndarray, owner: jnp.ndarray, n_owners: int,
                  cap: int):
    """Stable bucket packing: sort ids by owner, rank within bucket.

    ``owner[k] == n_owners`` marks id ``k`` unsendable (inactive, deduped
    or locally applied).  Returns ((n_owners, cap) int32 buckets -1 padded,
    () int32 sent count, () bool overflow).
    """
    e = ids.shape[0]
    sort_idx = jnp.argsort(owner)                      # (E,)
    owner_s = owner[sort_idx]
    ids_s = ids[sort_idx]
    starts = jnp.searchsorted(owner_s, jnp.arange(n_owners + 1))
    rank = jnp.arange(e) - starts[jnp.clip(owner_s, 0, n_owners)]
    sendable = owner_s < n_owners
    in_cap = sendable & (rank < cap)
    slot = jnp.where(in_cap, owner_s * cap + rank, n_owners * cap)
    buf = jnp.full((n_owners * cap + 1,), -1, jnp.int32).at[slot].set(
        jnp.where(in_cap, ids_s, -1).astype(jnp.int32))
    buckets = buf[: n_owners * cap].reshape(n_owners, cap)
    n_sent = in_cap.sum().astype(jnp.int32)
    overflow = (sendable & (rank >= cap)).any()
    return buckets, n_sent, overflow


def build_queue_buckets(dst_global: jnp.ndarray, active: jnp.ndarray,
                        part: Partition1D, me: jnp.ndarray, cap: int,
                        local_update: bool = True, dedupe: bool = True):
    """Pack active candidate targets into per-owner send buffers.

    dst_global: (C,) int32 global candidate ids, the compacted frontier
    edge buffer of ``compact_frontier_edges``; active: (C,) bool (a live
    candidate slot).  Returns:
      buckets:   (p, cap) int32 global ids, -1 padded — ``SendBuf_j``.
      local_mask:(shard,) uint8 — candidates applied locally (opt 5.1-1);
                 all-zero when ``local_update=False`` (they go in buckets).
      n_sent:    () int32 — total ids placed in send buffers (for stats).
      overflow:  () bool — some bucket exceeded cap (caller escalates to
                 the dense representation).
    """
    p, shard = part.p, part.shard_size
    if local_update and p == 1:
        # the one shard owns every candidate: all apply locally, where
        # duplicates merge in the scatter-max, and no bucket is filled
        lid = jnp.where(active, dst_global, shard)
        local_mask = jnp.zeros((shard + 1,), jnp.uint8).at[lid].max(
            active.astype(jnp.uint8))[:shard]
        return (jnp.full((1, cap), -1, jnp.int32), local_mask, jnp.int32(0),
                jnp.bool_(False))
    owner = jnp.where(active, dst_global // shard, p)

    if dedupe:
        owner = _dedupe_owner(dst_global, active, owner, part.n, p)

    local_mask = jnp.zeros((shard,), jnp.uint8)
    if local_update:
        mine = owner == me
        lid = jnp.where(mine, dst_global - me * shard, shard)
        local_mask = jnp.zeros((shard + 1,), jnp.uint8).at[lid].max(
            mine.astype(jnp.uint8))[:shard]
        owner = jnp.where(mine, p, owner)

    buckets, n_sent, overflow = _pack_buckets(dst_global, owner, p, cap)
    return buckets, local_mask, n_sent, overflow


def build_queue_buckets_2d(dst_fold: jnp.ndarray, active: jnp.ndarray,
                           part2, me_row: jnp.ndarray, cap: int,
                           local_update: bool = True, dedupe: bool = True):
    """2-D analog of ``build_queue_buckets`` in the fold layout.

    Buckets active candidate targets by *column-owner row rank*
    (``dst_fold // b``): bucket ``rr`` travels down this device's grid
    column to the device at row rank ``rr``, which owns exactly the fold
    slice ``[rr*b, (rr+1)*b)``.  The §5.1 local-update exclusion applies
    with the device's own row rank (targets this device owns skip the
    wire); the dedupe sentinel is the padded fold-layout size ``r*b``
    (strictly above every storable fold index).  Returns
    (buckets (r, cap) int32 fold ids -1 padded, local_mask (b,) uint8,
    n_sent () int32, overflow () bool).
    """
    r, b = part2.r, part2.shard_size
    owner = jnp.where(active, dst_fold // b, r)

    if dedupe:
        owner = _dedupe_owner(dst_fold, active, owner, part2.fold_size, r)

    local_mask = jnp.zeros((b,), jnp.uint8)
    if local_update:
        mine = owner == me_row
        lid = jnp.where(mine, dst_fold - me_row * b, b)
        local_mask = jnp.zeros((b + 1,), jnp.uint8).at[lid].max(
            mine.astype(jnp.uint8))[:b]
        owner = jnp.where(mine, r, owner)

    buckets, n_sent, overflow = _pack_buckets(dst_fold, owner, r, cap)
    return buckets, local_mask, n_sent, overflow


def pack_frontier_ids(frontier: jnp.ndarray, cap: int):
    """Pack the active local frontier (single-source column) into a
    fixed-capacity id buffer for the sparse expand phase.

    frontier: (shard, 1) uint8.  Returns (ids (cap,) int32 local ids -1
    padded, count () int32, overflow () bool — more active vertices than
    ``cap``; the caller escalates the level to the dense representation).
    """
    shard = frontier.shape[0]
    act = frontier[:, 0] > 0
    lid = jnp.where(act, jnp.arange(shard), shard)
    if cap > shard:
        lid = jnp.concatenate(
            [lid, jnp.full((cap - shard,), shard, lid.dtype)])
    packed = jnp.sort(lid)[:cap]                 # active ids sort first
    ids = jnp.where(packed < shard, packed, -1).astype(jnp.int32)
    count = act.sum(dtype=jnp.int32)
    overflow = count > cap
    return ids, count, overflow


def unpack_row_frontier(all_ids: jnp.ndarray, c: int,
                        shard: int) -> jnp.ndarray:
    """Rebuild a grid row's frontier bitmap from c gathered id buffers.

    all_ids: (c*cap,) int32 — the row allgather of every row peer's
    ``pack_frontier_ids`` buffer, segment ``j`` holding local ids of the
    chunk at grid column ``j``.  Returns (c*shard, 1) uint8 — the same
    row-block layout ``expand_dense_2d`` consumes.
    """
    cap = all_ids.shape[0] // c
    seg = jnp.repeat(jnp.arange(c), cap)
    ok = (all_ids >= 0) & (all_ids < shard)
    pos = jnp.where(ok, all_ids + seg * shard, c * shard)
    frow = jnp.zeros((c * shard + 1,), jnp.uint8).at[pos].max(
        ok.astype(jnp.uint8))
    return frow[: c * shard][:, None]


def apply_queue(recv: jnp.ndarray, me: jnp.ndarray, shard: int) -> jnp.ndarray:
    """Scatter received global ids into this shard's candidate bitmap."""
    flat = recv.reshape(-1)
    lid = flat - me * shard
    valid = (flat >= 0) & (lid >= 0) & (lid < shard)  # drop pads/foreign ids
    lid = jnp.where(valid, lid, shard)
    mask = jnp.zeros((shard + 1,), jnp.uint8).at[lid].max(
        valid.astype(jnp.uint8))
    return mask[:shard]


def frontier_nonzero(frontier: jnp.ndarray) -> jnp.ndarray:
    return frontier.max() > 0


# ---------------------------------------------------------------------------
# Compressed sparse-id wire format (delta + varint, bitmap-adaptive)
# ---------------------------------------------------------------------------
# Sparse phases ship vertex *ids*; sorted ids delta-encode to small gaps
# and gaps varint-encode to ~1 byte each on typical frontiers ("Compression
# and Sieve", Lv et al.) — 4x fewer bytes than raw int32 before the ids
# even thin out.  Buffers stay statically shaped: a fixed byte capacity
# priced by ``compressed_capacity``, an overflow flag escalating to dense
# (the same predicate contract as the id-capacity overflow), and a
# bitmap-mode rescue when the whole id range packs smaller than the ids.

def varint_len(value: int) -> int:
    """Host-side: bytes a base-128 varint needs for ``value`` (>= 0)."""
    v = int(value)
    return (1 + (v >= 1 << 7) + (v >= 1 << 14) + (v >= 1 << 21)
            + (v >= 1 << 28))


def compressed_capacity(cap: int, id_range: int) -> int:
    """Static byte size of one compressed buffer for ``cap`` ids drawn
    from ``[0, id_range)``.

    The varint stream is sized for deltas averaging *twice* the uniform
    spacing (``2 * id_range / cap`` — headroom for clustering) plus a
    4-byte header and slack; burstier levels raise the overflow flag
    and escalate to dense.  When the packed bitset of the whole range
    is smaller than that, the buffer shrinks to bitset size instead —
    ids *lose* to the bitmap at high density, and a bitmap-capacity
    buffer can always represent any id set, so that regime is
    overflow-free.  Byte models price exactly this number, keeping
    modeled and shipped bytes equal by construction.
    """
    avg2 = max(1, (2 * max(1, id_range)) // max(1, cap))
    varint_cap = cap * varint_len(avg2) + 8
    bitmap_cap = 4 + 4 * packed_words(max(1, id_range))
    return min(varint_cap, bitmap_cap)


def _le_bytes(word: jnp.ndarray) -> jnp.ndarray:
    """() uint32 -> (4,) uint8 little-endian."""
    shifts = jnp.uint32(8) * jnp.arange(4, dtype=jnp.uint32)
    return ((word >> shifts) & jnp.uint32(0xFF)).astype(jnp.uint8)


def encode_delta_varint(ids: jnp.ndarray, byte_cap: int, id_range: int):
    """Encode a -1-padded id buffer into a fixed-size compressed payload.

    ids: (cap,) int32, valid entries in ``[0, id_range)``, -1 = padding,
    any order (bucket packing is owner-stable, not id-sorted — the ids
    are sorted here).  Returns ``(buf (byte_cap,) uint8, overflow ()
    bool)``.

    Layout: a 4-byte little-endian header word (bits 0-30 = id count,
    bit 31 = bitmap mode), then either the sorted ids' delta stream as
    LSB-first base-128 varints (high bit = continuation, <= 5 bytes per
    delta for ids < 2^30) or, in bitmap mode, the range's packed bitset
    words serialized LE.  Bitmap mode engages when it statically fits
    ``byte_cap`` and the varint stream runs longer; ``overflow`` is
    True only when the varints spill *and* no bitmap slot exists.
    """
    cap = ids.shape[0]
    valid = (ids >= 0) & (ids < id_range)
    count = valid.sum(dtype=jnp.int32)
    key = jnp.where(valid, ids, jnp.int32(id_range))
    srt = jnp.sort(key)
    k = jnp.arange(cap)
    live = k < count
    prev = jnp.where(k > 0, srt[jnp.maximum(k - 1, 0)], 0)
    delta = jnp.where(live, srt - prev, 0).astype(jnp.uint32)

    nlen = (jnp.int32(1)
            + (delta >= jnp.uint32(1 << 7)).astype(jnp.int32)
            + (delta >= jnp.uint32(1 << 14)).astype(jnp.int32)
            + (delta >= jnp.uint32(1 << 21)).astype(jnp.int32)
            + (delta >= jnp.uint32(1 << 28)).astype(jnp.int32))
    nlen = jnp.where(live, nlen, 0)
    off = jnp.cumsum(nlen) - nlen                      # exclusive
    total = 4 + nlen.sum()
    varint_ovf = total > byte_cap

    # slot k's group j (j < nlen[k]) lands at byte 4 + off[k] + j; spilled
    # or dead bytes divert to the dump slot at index byte_cap
    j = jnp.arange(5)
    emit = j[None, :] < nlen[:, None]                               # (cap, 5)
    grp = ((delta[:, None] >> (jnp.uint32(7) * j[None, :].astype(jnp.uint32)))
           & jnp.uint32(0x7F))
    cont = j[None, :] < (nlen - 1)[:, None]
    payload_bytes = jnp.where(cont, grp | jnp.uint32(0x80), grp)
    payload_bytes = jnp.where(emit, payload_bytes, 0).astype(jnp.uint8)
    pos = 4 + off[:, None] + j[None, :]
    pos = jnp.where(emit & (pos < byte_cap), pos, byte_cap)
    buf = jnp.zeros((byte_cap + 1,), jnp.uint8).at[pos.reshape(-1)].max(
        payload_bytes.reshape(-1))[:byte_cap]

    hdr = count.astype(jnp.uint32)
    w = packed_words(id_range)
    if 4 + 4 * w <= byte_cap:                # bitmap rescue statically fits
        mask = jnp.zeros((id_range + 1,), jnp.uint8).at[key].max(
            valid.astype(jnp.uint8))[:id_range]
        words = pack_bits(mask[:, None])[:, 0]                     # (w,)
        shifts = jnp.uint32(8) * jnp.arange(4, dtype=jnp.uint32)
        wbytes = ((words[:, None] >> shifts[None, :])
                  & jnp.uint32(0xFF)).astype(jnp.uint8).reshape(-1)
        bbuf = jnp.zeros((byte_cap,), jnp.uint8).at[4:4 + 4 * w].set(wbytes)
        use_bitmap = total > 4 + 4 * w
        buf = jnp.where(use_bitmap, bbuf, buf)
        hdr = hdr | (use_bitmap.astype(jnp.uint32) << 31)
        overflow = jnp.zeros((), bool)       # bitmap always representable
    else:
        overflow = varint_ovf
    return buf.at[:4].set(_le_bytes(hdr)), overflow


def decode_delta_varint(buf: jnp.ndarray, cap: int, id_range: int):
    """Inverse of ``encode_delta_varint``: (byte_cap,) uint8 payload ->
    (cap,) int32 sorted ids, -1 padded at the tail.

    Trailing zero bytes would decode as phantom zero-delta groups; the
    header count masks everything past the real ids to -1.
    """
    byte_cap = buf.shape[0]
    shifts = jnp.uint32(8) * jnp.arange(4, dtype=jnp.uint32)
    hdr = (buf[:4].astype(jnp.uint32) << shifts).sum(dtype=jnp.uint32)
    count = (hdr & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
    use_bitmap = (hdr >> 31) > 0
    data = buf[4:]
    d = data.shape[0]

    # group index per byte = exclusive count of terminators (high bit 0)
    # before it; within-group position from the previous terminator
    term = (data & jnp.uint8(0x80)) == 0
    g = jnp.cumsum(term.astype(jnp.int32)) - term.astype(jnp.int32)
    idx = jnp.arange(d)
    startm = lax.cummax(jnp.where(term, idx + 1, 0))
    start = jnp.concatenate([jnp.zeros((1,), startm.dtype), startm[:-1]])
    within = idx - start
    contrib = jnp.where(
        within <= 4,
        (data.astype(jnp.uint32) & jnp.uint32(0x7F))
        << (jnp.uint32(7) * jnp.minimum(within, 4).astype(jnp.uint32)),
        jnp.uint32(0))
    deltas = jnp.zeros((cap + 1,), jnp.uint32).at[jnp.minimum(g, cap)].add(
        contrib)[:cap]
    acc = jnp.cumsum(deltas.astype(jnp.int32))
    k = jnp.arange(cap)
    ids_varint = jnp.where(k < count, acc, -1).astype(jnp.int32)

    w = packed_words(id_range)
    if 4 + 4 * w <= byte_cap:                # bitmap mode statically possible
        wraw = data[: 4 * w].astype(jnp.uint32).reshape(w, 4)
        words = (wraw << shifts[None, :]).sum(axis=1, dtype=jnp.uint32)
        mask = unpack_bits(words[:, None], id_range)[:, 0]
        lid = jnp.where(mask > 0, jnp.arange(id_range), id_range)
        if cap > id_range:
            lid = jnp.concatenate(
                [lid, jnp.full((cap - id_range,), id_range, lid.dtype)])
        packed = jnp.sort(lid)[:cap]
        ids_bitmap = jnp.where(packed < id_range, packed, -1).astype(jnp.int32)
        return jnp.where(use_bitmap, ids_bitmap, ids_varint)
    return ids_varint


# ---------------------------------------------------------------------------
# Visited sieve: replicated coarse visited summary ("Compression and Sieve")
# ---------------------------------------------------------------------------

SIEVE_MAX_BITS = 1024     # summary bits per shard (<= 32 words = 128 B)


def sieve_layout(shard: int):
    """``(bits, bucket, words)`` of one shard's visited summary: ``bits``
    buckets of ``bucket`` consecutive local vertices, packed into
    ``words`` uint32s.  Capped at ``SIEVE_MAX_BITS`` bits so the
    replicated summary stays negligible next to the id payload it
    prunes."""
    bits = min(SIEVE_MAX_BITS, max(1, shard))
    bucket = -(-shard // bits)
    bits = -(-shard // bucket)
    return bits, bucket, packed_words(bits)


def sieve_summary(dist_col: jnp.ndarray, bits: int,
                  bucket: int) -> jnp.ndarray:
    """(shard,) int32 distances -> (words,) uint32 summary; bit ``k`` is
    set iff *every* vertex of bucket ``k`` is visited.  A set bit means
    any candidate landing in the bucket is provably redundant — the
    filter is conservative, so sieving never changes a distance.  Pad
    slots of a straddling final bucket count as visited (they are never
    candidates), keeping the bit exact."""
    shard = dist_col.shape[0]
    visited = dist_col < INF
    if bits * bucket != shard:
        visited = jnp.concatenate(
            [visited, jnp.ones((bits * bucket - shard,), bool)])
    full = visited.reshape(bits, bucket).all(axis=1)
    return pack_bits(full[:, None].astype(jnp.uint8))[:, 0]


def sieve_lookup(gwords: jnp.ndarray, gids: jnp.ndarray, shard: int,
                 bits: int, bucket: int, words: int) -> jnp.ndarray:
    """Look candidate *global* ids up in the replicated summary.

    gwords: (n_shards * words,) uint32, block ``k`` = shard ``k``'s
    ``sieve_summary``.  gids: (...,) int32 candidates (negatives pass
    through unhit).  Returns a bool mask, True where the candidate's
    whole bucket is already visited — it can be sieved out before the
    exchange without changing any distance."""
    ok = gids >= 0
    gid = jnp.where(ok, gids, 0)
    owner = gid // shard
    bit = (gid - owner * shard) // bucket
    word = gwords[owner * words + bit // 32]
    hit = ((word >> (bit % 32).astype(jnp.uint32)) & jnp.uint32(1)) > 0
    return hit & ok
