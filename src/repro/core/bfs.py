"""Distributed level-synchronous BFS with 1-D partitioning (paper fig. 2).

The traversal kernel is a single ``shard_map``-wrapped ``lax.while_loop``:
every iteration is one BFS level — local expansion (computation step, paper
§2.3) followed by an owner exchange (communication step) and the owner-side
distance update.  All shapes are static; termination is a replicated
``psum`` of the new-frontier population so every shard exits together.

This module holds the *kernel*: options, per-shard loop body builder and
source validation.  The public lifecycle lives in ``core/engine.py``::

    plan(graph, opts, mesh) -> BFSPlan -> .compile() -> BFSEngine -> .run()

``bfs()`` below is the deprecated one-shot wrapper over that lifecycle; it
resolves engines through the process-wide shared cache
(``repro.serve.engine_cache``) so legacy call sites no longer recompile on
every traversal and share compiled engines with the serving paths.

Modes (``BFSOptions.mode``):
  * ``dense``  — bitmap frontier, candidate exchange via any strategy
    registered under ``exchange.register_exchange("dense", ...)``.
    Supports batched multi-source BFS (S sources traversed simultaneously
    — the Graph500-style formulation that keeps the MXU busy; see
    kernels/bsr_spmm).
  * ``queue``  — the paper's sparse per-owner send buffers (S = 1).
  * ``auto``   — beyond-paper direction-optimizing hybrid: per level picks
    bottom-up (frontier huge), queue (frontier tiny) or dense top-down,
    from replicated frontier statistics.  This is the TPU adaptation of
    Beamer-style direction switching: on a systolic machine the win is in
    *bytes on the wire*, not early-exit branchiness.

All three modes exist under both partition schemes: the 2-D backend
(``_make_shard_fn_2d``) maps queue onto sparse expand/fold id exchanges
and bottom-up onto a both-axes frontier gather over the owner-side
in-edge blocks, switching per level exactly like the 1-D hybrid.

The returned stats carry per-level analytic communication bytes so the
benchmarks can reproduce the paper's scalability contrast (computation vs
communication cost, §4) without real multi-host hardware.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from repro.core import exchange as ex
from repro.core import frontier as fr
from repro.core.partition import Partition1D, Partition2D
# module import (not the function): kernels.fold_update imports
# core.frontier, so either module may be imported first
from repro.kernels import fold_update as fold_kernel

if TYPE_CHECKING:  # graphs.formats imports core.partition; avoid the cycle
    from repro.graphs.formats import ShardedGraph

INF = fr.INF

# The traversal's own names in the compiled program.  ``jax.named_scope``
# writes them into each HLO instruction's ``op_name`` metadata and
# changes no computation.  Every level runs under one mode scope (in the
# order of ``BFSRunStats.mode_counts``) and each of its ops under one phase
# scope; where scopes nest, the outermost mode and the innermost phase
# count.  A queue level that escalates runs its dense ops inside
# ``bfs.queue``, as ``mode_counts`` counts it.
MODE_SCOPES = ("bfs.dense", "bfs.queue", "bfs.bottom_up")
PHASE_SCOPES = ("bfs.decide", "bfs.expand", "bfs.exchange", "bfs.fold",
                "bfs.update")
INIT_SCOPE = "bfs.init"
_DENSE, _QUEUE, _BOTTOM_UP = MODE_SCOPES
_DECIDE, _EXPAND, _EXCHANGE, _FOLD, _UPDATE = PHASE_SCOPES
_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class BFSOptions:
    mode: str = "dense"                       # dense | queue | auto
    dense_exchange: str = "alltoall_direct"   # see exchange.DENSE_STRATEGIES
    queue_exchange: str = "alltoall_direct"   # see exchange.QUEUE_STRATEGIES
    # 2-D (partition="2d") phase strategies; "auto" picks the registered
    # strategy with the smallest modeled bytes (exchange.select_exchange).
    expand_exchange: str = "allgather"        # see exchange.EXPAND_ROW_STRATEGIES
    fold_exchange: str = "alltoall_reduce"    # see exchange.FOLD_COL_STRATEGIES
    # sparse (queue/auto) 2-D phase strategies: id buffers on the wire
    expand_sparse_exchange: str = "allgather"       # EXPAND_ROW_SPARSE_...
    fold_sparse_exchange: str = "alltoall_direct"   # FOLD_COL_SPARSE_...
    local_update: bool = True                 # paper §5.1 opt (1)
    dedupe: bool = True                       # drop dup targets pre-wire
    queue_cap: int = 1024                     # ids per destination bucket
    max_levels: int = 0                       # 0 -> derive from n
    # auto-mode thresholds (fractions of global E / V):
    queue_threshold: float = 1 / 64           # frontier edges below -> queue
    bottom_up_threshold: float = 0.05         # frontier verts above -> bottom-up
    use_kernel: bool = False                  # Pallas bsr_spmm expansion
                                              # (dense mode, 1-D partition;
                                              # runs per shard under the
                                              # multi-device loop)
    # Wire layout of the exchanges.  Dense phases: "packed" ships uint32
    # bitset words (8x smaller, OR merges), "bytes" the uint8 mask.
    # Sparse phases (queue / expand_row_sparse / fold_col_sparse):
    # "compressed" ships delta+varint id streams (frontier.encode_delta_
    # varint, ~1 byte per id, bitmap-capped) instead of raw int32 ids.
    # "auto" prices every layout per phase at plan time
    # (exchange.select_exchange / the _packed and _compressed strategy
    # twins) and picks the cheapest; "packed"/"compressed" pin the dense/
    # sparse tier each names and leave the other tier at its default.
    wire_format: str = "auto"       # packed | bytes | compressed | auto
    # Visited sieve ("Compression and Sieve"): filter candidate ids
    # against a replicated coarse visited summary *before* the sparse
    # exchange, so already-discovered vertices never occupy bucket slots
    # (fewer dense escalations as the traversal converges).  "auto"
    # enables it where the sparse paths exist: non-dense single-source
    # plans on a real mesh.
    sieve: object = "auto"          # True | False | "auto"
    # Fused fold/owner-update tail (kernels/fold_update): replace the
    # dense tail's unpack -> compare -> where op chain with one kernel
    # pass over the merged candidate words that also emits the next
    # frontier generation pre-packed, double-buffered in loop state so
    # word-consuming collectives of level L+1 need no pack after level
    # L's update.  Requires the dense (1-D) / fold (2-D) wire to resolve
    # packed; "auto" turns it on exactly there for dense/auto-mode plans
    # (queue-mode plans only benefit on escalated levels but would pay a
    # re-pack on every sparse level).  Resolved at plan time like
    # wire_format — the resolved flag keys into plan_key().
    use_fused_tail: object = "auto"  # True | False | "auto"

    def validate(self):
        if self.mode not in ("dense", "queue", "auto"):
            raise ValueError(f"unknown BFS mode {self.mode!r}; "
                             "expected dense | queue | auto")
        if self.wire_format not in ("packed", "bytes", "compressed", "auto"):
            raise ValueError(f"unknown wire_format {self.wire_format!r}; "
                             "expected packed | bytes | compressed | auto")
        if self.sieve not in (True, False, "auto"):
            raise ValueError(f"unknown sieve setting {self.sieve!r}; "
                             "expected True | False | 'auto'")
        if self.use_fused_tail not in (True, False, "auto"):
            raise ValueError(
                f"unknown use_fused_tail setting {self.use_fused_tail!r}; "
                "expected True | False | 'auto'")
        # get_exchange raises a ValueError naming the registered strategies;
        # "auto" defers to the byte-model selection at plan time.
        for kind, name in (("dense", self.dense_exchange),
                           ("queue", self.queue_exchange),
                           ("expand_row", self.expand_exchange),
                           ("fold_col", self.fold_exchange),
                           ("expand_row_sparse", self.expand_sparse_exchange),
                           ("fold_col_sparse", self.fold_sparse_exchange)):
            if name != "auto":
                ex.get_exchange(kind, name)
        if self.queue_cap <= 0:
            raise ValueError(f"queue_cap must be positive ({self.queue_cap})")
        if self.max_levels < 0:
            raise ValueError(f"max_levels must be >= 0 ({self.max_levels})")


@dataclasses.dataclass
class BFSStats:
    """Host-side summary of one traversal (legacy / ``bfs()`` interface).

    The engine API splits this into static plan metadata
    (``BFSPlan.describe()``) and per-run device stats (``BFSRunStats``,
    a pytree that stays on device until ``.block()``); this container is
    what ``BFSResult.stats()`` materializes for host consumers.
    """

    levels: int
    visited: int
    comm_bytes: float          # analytic, summed over levels, per chip
    overflowed: bool           # a queue level overflowed (result still exact:
                               # engine falls back to dense for that level)
    mode_counts: dict
    sieve_hits: int = 0        # candidates the visited-sieve dropped
                               # before they reached a collective


def validate_sources(sources, n_logical: int,
                     max_sources: Optional[int] = None) -> np.ndarray:
    """Validate BFS source ids; returns them as a 1-D int64 array.

    Rejects ids outside ``[0, n_logical)`` and duplicates with a clear
    ValueError (previously ``dist0[sv, j]`` either crashed cryptically or
    silently wrapped on negative ids).
    """
    arr = np.atleast_1d(np.asarray(sources))
    if arr.ndim != 1:
        raise ValueError(f"sources must be a scalar or 1-D sequence, "
                         f"got shape {arr.shape}")
    if arr.size == 0:
        raise ValueError("sources must contain at least one vertex id")
    if not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(f"sources must be integer vertex ids, "
                         f"got dtype {arr.dtype}")
    arr = arr.astype(np.int64)
    bad = arr[(arr < 0) | (arr >= n_logical)]
    if bad.size:
        raise ValueError(f"source ids {bad.tolist()} outside "
                         f"[0, {n_logical})")
    uniq, counts = np.unique(arr, return_counts=True)
    dup = uniq[counts > 1]
    if dup.size:
        raise ValueError(f"duplicate source ids {dup.tolist()}; each "
                         "column of a batched traversal needs a distinct "
                         "source")
    if max_sources is not None and arr.size > max_sources:
        raise ValueError(f"{arr.size} sources exceed the engine's "
                         f"compiled capacity of {max_sources}; build a "
                         "plan with a larger num_sources")
    return arr


def takes_queue_levels(mode: str, s: int) -> bool:
    """Whether a plan can run a sparse queue level: queue mode, or the
    auto hybrid with a single source (id buckets are per vertex, not per
    source column)."""
    return mode != "dense" and s == 1


def _owned_update(dist, own_cand, level):
    """Owner-computes rule: only unvisited vertices take the new level."""
    unseen = dist == INF
    new = (own_cand > 0) & unseen
    dist = jnp.where(new, level, dist)
    return dist, new.astype(jnp.uint8)


def _make_shard_fn(part: Partition1D, e_total: int, s: int,
                   axis, axes_sizes, opts: BFSOptions, max_levels: int,
                   dense_strategy: ex.ExchangeStrategy,
                   queue_strategy: ex.ExchangeStrategy,
                   expand_fn=None, expand_emits_packed: bool = False,
                   n_kernel_args: int = 0, bottom_up_wire: str = "bytes",
                   sieve: bool = False, fused: bool = False,
                   on_tpu: bool = False, on_trace=None):
    """Builds the per-shard BFS body (runs under shard_map).

    Exchange strategies arrive pre-resolved from the registry (plan time),
    so the loop body never consults strategy names; the strategy's
    ``wire`` field decides whether candidates cross the exchange packed
    (uint32 bitset words, OR merges) or as the uint8 mask.  ``expand_fn``
    (the Pallas bsr_spmm path) receives the frontier plus
    ``n_kernel_args`` extra per-shard operands (the device-resident
    blocked adjacency); with ``expand_emits_packed`` its output is
    already the per-shard-blocked word array, so a packed exchange
    consumes it with no pack step.  ``on_trace`` is invoked once per
    trace — engines use it to prove compile-once reuse.

    ``fused`` (plan-time resolution of ``BFSOptions.use_fused_tail``;
    requires the dense wire to be packed) replaces the dense level's
    unpack → owner-update tail with the ``kernels/fold_update`` fused
    kernel and double-buffers the frontier: the loop state carries the
    packed word generation (``fwords``) alongside the byte mask, each
    level tail emits the next generation, and word-consuming collectives
    (the packed bottom-up gather here; the 2-D expand allgather in
    ``_make_shard_fn_2d``) read the *carried* words — their payload is
    ready the moment the previous level's fused tail retires, with no
    pack on the critical path between levels.  ``on_tpu`` (the mesh's
    platform) runs that tail as the compiled Pallas kernel; every other
    platform runs its fused jnp twin.

    Plans that can take a queue level (``takes_queue_levels``) receive
    the shard's CSR view (``row_ptr``, ``csr_dst``; ``frontier.csr_view``)
    after the kernel operands.  The auto decide phase counts frontier
    edges from its row offsets, and a queue level compacts the frontier's
    edges into a buffer of ``min(e_cap, queue edge cutoff)`` slots
    (auto takes a queue level only below the cutoff; forced queue mode
    has no bound, so ``e_cap``) instead of passing over every edge slot.
    """
    p, shard, n = part.p, part.shard_size, part.n
    itemsize = 1  # uint8 masks (the "bytes" wire format)
    w_shard = fr.packed_words(shard)
    csr = takes_queue_levels(opts.mode, s)
    queue_edge_cutoff = max(1, int(opts.queue_threshold * e_total))
    bottom_up_cutoff = max(1, int(opts.bottom_up_threshold * part.n_logical))
    # compressed queue wire: bucket row j encodes ids relative to j*shard
    # (range [0, shard)); the static byte capacity below is exactly what
    # the strategy's byte model prices at this plan's capacity density
    use_compressed = queue_strategy.wire == "compressed"
    q_byte_cap = fr.compressed_capacity(opts.queue_cap, shard)
    sv_bits, sv_bucket, sv_words = fr.sieve_layout(shard)
    sieve_gather_bytes = float((p - 1) * sv_words * 4) if sieve else 0.0
    dense_bytes = dense_strategy.bytes_model(n, p, s, itemsize, axes_sizes)
    queue_bytes = queue_strategy.bytes_model(
        p, opts.queue_cap, 4, opts.queue_cap / shard) + sieve_gather_bytes
    bottom_up_bytes = ex.bottomup_level_bytes(n, p, s, itemsize,
                                              wire=bottom_up_wire)

    def dense_level(frontier, dist, level, src_local, dst_global, kargs):
        with _scope(_EXPAND):
            if expand_fn is not None:
                cand = expand_fn(frontier, *kargs)
            else:
                cand = fr.expand_dense(frontier, src_local, dst_global, n)
        if dense_strategy.wire == "packed":
            # keep candidates packed through the collective: pack once
            # (unless the kernel already emitted words), OR-merge on the
            # wire payload, unpack only the owned W-word slice
            with _scope(_EXCHANGE):
                words = cand if (expand_fn is not None and expand_emits_packed
                                 ) else fr.pack_bits(cand, n_blocks=p)
                merged = dense_strategy.impl(words, axis)
            if fused:
                # fused tail: one kernel pass bit-tests the merged words
                # against dist, writes depths and emits the next packed
                # frontier generation — no (shard, S) unpack between the
                # collective and the next level
                with _scope(_FOLD):
                    dist, new, nwords = fold_kernel.fold_update(
                        merged, dist, level, use_pallas=on_tpu)
                return dist, new, nwords, jnp.float32(dense_bytes)
            with _scope(_FOLD):
                own = fr.unpack_bits(merged, shard)
        else:
            with _scope(_EXCHANGE):
                own = dense_strategy.impl(cand, axis)
        with _scope(_UPDATE):
            dist, new = _owned_update(dist, own, level)
        return dist, new, None, jnp.float32(dense_bytes)

    def bottom_up_level(frontier, fwords, dist, level, in_src_global,
                        in_dst_local):
        if bottom_up_wire == "packed":
            # gather the packed frontier (8x smaller) and read source
            # bits straight out of the words — no (n, S) unpack.  Fused
            # plans carry the packed generation in loop state (the
            # previous level's tail emitted it), so the gather payload is
            # ready with no pack on this level's critical path.
            with _scope(_EXCHANGE):
                fw = fwords if fused else fr.pack_bits(frontier)  # (W, S)
                fglob_w = ex.allgather_frontier(fw, axis)         # (p*W, S)
            with _scope(_EXPAND):
                cand = fr.expand_bottom_up_packed(
                    fglob_w, in_src_global, in_dst_local, shard, w_shard)
        else:
            with _scope(_EXCHANGE):
                fglob = ex.allgather_frontier(frontier, axis)  # (n, S)
            with _scope(_EXPAND):
                cand = fr.expand_bottom_up(fglob, in_src_global,
                                           in_dst_local, shard)
        with _scope(_UPDATE):
            dist, new = _owned_update(dist, cand, level)
            nwords = fr.pack_bits(new) if fused else None
        return dist, new, nwords, jnp.float32(bottom_up_bytes)

    def queue_level(frontier, dist, level, src_local, dst_global, kargs,
                    row_ptr, csr_dst, fdeg):
        with _scope(_EXPAND):
            me = lax.axis_index(axis)
            e_cap = csr_dst.shape[0]
            slots = (e_cap if opts.mode == "queue"
                     else min(e_cap, queue_edge_cutoff))
            if fdeg is None:             # queue mode has no decide phase
                fdeg = fr.frontier_out_degrees(frontier[:, 0], row_ptr)
            cand, active = fr.compact_frontier_edges(fdeg, row_ptr, csr_dst,
                                                     slots)
            hits = jnp.int32(0)
            if sieve:
                # replicate each shard's coarse visited summary and drop
                # candidates whose whole bucket is already visited — they
                # can never lower a distance, so they need not ship
                own_sum = fr.sieve_summary(dist[:, 0], sv_bits, sv_bucket)
                with _scope(_EXCHANGE):
                    gsum = lax.all_gather(own_sum, axis, tiled=True)
                drop = fr.sieve_lookup(gsum, cand, shard, sv_bits,
                                       sv_bucket, sv_words) & active
                with _scope(_EXCHANGE):
                    hits = lax.psum(drop.sum(dtype=jnp.int32), axis)
                active = active & ~drop
            buckets, local_mask, _, overflow = fr.build_queue_buckets(
                cand, active, part, me, opts.queue_cap,
                local_update=opts.local_update, dedupe=opts.dedupe)
            if use_compressed:
                base = jnp.arange(p, dtype=jnp.int32)[:, None] * shard
                rel = jnp.where(buckets >= 0, buckets - base, -1)
                payload, enc_ovf = jax.vmap(
                    lambda row: fr.encode_delta_varint(row, q_byte_cap, shard)
                )(rel)
                overflow = overflow | enc_ovf.any()
        # Exactness guarantee: if any shard's bucket (or compressed
        # stream) overflowed, run the whole level densely instead (the
        # predicate is replicated, so all shards take the same branch and
        # collectives stay collective).
        with _scope(_EXCHANGE):
            overflow_any = lax.psum(overflow.astype(jnp.int32), axis) > 0

        def sparse_branch():
            with _scope(_EXCHANGE):
                if use_compressed:
                    recv = queue_strategy.impl(payload, axis)  # (p, byte_cap)
                    rec_ids = jax.vmap(
                        lambda row: fr.decode_delta_varint(
                            row, opts.queue_cap, shard))(recv)
                    rec_ids = jnp.where(rec_ids >= 0, rec_ids + me * shard,
                                        -1)
                else:
                    rec_ids = queue_strategy.impl(buckets, axis)
            with _scope(_FOLD):
                own = jnp.maximum(fr.apply_queue(rec_ids, me, shard),
                                  local_mask)
            with _scope(_UPDATE):
                d2, new = _owned_update(dist, own[:, None], level)
                nwords = fr.pack_bits(new) if fused else None
            return d2, new, nwords, jnp.float32(queue_bytes)

        def dense_branch():
            d2, new, nwords, bb = dense_level(frontier, dist, level,
                                              src_local, dst_global, kargs)
            # the sieve gather (if any) already ran before escalation
            with _scope(_UPDATE):
                bb = bb + jnp.float32(sieve_gather_bytes)
            return d2, new, nwords, bb

        d2, new, nwords, bytes_ = lax.cond(overflow_any, dense_branch,
                                           sparse_branch)
        return d2, new, nwords, bytes_, overflow_any, hits

    def body(state, src_local, dst_global, in_src_global, in_dst_local,
             kargs, row_ptr, csr_dst, valid_local, vwords):
        if fused:
            (dist, frontier, fwords, level, _, bytes_acc, overflowed,
             modes, hits_acc) = state
        else:
            (dist, frontier, level, _, bytes_acc, overflowed, modes,
             hits_acc) = state
            fwords = None
        hits = jnp.int32(0)

        if opts.mode == "dense":
            with _scope(_DENSE):
                dist, new, nwords, b = dense_level(
                    frontier, dist, level, src_local, dst_global, kargs)
            with _scope(_UPDATE):
                modes = modes.at[0].add(1)
            ovf = jnp.bool_(False)
        elif opts.mode == "queue":
            with _scope(_QUEUE):
                dist, new, nwords, b, ovf, hits = queue_level(
                    frontier, dist, level, src_local, dst_global, kargs,
                    row_ptr, csr_dst, None)
            with _scope(_UPDATE):
                modes = modes.at[1].add(1)
        else:  # auto: direction-optimizing hybrid
            with _scope(_DECIDE):
                f_verts = lax.psum(frontier.sum(dtype=jnp.int32), axis)
                if csr:
                    fdeg = fr.frontier_out_degrees(frontier[:, 0], row_ptr)
                    f_edges_local = fdeg.sum(dtype=jnp.int32)
                else:
                    # S > 1: no queue level reads the count, and the
                    # compiler drops this pass over the edge slots
                    f_edges_local = jnp.where(
                        dst_global >= 0, frontier[src_local, 0], 0
                    ).sum(dtype=jnp.int32)
                f_edges = lax.psum(f_edges_local, axis)
                big = f_verts > jnp.int32(bottom_up_cutoff)
                tiny = f_edges < jnp.int32(queue_edge_cutoff)

            def do_bottom_up():
                with _scope(_BOTTOM_UP):
                    d, nw, nwd, b = bottom_up_level(frontier, fwords, dist,
                                                    level, in_src_global,
                                                    in_dst_local)
                return (d, nw, nwd, b, jnp.bool_(False), jnp.int32(2),
                        jnp.int32(0))

            def do_queue():
                with _scope(_QUEUE):
                    d, nw, nwd, b, ovf, h = queue_level(
                        frontier, dist, level, src_local, dst_global, kargs,
                        row_ptr, csr_dst, fdeg)
                return d, nw, nwd, b, ovf, jnp.int32(1), h

            def do_dense():
                with _scope(_DENSE):
                    d, nw, nwd, b = dense_level(frontier, dist, level,
                                                src_local, dst_global, kargs)
                return (d, nw, nwd, b, jnp.bool_(False), jnp.int32(0),
                        jnp.int32(0))

            if csr:
                dist, new, nwords, b, ovf, which, hits = lax.cond(
                    big, do_bottom_up,
                    lambda: lax.cond(tiny, do_queue, do_dense))
            else:
                dist, new, nwords, b, ovf, which, hits = lax.cond(
                    big, do_bottom_up, do_dense)
            with _scope(_UPDATE):
                modes = modes.at[which].add(1)

        with _scope(_UPDATE):
            # Mask padding vertices (ids >= n_logical can never be visited).
            new = new * valid_local[:, None].astype(new.dtype)
            dist = jnp.where(valid_local[:, None], dist, INF)
            active = lax.psum(new.sum(dtype=jnp.int32), axis) > 0
            if fused:
                # next packed generation, pad bits cleared to match the
                # masked byte frontier exactly
                fwords = nwords & vwords
                return (dist, new, fwords, level + 1, active, bytes_acc + b,
                        overflowed | ovf, modes, hits_acc + hits)
            return (dist, new, level + 1, active, bytes_acc + b,
                    overflowed | ovf, modes, hits_acc + hits)

    def shard_fn(src_local, dst_global, in_src_global, in_dst_local, *rest):
        if on_trace is not None:
            on_trace()
        kargs = rest[:n_kernel_args]
        row_ptr = csr_dst = None
        if csr:
            row_ptr, csr_dst = rest[n_kernel_args:n_kernel_args + 2]
        dist0, frontier0, valid_local = rest[-3:]
        tail0 = (jnp.int32(1), jnp.bool_(True), jnp.float32(0),
                 jnp.bool_(False), jnp.zeros(3, jnp.int32), jnp.int32(0))
        if fused:
            vwords = fr.pack_bits(valid_local.astype(jnp.uint8)[:, None])
            state0 = (dist0, frontier0, fr.pack_bits(frontier0)) + tail0
        else:
            vwords = None
            state0 = (dist0, frontier0) + tail0
        lvl_i, act_i = (3, 4) if fused else (2, 3)

        def cond(st):
            return st[act_i] & (st[lvl_i] <= max_levels)

        def body_fn(st):
            return body(st, src_local, dst_global, in_src_global,
                        in_dst_local, kargs, row_ptr, csr_dst, valid_local,
                        vwords)

        st = lax.while_loop(cond, body_fn, state0)
        level = st[lvl_i]
        bytes_acc, overflowed, modes, sieve_hits = st[lvl_i + 2:lvl_i + 6]
        return st[0], level - 1, bytes_acc, overflowed, modes, sieve_hits

    return shard_fn


def _make_shard_fn_2d(part2: Partition2D, e_total: int, s: int,
                      row_axis, col_axis, opts: BFSOptions, max_levels: int,
                      expand_strategy: ex.ExchangeStrategy,
                      fold_strategy: ex.ExchangeStrategy,
                      expand_sparse_strategy: ex.ExchangeStrategy,
                      fold_sparse_strategy: ex.ExchangeStrategy,
                      bottom_up_wire: str = "bytes",
                      sieve: bool = False, fused: bool = False,
                      on_tpu: bool = False, on_trace=None):
    """Per-device body of the 2-D two-phase BFS level loop (shard_map).

    Each dense level is expand -> local edge scatter -> fold -> owner
    update:

      1. expand (row phase): allgather this device's (b, S) frontier chunk
         across its grid row (the ``col_axis``, c participants) into the
         contiguous (c*b, S) row-block frontier.
      2. local expansion: scatter the device's edge block through the
         gathered frontier into the *transposed* (r*b, S) fold layout.
      3. fold (column phase): all-to-all+reduce the fold blocks across the
         grid column (the ``row_axis``, r participants); each device
         receives exactly its owned (b, S) candidate merge.
      4. owner-computes update + replicated termination psum over both
         grid axes — identical semantics to the 1-D loop, so BFSRunStats
         and the donated dist buffer behave the same.

    The direction-optimizing variants make both phases cheap when the
    frontier is narrow or huge (mirroring the 1-D hybrid):

      * queue  — the expand allgather ships active frontier *ids*
        (pack_frontier_ids, cap-bounded) instead of the bitmap, and the
        fold ships per-row-rank candidate id buckets
        (build_queue_buckets_2d, §5.1 local-update exclusion applied with
        the device's row rank).  Any pack/bucket overflow escalates the
        whole level to the dense representation under a replicated
        predicate, so results stay exact and collectives stay collective.
      * bottom-up — the frontier bitmap is gathered over *both* grid axes
        and each device checks the in-edges of the vertices it owns
        (the in-edge blocks on ShardedGraph2D); no fold exchange at all.
      * auto — per level picks bottom-up (frontier huge), queue (frontier
        edges tiny, S = 1) or dense, from replicated frontier statistics
        (the frontier-edge count uses the per-vertex out_degree block).

    ``fused`` (requires the fold wire packed) fuses the fold-merge +
    owner-update tail into the ``kernels/fold_update`` kernel and carries
    the packed frontier generation in loop state, exactly as in the 1-D
    builder — here the payoff is larger: the expand-phase allgather of
    level L+1 ships the carried words the fused tail of level L emitted,
    so XLA can issue that collective with no pack (and, via
    ``frontier.expand_dense_2d_packed``, no row-frontier unpack) between
    it and the previous level's update.  ``on_tpu`` picks the kernel as
    in the 1-D builder.
    """
    r, c, b = part2.r, part2.c, part2.shard_size
    p = part2.p
    fold_len = part2.fold_size
    w_chunk = fr.packed_words(b)
    grid_axes = (row_axis, col_axis)
    queue_edge_cutoff = max(1, int(opts.queue_threshold * e_total))
    bottom_up_cutoff = max(1, int(opts.bottom_up_threshold * part2.n_logical))
    # compressed sparse phases: both ship ids from [0, b) (expand: local
    # frontier ids; fold: bucket row rr relative to rr*b), so they share
    # one static byte capacity, matching the models' capacity density
    use_comp_expand = expand_sparse_strategy.wire == "compressed"
    use_comp_fold = fold_sparse_strategy.wire == "compressed"
    g_byte_cap = fr.compressed_capacity(opts.queue_cap, b)
    g_density = opts.queue_cap / b
    sv_bits, sv_bucket, sv_words = fr.sieve_layout(b)
    sieve_gather_bytes = jnp.float32(
        (p - 1) * sv_words * 4 if sieve else 0.0)
    dense_bytes = jnp.float32(
        expand_strategy.bytes_model(part2.n, r, c, s, 1) +
        fold_strategy.bytes_model(part2.n, r, c, s, 1))
    expand_sparse_bytes = jnp.float32(
        expand_sparse_strategy.bytes_model(r, c, opts.queue_cap, 4,
                                           g_density))
    sparse_bytes = expand_sparse_bytes + sieve_gather_bytes + jnp.float32(
        fold_sparse_strategy.bytes_model(r, c, opts.queue_cap, 4, g_density))
    bottom_up_bytes = jnp.float32(ex.bottomup_level_bytes(
        part2.n, p, s, 1, wire=bottom_up_wire))

    def dense_level(frontier, fwords, dist, level, src_rowlocal, dst_fold):
        if expand_strategy.wire == "packed":
            # ship the frontier chunk as words.  Fused plans gather the
            # *carried* packed generation (emitted by the previous
            # level's fused tail — double buffering: the collective's
            # payload has no compute dependency at the top of this level)
            # and read source bits straight from the gathered words; the
            # unfused path packs here and unpacks the c gathered segments
            # into the row frontier the expansion reads.
            with _scope(_EXCHANGE):
                payload = fwords if fused else fr.pack_bits(frontier)
                fw = expand_strategy.impl(payload, col_axis)
            if fused:
                with _scope(_EXPAND):
                    cand = fr.expand_dense_2d_packed(fw, src_rowlocal,
                                                     dst_fold, fold_len, b)
            else:
                with _scope(_EXCHANGE):
                    frow = fr.unpack_bits(fw, b, n_blocks=c)     # (c*b, S)
                with _scope(_EXPAND):
                    cand = fr.expand_dense_2d(frow, src_rowlocal, dst_fold,
                                              fold_len)
        else:
            with _scope(_EXCHANGE):
                frow = expand_strategy.impl(frontier, col_axis)  # (c*b, S)
            with _scope(_EXPAND):
                cand = fr.expand_dense_2d(frow, src_rowlocal, dst_fold,
                                          fold_len)
        if fold_strategy.wire == "packed":
            with _scope(_EXCHANGE):
                cw = fold_strategy.impl(fr.pack_bits(cand, n_blocks=r),
                                        row_axis)
            if fused:
                # fused fold tail: merge words -> dist depths + next
                # packed generation in one kernel pass (no (b, S) unpack)
                with _scope(_FOLD):
                    dist, new, nwords = fold_kernel.fold_update(
                        cw, dist, level, use_pallas=on_tpu)
                return dist, new, nwords, dense_bytes
            with _scope(_FOLD):
                own = fr.unpack_bits(cw, b)                      # (b, S)
        else:
            with _scope(_EXCHANGE):
                own = fold_strategy.impl(cand, row_axis)         # (b, S)
        with _scope(_UPDATE):
            dist, new = _owned_update(dist, own, level)
        return dist, new, None, dense_bytes

    def bottom_up_level(frontier, fwords, dist, level, in_src_global,
                        in_dst_local):
        # gather over (rows, cols) is chunk-id order: chunk k lives on
        # grid device (k // c, k % c), the same major-first linearization
        if bottom_up_wire == "packed":
            with _scope(_EXCHANGE):
                fw = fwords if fused else fr.pack_bits(frontier)  # (Wb, S)
                fglob_w = ex.allgather_frontier(fw, grid_axes)    # (p*Wb, S)
            with _scope(_EXPAND):
                cand = fr.expand_bottom_up_packed(fglob_w, in_src_global,
                                                  in_dst_local, b, w_chunk)
        else:
            with _scope(_EXCHANGE):
                fglob = ex.allgather_frontier(frontier, grid_axes)  # (n, S)
            with _scope(_EXPAND):
                cand = fr.expand_bottom_up(fglob, in_src_global,
                                           in_dst_local, b)
        with _scope(_UPDATE):
            dist, new = _owned_update(dist, cand, level)
            nwords = fr.pack_bits(new) if fused else None
        return dist, new, nwords, bottom_up_bytes

    def queue_level(frontier, fwords, dist, level, src_rowlocal, dst_fold):
        # the row phase ships the frontier's ids, so packing, encoding
        # and decoding them is exchange work
        with _scope(_EXCHANGE):
            me_row = lax.axis_index(row_axis)
            ids, _, pack_ovf = fr.pack_frontier_ids(frontier, opts.queue_cap)
            if use_comp_expand:
                pay, enc_ovf = fr.encode_delta_varint(ids, g_byte_cap, b)
                pack_ovf = pack_ovf | enc_ovf
                all_pay = expand_sparse_strategy.impl(pay, col_axis)
                all_ids = jax.vmap(
                    lambda seg: fr.decode_delta_varint(seg, opts.queue_cap, b)
                )(all_pay.reshape(c, g_byte_cap)).reshape(-1)    # (c*cap,)
            else:
                all_ids = expand_sparse_strategy.impl(ids, col_axis)
            frow = fr.unpack_row_frontier(all_ids, c, b)         # (c*b, 1)
        with _scope(_EXPAND):
            valid = dst_fold >= 0
            active = (frow[src_rowlocal, 0] > 0) & valid
            hits = jnp.int32(0)
            if sieve:
                # candidate dst_fold = rr*b + loc targets the vertex owned
                # by the grid device (rr, me_col), global chunk
                # rr*c + me_col — the both-axes summary gather is in
                # exactly that chunk order
                own_sum = fr.sieve_summary(dist[:, 0], sv_bits, sv_bucket)
                with _scope(_EXCHANGE):
                    gsum = lax.all_gather(own_sum, grid_axes, tiled=True)
                me_col = lax.axis_index(col_axis)
                df = jnp.where(active, dst_fold, 0)
                rr = df // b
                gid = (rr * c + me_col) * b + (df - rr * b)
                drop = fr.sieve_lookup(gsum, gid, b, sv_bits, sv_bucket,
                                       sv_words) & active
                with _scope(_EXCHANGE):
                    hits = lax.psum(drop.sum(dtype=jnp.int32), grid_axes)
                active = active & ~drop
            buckets, local_mask, _, bucket_ovf = fr.build_queue_buckets_2d(
                dst_fold, active, part2, me_row, opts.queue_cap,
                local_update=opts.local_update, dedupe=opts.dedupe)
            if use_comp_fold:
                base = jnp.arange(r, dtype=jnp.int32)[:, None] * b
                rel = jnp.where(buckets >= 0, buckets - base, -1)
                fpay, fenc_ovf = jax.vmap(
                    lambda row: fr.encode_delta_varint(row, g_byte_cap, b)
                )(rel)
                bucket_ovf = bucket_ovf | fenc_ovf.any()
        # Exactness guarantee: if any device's frontier pack, send bucket
        # or compressed stream overflowed, run the whole level densely
        # instead (the predicate is replicated over both grid axes, so
        # every device takes the same branch and collectives stay
        # collective).
        with _scope(_EXCHANGE):
            overflow_any = lax.psum(
                (pack_ovf | bucket_ovf).astype(jnp.int32), grid_axes) > 0

        def sparse_branch():
            with _scope(_EXCHANGE):
                if use_comp_fold:
                    recvp = fold_sparse_strategy.impl(fpay, row_axis)
                    rec = jax.vmap(lambda row: fr.decode_delta_varint(
                        row, opts.queue_cap, b))(recvp)          # (r, cap)
                    rec = jnp.where(rec >= 0, rec + me_row * b, -1)
                else:
                    rec = fold_sparse_strategy.impl(buckets, row_axis)
            with _scope(_FOLD):
                own = jnp.maximum(fr.apply_queue(rec, me_row, b), local_mask)
            with _scope(_UPDATE):
                d2, new = _owned_update(dist, own[:, None], level)
                nwords = fr.pack_bits(new) if fused else None
            return d2, new, nwords, sparse_bytes

        def dense_branch():
            # the sparse expand allgather (and sieve gather) above
            # already ran, so an escalated level pays their bytes on top
            # of the dense level's
            d2, new, nwords, bb = dense_level(frontier, fwords, dist, level,
                                              src_rowlocal, dst_fold)
            with _scope(_UPDATE):
                bb = bb + expand_sparse_bytes + sieve_gather_bytes
            return d2, new, nwords, bb

        d2, new, nwords, bytes_ = lax.cond(overflow_any, dense_branch,
                                           sparse_branch)
        return d2, new, nwords, bytes_, overflow_any, hits

    def body(state, src_rowlocal, dst_fold, in_src_global, in_dst_local,
             out_degree, valid_local, vwords):
        if fused:
            (dist, frontier, fwords, level, _, bytes_acc, overflowed,
             modes, hits_acc) = state
        else:
            (dist, frontier, level, _, bytes_acc, overflowed, modes,
             hits_acc) = state
            fwords = None
        hits = jnp.int32(0)

        if opts.mode == "dense":
            with _scope(_DENSE):
                dist, new, nwords, bb = dense_level(frontier, fwords, dist,
                                                    level, src_rowlocal,
                                                    dst_fold)
            with _scope(_UPDATE):
                modes = modes.at[0].add(1)
            ovf = jnp.bool_(False)
        elif opts.mode == "queue":
            with _scope(_QUEUE):
                dist, new, nwords, bb, ovf, hits = queue_level(
                    frontier, fwords, dist, level, src_rowlocal, dst_fold)
            with _scope(_UPDATE):
                modes = modes.at[1].add(1)
        else:  # auto: direction-optimizing hybrid on the grid
            with _scope(_DECIDE):
                f_verts = lax.psum(frontier.sum(dtype=jnp.int32), grid_axes)
                f_edges = lax.psum(
                    (out_degree * frontier[:, 0].astype(jnp.int32)
                     ).sum(dtype=jnp.int32), grid_axes)
                big = f_verts > jnp.int32(bottom_up_cutoff)
                tiny = f_edges < jnp.int32(queue_edge_cutoff)

            def do_bottom_up():
                with _scope(_BOTTOM_UP):
                    d, nw, nwd, bb = bottom_up_level(frontier, fwords, dist,
                                                     level, in_src_global,
                                                     in_dst_local)
                return (d, nw, nwd, bb, jnp.bool_(False), jnp.int32(2),
                        jnp.int32(0))

            def do_queue():
                with _scope(_QUEUE):
                    d, nw, nwd, bb, ovf, h = queue_level(
                        frontier, fwords, dist, level, src_rowlocal,
                        dst_fold)
                return d, nw, nwd, bb, ovf, jnp.int32(1), h

            def do_dense():
                with _scope(_DENSE):
                    d, nw, nwd, bb = dense_level(frontier, fwords, dist,
                                                 level, src_rowlocal,
                                                 dst_fold)
                return (d, nw, nwd, bb, jnp.bool_(False), jnp.int32(0),
                        jnp.int32(0))

            if s == 1:
                dist, new, nwords, bb, ovf, which, hits = lax.cond(
                    big, do_bottom_up,
                    lambda: lax.cond(tiny, do_queue, do_dense))
            else:
                dist, new, nwords, bb, ovf, which, hits = lax.cond(
                    big, do_bottom_up, do_dense)
            with _scope(_UPDATE):
                modes = modes.at[which].add(1)

        with _scope(_UPDATE):
            # Mask padding vertices (ids >= n_logical can never be visited).
            new = new * valid_local[:, None].astype(new.dtype)
            dist = jnp.where(valid_local[:, None], dist, INF)
            active = lax.psum(new.sum(dtype=jnp.int32), grid_axes) > 0
            if fused:
                # next packed generation, pad bits cleared to match the
                # masked byte frontier exactly
                fwords = nwords & vwords
                return (dist, new, fwords, level + 1, active, bytes_acc + bb,
                        overflowed | ovf, modes, hits_acc + hits)
            return (dist, new, level + 1, active, bytes_acc + bb,
                    overflowed | ovf, modes, hits_acc + hits)

    def _run(src_rowlocal, dst_fold, in_src_global, in_dst_local,
             out_degree, dist0, frontier0, valid_local):
        if on_trace is not None:
            on_trace()
        tail0 = (jnp.int32(1), jnp.bool_(True), jnp.float32(0),
                 jnp.bool_(False), jnp.zeros(3, jnp.int32), jnp.int32(0))
        if fused:
            vwords = fr.pack_bits(valid_local.astype(jnp.uint8)[:, None])
            state0 = (dist0, frontier0, fr.pack_bits(frontier0)) + tail0
        else:
            vwords = None
            state0 = (dist0, frontier0) + tail0
        lvl_i, act_i = (3, 4) if fused else (2, 3)

        def cond(st):
            return st[act_i] & (st[lvl_i] <= max_levels)

        def body_fn(st):
            return body(st, src_rowlocal, dst_fold, in_src_global,
                        in_dst_local, out_degree, valid_local, vwords)

        st = lax.while_loop(cond, body_fn, state0)
        level = st[lvl_i]
        bytes_acc, overflowed, modes, sieve_hits = st[lvl_i + 2:lvl_i + 6]
        return st[0], level - 1, bytes_acc, overflowed, modes, sieve_hits

    if opts.mode == "auto":
        shard_fn = _run
    else:
        # dense/queue loops never read the bottom-up blocks; the engine
        # uploads only (src_rowlocal, dst_fold) for them
        def shard_fn(src_rowlocal, dst_fold, dist0, frontier0, valid_local):
            return _run(src_rowlocal, dst_fold, None, None, None,
                        dist0, frontier0, valid_local)

    return shard_fn


def bfs(graph: "ShardedGraph", sources, mesh: Optional[Mesh] = None,
        axis=None, opts: BFSOptions = BFSOptions()):
    """One-shot BFS from ``sources`` (int or sequence -> batched).

    .. deprecated::
        ``bfs()`` is a thin wrapper over the compile-once lifecycle —
        ``plan(graph, opts, mesh).compile().run(sources)`` — kept for
        existing call sites.  Engines resolve through the process-wide
        shared ``EngineCache`` (serve/engine_cache.py, LRU over
        ``plan_key()`` with a configurable device-byte budget), so
        repeated calls amortize the compile *and* share compiled engines
        with the serving paths; new code should hold a ``BFSEngine``
        directly (and use ``run_async`` for pipelined dispatch).

    Returns (dist, stats): dist is (n_logical, S) int32 with INF for
    unreachable vertices; stats is a BFSStats.
    """
    from repro.core import engine as _engine  # deferred: engine imports us
    from repro.serve.engine_cache import default_engine_cache

    warnings.warn(
        "repro.core.bfs.bfs() is deprecated; use "
        "plan(graph, opts, mesh=...).compile().run(sources)",
        DeprecationWarning, stacklevel=2)
    src_arr = validate_sources(sources, graph.part.n_logical)
    s = int(src_arr.shape[0])

    pl = _engine.plan(graph, opts, mesh=mesh, axis=axis, num_sources=s)
    eng = default_engine_cache().get_or_compile(pl)
    res = eng.run(src_arr)
    return res.dist_host, res.stats()
