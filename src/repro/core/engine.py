"""Compile-once BFS lifecycle: ``plan() -> BFSPlan -> compile() -> BFSEngine``.

The paper's headline result is cutting *per-traversal* communication cost,
so the API must not give the win back at the call boundary.  The lifecycle
separates the three cost tiers explicitly:

  * ``plan(graph, opts, mesh)``   — host-side validation and static-shape
    derivation: checks options, resolves exchange strategies from the
    registry (core/exchange.py), normalizes the mesh/axis, fixes the
    source-batch capacity S.  Cheap; pure metadata (``BFSPlan``).
  * ``BFSPlan.compile()``         — builds the ``shard_map``-wrapped
    while-loop once and AOT-lowers it via ``jax.jit(...).lower().compile()``
    with the ``dist`` buffer donated; uploads the graph's edge blocks to
    device.  Paid once per (graph, opts, mesh, S).
  * ``BFSEngine.run(sources)``    — per traversal.  Source injection is a
    device-side scatter from an ``(S,)`` int32 array
    (frontier.init_dist_frontier), so fresh source sets never retrace and
    never materialize host ``(n, S)`` arrays.  ``run_async`` returns
    un-blocked device arrays for pipelined dispatch; stats stay on device
    (``BFSRunStats`` pytree) until ``.block()``/``.stats()``.

Every later scaling feature plugs into this seam; the first alternative
backend is already here: ``plan(graph, opts, mesh, partition="2d")``
compiles the 2-D edge-partitioned two-phase traversal (row-allgather
expand + column fold, r + c collective participants instead of p) behind
the exact same lifecycle — callers change nothing but the flag.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import exchange as ex
from repro.core import frontier as fr
from repro.core.bfs import (BFSOptions, BFSStats, INF, INIT_SCOPE,
                            _make_shard_fn, _make_shard_fn_2d,
                            takes_queue_levels, validate_sources)
# chaos layer: a no-op global read unless a FaultPlan is installed
# (stdlib-only module; degrade.py defers its engine import, no cycle)
from repro.serve.resilience import faults as _faults

if TYPE_CHECKING:
    from repro.graphs.formats import ShardedGraph, ShardedGraph2D


# ---------------------------------------------------------------------------
# Per-run stats: a device pytree — no host sync until .block()/.to_host()
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BFSRunStats:
    """Per-traversal statistics as device scalars (a JAX pytree).

    Static plan facts (p, S, strategies, byte model, ...) live in
    ``BFSPlan.describe()``; only values produced by the traversal itself
    are here, so pipelined ``run_async`` dispatch never blocks on stats.
    """

    levels: jax.Array          # () int32
    comm_bytes: jax.Array      # () float32, analytic per-chip
    overflowed: jax.Array      # () bool
    mode_counts: jax.Array     # (3,) int32: dense, queue, bottom_up levels
    sieve_hits: jax.Array      # () int32: candidates dropped pre-collective

    def block(self) -> "BFSRunStats":
        jax.block_until_ready((self.levels, self.comm_bytes,
                               self.overflowed, self.mode_counts,
                               self.sieve_hits))
        return self

    def to_host(self) -> dict:
        return {
            "levels": int(self.levels),
            "comm_bytes": float(self.comm_bytes),
            "overflowed": bool(self.overflowed),
            "mode_counts": {"dense": int(self.mode_counts[0]),
                            "queue": int(self.mode_counts[1]),
                            "bottom_up": int(self.mode_counts[2])},
            "sieve_hits": int(self.sieve_hits),
        }


jax.tree_util.register_dataclass(
    BFSRunStats,
    data_fields=["levels", "comm_bytes", "overflowed", "mode_counts",
                 "sieve_hits"],
    meta_fields=[])


@dataclasses.dataclass
class BFSResult:
    """One traversal's outputs; device-resident until explicitly synced.

    ``dist`` is the padded global (n, S) int32 distance matrix (sharded
    over the mesh); ``dist_host`` slices it to the logical vertex range
    and the actually-requested source columns.
    """

    dist: jax.Array
    run_stats: BFSRunStats
    n_logical: int
    n_sources: int             # actual requested sources (<= compiled S)

    def block(self) -> "BFSResult":
        jax.block_until_ready(self.dist)
        self.run_stats.block()
        return self

    @property
    def dist_host(self) -> np.ndarray:
        """Host view of the distances; the D2H copy is made once and
        cached (stats() and callers both read it)."""
        if not hasattr(self, "_dist_host"):
            self._dist_host = np.asarray(
                self.dist)[: self.n_logical, : self.n_sources]
        return self._dist_host

    def stats(self) -> BFSStats:
        """Materialize legacy host-side stats (syncs device -> host)."""
        h = self.run_stats.to_host()
        visited = int((self.dist_host < int(INF)).sum())
        return BFSStats(levels=h["levels"], visited=visited,
                        comm_bytes=h["comm_bytes"],
                        overflowed=h["overflowed"],
                        mode_counts=h["mode_counts"],
                        sieve_hits=h["sieve_hits"])


# ---------------------------------------------------------------------------
# Plan: validated static metadata for one (graph, opts, mesh, S) traversal
# ---------------------------------------------------------------------------

def _roofline_row(wire_bytes, hbm_bytes, flops, overlap: bool) -> dict:
    """Price one level variant on the TPU-v5e roofline.

    Three analytic terms per level: collective bytes over ICI bandwidth,
    memory traffic over HBM bandwidth, and elementwise work over peak
    FLOPs (bit tests and compares counted one op each).  Fused plans
    double-buffer the frontier generation, so the expand collective of
    level L+1 can overlap the tail compute of level L — modeled as
    ``max(collective, compute)``; unfused plans serialize the two
    (``sum``).  Absolute numbers use the v5e constants from
    launch/hlo_stats (the runtime here is CPU); the benchmark harness
    validates *relative* phase shape against parsed profiler traces
    after fitting one global calibration scale.
    """
    # deferred import: launch/hlo_stats is stdlib-only (import-light by
    # its package contract), so core -> launch here cannot cycle
    from repro.launch.hlo_stats import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

    t_coll = wire_bytes / ICI_BW
    t_comp = hbm_bytes / HBM_BW + flops / PEAK_FLOPS_BF16
    t_level = max(t_coll, t_comp) if overlap else t_coll + t_comp
    return {
        "wire_bytes": float(wire_bytes),
        "hbm_bytes": float(hbm_bytes),
        "flops": float(flops),
        "t_collective_s": t_coll,
        "t_compute_s": t_comp,
        "t_level_s": t_level,
        "bottleneck": "collective" if t_coll >= t_comp else "compute",
        "model": "overlap(max)" if overlap else "serial(sum)",
    }


@dataclasses.dataclass(frozen=True)
class BFSPlan:
    graph: "ShardedGraph"
    opts: BFSOptions
    mesh: Mesh
    axis: object               # str or tuple of mesh axis names
    axes_sizes: tuple
    num_sources: int           # compiled source-batch capacity S
    max_levels: int
    dense_strategy: Optional[ex.ExchangeStrategy] = None
    queue_strategy: Optional[ex.ExchangeStrategy] = None
    # 2-D (partition="2d") plans: the r x c edge blocks plus the two phase
    # strategies that replace the single dense exchange.
    partition: str = "1d"
    graph2d: Optional["ShardedGraph2D"] = None
    expand_strategy: Optional[ex.ExchangeStrategy] = None
    fold_strategy: Optional[ex.ExchangeStrategy] = None
    expand_sparse_strategy: Optional[ex.ExchangeStrategy] = None
    fold_sparse_strategy: Optional[ex.ExchangeStrategy] = None
    # resolved wire layout of the bottom-up frontier gather (the one dense
    # exchange that is not a registry strategy); "auto" resolves here at
    # plan time just like the per-phase strategies resolve above
    bottom_up_wire: str = "bytes"
    # resolved visited-sieve decision (BFSOptions.sieve="auto" resolves at
    # plan time: on when the plan has a reachable queue path and p > 1)
    sieve: bool = False
    # resolved fused fold/owner-update tail (BFSOptions.use_fused_tail;
    # "auto" resolves at plan time: on when the dense/fold phase ships
    # packed words — the fused kernel consumes them directly — and the
    # mode has a dense path to fuse)
    use_fused_tail: bool = False

    def describe(self) -> dict:
        """Static plan metadata (the non-per-run half of the old BFSStats)."""
        part = self.graph.part
        meta = {
            "mode": self.opts.mode,
            "partition": self.partition,
            "p": part.p,
            "n": part.n,
            "n_logical": part.n_logical,
            "shard_size": part.shard_size,
            "num_sources": self.num_sources,
            "max_levels": self.max_levels,
            "axes": self.axis if isinstance(self.axis, tuple) else (self.axis,),
            "axes_sizes": self.axes_sizes,
        }
        # sparse phases report their resolved payload layout: "ids" (raw
        # int32) or "compressed" (delta+varint uint8)
        def sparse_wire(strategy):
            return "ids" if strategy.wire == "bytes" else strategy.wire

        if self.partition == "2d":
            part2 = self.graph2d.part
            r, c, s = part2.r, part2.c, self.num_sources
            cap = self.opts.queue_cap
            b = part2.shard_size
            density = cap / b
            sieve_bytes = ((part2.p - 1) * fr.sieve_layout(b)[2] * 4
                           if self.sieve else 0)
            phase_bytes = {
                # per-phase byte split of every level variant: row phase
                # then column phase, dense bitmaps vs sparse id buffers
                "expand": self.expand_strategy.bytes_model(
                    part2.n, r, c, s, 1),
                "fold": self.fold_strategy.bytes_model(part2.n, r, c, s, 1),
                "expand_sparse": self.expand_sparse_strategy.bytes_model(
                    r, c, cap, 4, density),
                "fold_sparse": self.fold_sparse_strategy.bytes_model(
                    r, c, cap, 4, density),
            }
            meta.update({
                "grid": (r, c),
                "expand_exchange": self.expand_strategy.name,
                "fold_exchange": self.fold_strategy.name,
                "expand_sparse_exchange": self.expand_sparse_strategy.name,
                "fold_sparse_exchange": self.fold_sparse_strategy.name,
                # per-phase wire layout the plan resolved (what "auto"
                # actually picked)
                "wire_formats": {
                    "expand": self.expand_strategy.wire,
                    "fold": self.fold_strategy.wire,
                    "expand_sparse": sparse_wire(self.expand_sparse_strategy),
                    "fold_sparse": sparse_wire(self.fold_sparse_strategy),
                    "bottom_up": self.bottom_up_wire,
                },
                "sieve": self.sieve,
                # (no in_e_cap here: the bottom-up blocks build lazily at
                # compile time for auto plans; describe() must stay cheap)
                "e_cap": self.graph2d.e_cap,
                "phase_bytes": phase_bytes,
                # per-level exchange bytes of each mode a traversal can
                # take (mode_counts in BFSRunStats says how many of each
                # actually ran); queue levels add the sieve summary gather
                # when the plan resolved the sieve on
                "dense_level_bytes": (phase_bytes["expand"]
                                      + phase_bytes["fold"]),
                "queue_level_bytes": (phase_bytes["expand_sparse"]
                                      + phase_bytes["fold_sparse"]
                                      + sieve_bytes),
                "bottom_up_level_bytes": ex.bottomup_level_bytes(
                    part2.n, part2.p, s, 1, wire=self.bottom_up_wire),
            })
            # roofline latency terms per level variant (see _roofline_row):
            # HBM traffic = edge index reads (8B/edge) + frontier gather/
            # candidate scatter (1B/edge/source) + fold-width candidate
            # array passes + the dist read/write + mask tails
            e_p = self.graph2d.e_cap
            meta["use_fused_tail"] = self.use_fused_tail
            # byte passes only the *unfused* tail pays: the frontier pack
            # feeding the expand allgather, the c-segment row unpack the
            # expansion reads, the fold-word unpack, and the separate
            # new-frontier mask pass — all skipped by the carried packed
            # generation + fused fold/owner-update kernel
            elim_hbm = (c + 5) * b * s if self.use_fused_tail else 0
            elim_flops = (c + 2) * b * s if self.use_fused_tail else 0
            meta["roofline"] = {
                "dense": _roofline_row(
                    meta["dense_level_bytes"],
                    hbm_bytes=(8 * e_p + 2 * e_p * s
                               + (3 * r * b + 10 * b) * s - elim_hbm),
                    flops=(e_p + r * b + 4 * b) * s - elim_flops,
                    overlap=self.use_fused_tail),
                "queue": _roofline_row(
                    meta["queue_level_bytes"],
                    hbm_bytes=(8 * e_p + e_p * s + 16 * (r + c) * cap
                               + 8 * b * s),
                    flops=(e_p + (r + c) * cap) * s,
                    overlap=False),
                "bottom_up": _roofline_row(
                    meta["bottom_up_level_bytes"],
                    # in-edge blocks build lazily; the forward e_cap is the
                    # cheap same-order proxy describe() is allowed to use
                    hbm_bytes=8 * e_p + e_p * s + 8 * b * s,
                    flops=e_p * s,
                    overlap=self.use_fused_tail),
            }
        else:
            density = self.opts.queue_cap / part.shard_size
            sieve_bytes = ((part.p - 1) * fr.sieve_layout(part.shard_size)[2]
                           * 4 if self.sieve else 0)
            meta.update({
                "dense_exchange": self.dense_strategy.name,
                "queue_exchange": self.queue_strategy.name,
                "wire_formats": {
                    "dense": self.dense_strategy.wire,
                    "queue": sparse_wire(self.queue_strategy),
                    "bottom_up": self.bottom_up_wire,
                },
                "sieve": self.sieve,
                "e_cap": self.graph.e_cap,
                "in_e_cap": self.graph.in_e_cap,
                "dense_level_bytes": self.dense_strategy.bytes_model(
                    part.n, part.p, self.num_sources, 1, self.axes_sizes),
                "queue_level_bytes": self.queue_strategy.bytes_model(
                    part.p, self.opts.queue_cap, 4, density) + sieve_bytes,
                "bottom_up_level_bytes": ex.bottomup_level_bytes(
                    part.n, part.p, self.num_sources, 1,
                    wire=self.bottom_up_wire),
            })
            e_p, in_e = self.graph.e_cap, self.graph.in_e_cap
            shard, s = part.shard_size, self.num_sources
            cap = self.opts.queue_cap
            meta["use_fused_tail"] = self.use_fused_tail
            # unfused-only byte passes (1-D shape of the same list as the
            # 2-D branch: expand-side frontier pack, merged-word unpack,
            # separate new-frontier mask pass)
            elim_hbm = 5 * shard * s if self.use_fused_tail else 0
            elim_flops = 2 * shard * s if self.use_fused_tail else 0
            meta["roofline"] = {
                "dense": _roofline_row(
                    meta["dense_level_bytes"],
                    hbm_bytes=(8 * e_p + 2 * e_p * s
                               + (3 * part.n + 10 * shard) * s - elim_hbm),
                    flops=(e_p + part.n + 4 * shard) * s - elim_flops,
                    overlap=self.use_fused_tail),
                "queue": _roofline_row(
                    meta["queue_level_bytes"],
                    hbm_bytes=(8 * e_p + e_p * s + 16 * part.p * cap
                               + 8 * shard * s),
                    flops=(e_p + part.p * cap) * s,
                    overlap=False),
                "bottom_up": _roofline_row(
                    meta["bottom_up_level_bytes"],
                    hbm_bytes=8 * in_e + in_e * s + 8 * shard * s,
                    flops=in_e * s,
                    overlap=self.use_fused_tail),
            }
        return meta

    def plan_key(self) -> tuple:
        """Canonical hashable fingerprint of everything a compile depends
        on: graph content, options, mesh topology, partition scheme,
        source capacity and the *resolved* exchange strategies.

        Two plans with equal keys compile byte-identical executables, so
        the cross-graph ``EngineCache`` (serve/engine_cache.py) can hand
        out one engine for both.  Exchange strategies enter by resolved
        name — ``"auto"`` and the strategy it resolved to key the same.
        Graph identity is a content hash (``ShardedGraph.fingerprint``),
        cached on the container, so two independently built but
        block-identical graphs share engines too.
        """
        mesh_key = (tuple(self.mesh.axis_names),
                    tuple(int(self.mesh.shape[a])
                          for a in self.mesh.axis_names),
                    tuple(int(d.id) for d in self.mesh.devices.flat))
        o = self.opts
        opt_key = (o.mode, o.local_update, o.dedupe, o.queue_cap,
                   o.queue_threshold, o.bottom_up_threshold, o.use_kernel,
                   # wire formats key by what they *resolved* to: the
                   # packed-vs-bytes choice of each phase is in the
                   # resolved strategy names below; the bottom-up gather
                   # and the sieve have no registry strategy so their
                   # resolutions key here, as does the resolved fused tail
                   self.bottom_up_wire, self.sieve, self.use_fused_tail)
        strat_key = tuple(
            s.name if s is not None else None
            for s in (self.dense_strategy, self.queue_strategy,
                      self.expand_strategy, self.fold_strategy,
                      self.expand_sparse_strategy, self.fold_sparse_strategy))
        graph_fp = (self.graph2d.fingerprint() if self.partition == "2d"
                    else self.graph.fingerprint())
        axis_key = (tuple(self.axis) if isinstance(self.axis, tuple)
                    else self.axis)
        return ("bfs_plan", graph_fp, self.partition, mesh_key, axis_key,
                opt_key, strat_key, self.num_sources, self.max_levels)

    def estimated_device_bytes(self) -> int:
        """Upper-bound estimate of the device memory a compiled engine of
        this plan holds live: edge blocks (with the 1-D CSR view where
        the plan can take a queue level) + validity mask (engine-lifetime
        residents) plus two generations of (n, S) dist/frontier working
        buffers (one in flight, one being initialized — the dist buffer is
        donated so steady state never holds more).

        Derived from the same static shapes the byte models price, so the
        ``EngineCache`` budget can be enforced before compiling.  It
        deliberately ignores the cross-engine sharing of device blocks
        (engine.py dedups them per (mesh, axis, group)): counting each
        engine's blocks in full makes the estimate an upper bound, which
        is the safe direction for an eviction budget.  For a 2-D ``auto``
        plan the lazily built bottom-up blocks are priced at their exact
        padded capacity (``bottom_up_in_cap()``, a cached bincount —
        under degree skew it exceeds ``e_cap``, so pricing them at the
        forward blocks' size would undercount and break the bound).
        """
        if self.partition == "2d":
            g = self.graph2d
            n = g.part.n
            b = g.part.shard_size
            edge = 2 * g.p * g.e_cap * 4           # src_rowlocal + dst_fold
            if self.opts.mode == "auto":
                # in_src_global + in_dst_local and the (p, b) out-degrees
                edge += 2 * g.p * g.bottom_up_in_cap() * 4 + n * 4
            # packed phases keep a loop-live word array per device: the
            # gathered row words (c*Wb) and/or the fold words (r*Wb)
            wire = 0
            if self.expand_strategy.wire == "packed":
                wire += g.part.c * fr.packed_words(b) * 4
            if self.fold_strategy.wire == "packed":
                wire += g.part.r * fr.packed_words(b) * 4
            # compressed sparse phases keep encode + gathered decode
            # payloads live across the level; the sieve keeps the
            # replicated summary words
            if (self.expand_sparse_strategy.wire == "compressed"
                    or self.fold_sparse_strategy.wire == "compressed"):
                wire += 2 * g.part.p * fr.compressed_capacity(
                    self.opts.queue_cap, b)
            if self.sieve:
                wire += g.part.p * fr.sieve_layout(b)[2] * 4
            if self.use_fused_tail:
                # double-buffered frontier generation: the carried packed
                # words plus the kernel's emitted next-generation words
                # are both live across the level boundary (that overlap
                # window is the point), and the fused kernel keeps one
                # (32-row, S) dist tile of scratch in flight
                wire += 2 * fr.packed_words(b) * 4 + 32 * 4
        else:
            g = self.graph
            n = g.part.n
            edge = 2 * g.p * (g.e_cap + g.in_e_cap) * 4
            if takes_queue_levels(self.opts.mode, self.num_sources):
                # the CSR view: row offsets and targets in source order
                edge += g.p * (g.part.shard_size + 1 + g.e_cap) * 4
            # the packed candidate word array ((p*W, S) uint32) is live
            # across the dense exchange
            wire = (g.p * fr.packed_words(g.part.shard_size) * 4
                    if self.dense_strategy.wire == "packed" else 0)
            if self.queue_strategy.wire == "compressed":
                wire += 2 * g.p * fr.compressed_capacity(
                    self.opts.queue_cap, g.part.shard_size)
            if self.sieve:
                wire += g.p * fr.sieve_layout(g.part.shard_size)[2] * 4
            if self.use_fused_tail:
                # same double-buffered generation + kernel scratch as 2-D
                wire += 2 * fr.packed_words(g.part.shard_size) * 4 + 32 * 4
            if self.opts.use_kernel:
                # per-shard blocked adjacency resident on device for the
                # engine's lifetime (tile values + block row/col indices),
                # priced from the tile *count* alone — materializing the
                # dense tiles belongs to compile(), not cache admission
                kmax, blk = g.bsr_shard_caps()
                edge += g.p * kmax * (blk * blk * 4 + 2 * 4)
        s = self.num_sources
        work = 2 * (n * s * 4 + n * s * 1)         # dist (i32) + frontier (u8)
        return int(edge + n + work + wire * s)     # + 1-byte validity mask

    def compile(self) -> "BFSEngine":
        return BFSEngine(self)


_SPARSE_KINDS = ("queue", "expand_row_sparse", "fold_col_sparse")


def _resolve_strategy(kind: str, name: str, model_args: tuple,
                      wire_format: str = "bytes"):
    """Registry lookup, or byte-model auto-selection for name="auto".

    ``wire_format`` (``BFSOptions.wire_format``) resolves each phase's
    payload layout at plan time.  Dense kinds choose between raw uint8
    masks and the strategy's ``<name>_packed`` bitset twin; sparse kinds
    (queue / expand_row_sparse / fold_col_sparse) choose between raw
    int32 ids and the ``<name>_compressed`` delta+varint twin.  The
    option's tier maps onto what each kind implements:

      * ``"bytes"``      — the named strategy as registered.
      * ``"packed"``     — dense: the packed twin (error if none);
        sparse: raw ids (the bitset tier has no sparse analog — the
        compressed codec carries its own adaptive bitmap fallback).
      * ``"compressed"`` — sparse: the compressed twin (error if none);
        dense: the packed twin (the densest layout that kind has).
      * ``"auto"``       — whichever twin models fewer bytes for this
        plan's shapes; ties keep the base (no pack/codec work when
        nothing crosses the wire, e.g. p = 1).

    A name that already carries a twin suffix is an explicit choice and
    short-circuits the resolution; ``name="auto"`` spans every
    registered strategy of the wire formats the option admits.
    """
    sparse = kind in _SPARSE_KINDS
    suffix = "_compressed" if sparse else "_packed"
    if sparse:
        effective = {"bytes": "bytes", "packed": "bytes",
                     "compressed": "compressed",
                     "auto": "auto"}[wire_format]
    else:
        effective = {"bytes": "bytes", "packed": "packed",
                     "compressed": "packed", "auto": "auto"}[wire_format]
    if name == "auto":
        wire = None if effective == "auto" else effective
        return ex.select_exchange(kind, *model_args, wire=wire)
    if effective == "bytes" or name.endswith(suffix):
        return ex.get_exchange(kind, name)
    try:
        twin = ex.get_exchange(kind, name + suffix)
    except ValueError:
        if effective != "auto":
            raise ValueError(
                f"{kind} strategy {name!r} has no {suffix[1:]} variant; "
                f"use wire_format='bytes' or 'auto'") from None
        return ex.get_exchange(kind, name)
    if effective != "auto":
        return twin
    base = ex.get_exchange(kind, name)
    return (twin if twin.bytes_model(*model_args)
            < base.bytes_model(*model_args) else base)


def _resolve_sieve(sieve, mode: str, p: int, s: int) -> bool:
    """Resolve ``BFSOptions.sieve`` to the plan-time bool.

    The sieve filters queue-phase candidate ids against a replicated
    coarse visited summary *before* the collective, so it only applies
    where a queue path can run: not in pure dense mode, and only with a
    single source column (the summary is per vertex, not per source —
    multi-source plans keep it off even when asked).  ``"auto"`` turns
    it on exactly when the filter can save wire bytes: p > 1.
    """
    if not takes_queue_levels(mode, s):
        return False
    if sieve == "auto":
        return p > 1
    return bool(sieve)


def _resolve_bottom_up_wire(wire_format: str, n: int, p: int, s: int) -> str:
    """Packed-vs-bytes for the bottom-up frontier gather (not a registry
    strategy; same resolution rules as ``_resolve_strategy``)."""
    if wire_format == "packed":
        return "packed"
    if wire_format == "auto" and (
            ex.bottomup_level_bytes(n, p, s, wire="packed")
            < ex.bottomup_level_bytes(n, p, s)):
        return "packed"
    return "bytes"


def _resolve_fused_tail(use_fused_tail, mode: str, dense_wire: str) -> bool:
    """Resolve ``BFSOptions.use_fused_tail`` to the plan-time bool.

    The fused kernel consumes the *packed* merged candidate words of the
    dense (1-D) / fold (2-D) collective, so it only exists where that
    phase resolved to a packed wire — ``True`` on a bytes wire is a
    contradiction and fails loudly.  ``"auto"`` additionally requires a
    mode with a dense path on the steady critical path: pure queue mode
    re-packs per sparse level and only ever reaches the fused tail after
    a bottom-up escalation, so auto keeps it off there.
    """
    if use_fused_tail is False:
        return False
    packed = dense_wire == "packed"
    if use_fused_tail is True:
        if not packed:
            raise ValueError(
                "use_fused_tail=True needs the dense/fold phase on a "
                f"packed wire (resolved wire is {dense_wire!r}); set "
                "wire_format='packed' or 'auto', or drop the flag")
        return True
    return packed and mode in ("dense", "auto")


def normalize_ladder(ladder) -> tuple:
    """Canonicalize a batch-size bucket ladder: ints, deduped, ascending.

    The serving front-end compiles one engine per rung and routes every
    request to the smallest rung that fits, so the ladder is the whole
    set of compiled plans a lane can ever occupy — a malformed ladder
    must fail at configuration time, not on the first mid-sized request.
    """
    rungs = tuple(sorted({int(s) for s in ladder}))
    if not rungs:
        raise ValueError("bucket ladder must name at least one batch size")
    if rungs[0] < 1:
        raise ValueError(f"bucket ladder sizes must be >= 1 ({list(ladder)})")
    return rungs


def pick_bucket(n_sources: int, ladder) -> int:
    """Smallest ladder rung that fits ``n_sources`` (bucket routing).

    The engine already pads unused source columns on device (``run_async``
    accepts 1..S sources), so routing to the next-larger rung costs only
    the padded columns' device work — never a recompile.
    """
    n = int(n_sources)
    if n < 1:
        raise ValueError(f"n_sources must be >= 1 ({n_sources})")
    for s in normalize_ladder(ladder):
        if n <= s:
            return s
    raise ValueError(
        f"{n} sources exceed the largest bucket {max(ladder)} of ladder "
        f"{sorted(set(int(s) for s in ladder))}; add a larger rung or "
        "split the request")


def plan_ladder(graph, opts: BFSOptions = BFSOptions(), *,
                mesh: Optional[Mesh] = None, axis=None,
                ladder=(1, 8, 64), partition: Optional[str] = None) -> dict:
    """Plan one engine per batch-size bucket: ``{S: BFSPlan}`` ascending.

    The inference-serving idiom (sorted batch sizes, pad to bucket)
    applied to traversal: compiling a small ladder of source capacities
    once bounds the set of compiled executables while arbitrary request
    fan-outs route to the smallest fitting rung.  All rungs share the
    graph's device edge blocks (the per-(mesh, axis, group) upload dedup),
    so an extra rung costs roughly its (n, S) working buffers, not a
    second copy of the graph.
    """
    return {s: plan(graph, opts, mesh=mesh, axis=axis, num_sources=s,
                    partition=partition)
            for s in normalize_ladder(ladder)}


def plan(graph, opts: BFSOptions = BFSOptions(), *,
         mesh: Optional[Mesh] = None, axis=None,
         num_sources: int = 1, partition: Optional[str] = None) -> BFSPlan:
    """Validate options/topology and derive the static traversal shapes.

    ``num_sources`` fixes the compiled source-batch capacity S; a compiled
    engine accepts any 1..S sources per run without retracing.

    ``partition`` selects the scheme: ``"1d"`` (the paper's vertex blocks,
    default) or ``"2d"`` (edge blocks over an r x c grid — pass a mesh with
    two axes ``(rows, cols)``; each level's exchange is then a row
    allgather + column fold over r + c participants instead of one
    collective over all p shards).  ``None`` infers the scheme from the
    graph container, so callers holding a ``ShardedGraph2D`` need no flag;
    a 1-D graph is converted (and the conversion cached) on first use.
    """
    from repro.graphs.formats import ShardedGraph2D, to_2d

    opts.validate()
    part = graph.part
    s = int(num_sources)
    if num_sources < 1:
        raise ValueError(f"num_sources must be >= 1 ({num_sources})")
    if partition is None:
        partition = "2d" if isinstance(graph, ShardedGraph2D) else "1d"
    if partition not in ("1d", "2d"):
        raise ValueError(f"unknown partition scheme {partition!r}; "
                         "expected '1d' | '2d'")

    if opts.mode == "queue" and num_sources != 1:
        raise ValueError("queue frontier supports a single source "
                         f"(num_sources={num_sources})")
    if opts.use_kernel and opts.mode != "dense":
        # unsupported combos fail loudly instead of silently ignoring the
        # flag: the queue/auto level loops take the segment-scatter
        # expansion paths the kernel does not implement
        raise ValueError(
            f"use_kernel requires mode='dense' (got mode={opts.mode!r}); "
            "the Pallas bsr_spmm expansion has no queue/bottom-up analog")

    if partition == "2d":
        if opts.use_kernel:
            raise ValueError("use_kernel is a 1-D dense path (the blocked "
                             "adjacency is encoded per vertex shard); not "
                             "available with partition='2d'")
        if mesh is None:
            if part.p != 1:
                raise ValueError("pass a 2-axis mesh whose r*c equals the "
                                 f"graph's p={part.p}")
            mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                        ("rows", "cols"))
            axis = ("rows", "cols")
        axes = tuple(axis) if axis is not None else tuple(mesh.axis_names)
        if len(axes) != 2:
            raise ValueError(f"partition='2d' needs exactly two mesh axes "
                             f"(rows, cols); got {axes}")
        r, c = (int(mesh.shape[a]) for a in axes)
        if r * c != part.p:
            raise ValueError(f"mesh grid {r}x{c} does not multiply to the "
                             f"graph's p={part.p}")
        if isinstance(graph, ShardedGraph2D):
            # edge blocks are encoded for one specific grid shape; a
            # transposed/reshaped mesh would compile and silently traverse
            # wrong (gather indices clamp under jit)
            if (part.r, part.c) != (r, c):
                raise ValueError(
                    f"graph's edge blocks are laid out for a "
                    f"{part.r}x{part.c} grid; mesh is {r}x{c}")
            graph2d = graph
        else:
            graph2d = to_2d(graph, r, c)
        grid_args = (graph2d.part.n, r, c, s, 1)
        # sparse models take the plan's frontier density (cap relative to
        # the chunk size) so compressed twins price the same payload the
        # compiled loop ships
        sparse_args = (r, c, opts.queue_cap, 4,
                       opts.queue_cap / graph2d.part.shard_size)
        # the fold strategy resolves first: the fused-tail decision keys
        # off its resolved wire (the fused kernel consumes fold words)
        fold_strategy = _resolve_strategy(
            "fold_col", opts.fold_exchange, grid_args, opts.wire_format)
        return BFSPlan(
            graph=graph, opts=opts, mesh=mesh, axis=axes,
            axes_sizes=(r, c), num_sources=s,
            max_levels=opts.max_levels or part.n_logical,
            partition="2d", graph2d=graph2d,
            expand_strategy=_resolve_strategy(
                "expand_row", opts.expand_exchange, grid_args,
                opts.wire_format),
            fold_strategy=fold_strategy,
            expand_sparse_strategy=_resolve_strategy(
                "expand_row_sparse", opts.expand_sparse_exchange,
                sparse_args, opts.wire_format),
            fold_sparse_strategy=_resolve_strategy(
                "fold_col_sparse", opts.fold_sparse_exchange, sparse_args,
                opts.wire_format),
            bottom_up_wire=_resolve_bottom_up_wire(
                opts.wire_format, graph2d.part.n, part.p, s),
            sieve=_resolve_sieve(opts.sieve, opts.mode, part.p, s),
            use_fused_tail=_resolve_fused_tail(
                opts.use_fused_tail, opts.mode, fold_strategy.wire),
        )

    if isinstance(graph, ShardedGraph2D):
        raise ValueError("partition='1d' needs a 1-D ShardedGraph; this "
                         "graph holds 2-D edge blocks")

    if mesh is None:
        dev = jax.devices()[:1]
        mesh = Mesh(np.asarray(dev).reshape(1), ("bfs_p",))
        axis = "bfs_p"
        if part.p != 1:
            raise ValueError("pass a mesh whose total size equals part.p")
    axis = axis if axis is not None else tuple(mesh.axis_names)
    axis = tuple(axis) if isinstance(axis, (list, tuple)) else axis
    axes = axis if isinstance(axis, tuple) else (axis,)
    axes_sizes = tuple(mesh.shape[a] for a in axes)
    if int(np.prod(axes_sizes)) != part.p:
        raise ValueError(f"mesh axes {axes} of sizes {axes_sizes} do not "
                         f"multiply to the graph's p={part.p}")

    dense_strategy = _resolve_strategy(
        "dense", opts.dense_exchange,
        (part.n, part.p, s, 1, axes_sizes), opts.wire_format)
    return BFSPlan(
        graph=graph, opts=opts, mesh=mesh, axis=axis,
        axes_sizes=axes_sizes, num_sources=s,
        max_levels=opts.max_levels or part.n_logical,
        dense_strategy=dense_strategy,
        queue_strategy=_resolve_strategy(
            "queue", opts.queue_exchange,
            (part.p, opts.queue_cap, 4, opts.queue_cap / part.shard_size),
            opts.wire_format),
        bottom_up_wire=_resolve_bottom_up_wire(
            opts.wire_format, part.n, part.p, s),
        sieve=_resolve_sieve(opts.sieve, opts.mode, part.p, s),
        use_fused_tail=_resolve_fused_tail(
            opts.use_fused_tail, opts.mode, dense_strategy.wire),
    )


# ---------------------------------------------------------------------------
# Engine: AOT-compiled executables + device-resident graph buffers
# ---------------------------------------------------------------------------

class _BlockGroup:
    """Weakref-able holder for one group of uploaded device buffers.

    The per-graph dedup map (``graph._device_blocks``) stores these as
    *weak* values while each engine keeps a strong reference for its
    lifetime: concurrent engines of one graph share a single upload, and
    when the last engine holding a group dies (e.g. evicted from the
    serving ``EngineCache``) the device memory actually frees instead of
    being pinned forever by the graph object.
    """

    __slots__ = ("arrays", "__weakref__")

    def __init__(self, arrays):
        self.arrays = arrays


class BFSEngine:
    """A compiled traversal: run unlimited source sets with device-only work.

    Two AOT executables are built at construction:

      * ``_init_c(sources)``   — scatters the (S,) source vector into fresh
        (n, S) dist/frontier buffers on device.
      * ``_run_c(edges..., dist0, frontier0, valid)`` — the while-loop
        kernel.  ``dist0`` is donated: its (n, S) buffer is reused for the
        output distance matrix, so steady-state traversals allocate no new
        large buffers.  (``frontier0`` is not donated — the kernel has no
        same-shaped uint8 output to alias it to.)

    ``trace_count`` exposes how many times the kernel body has been traced;
    it must not grow across ``run()`` calls (asserted by the test suite).
    """

    def __init__(self, plan_: BFSPlan):
        self.plan = plan_
        _faults.fire("engine.compile", _faults.plan_tag(plan_))
        self._trace_count = 0
        opts, mesh = plan_.opts, plan_.mesh
        s = plan_.num_sources
        axis = plan_.axis
        # Pallas kernels compile for a TPU mesh; on any other platform
        # the TPU kernels can only run in interpret mode
        on_tpu = mesh.devices.flat[0].platform == "tpu"

        # The two partition schemes differ only in the per-shard loop body
        # and the edge-block encoding; everything below the dispatch —
        # sharding specs, device buffer cache, AOT compile with the donated
        # dist buffer, on-device source scatter — is shared.
        if plan_.partition == "2d":
            buf_owner = plan_.graph2d
            part = buf_owner.part
            shard_fn = _make_shard_fn_2d(
                part, buf_owner.n_edges, s, axis[0], axis[1], opts,
                plan_.max_levels, plan_.expand_strategy, plan_.fold_strategy,
                plan_.expand_sparse_strategy, plan_.fold_sparse_strategy,
                bottom_up_wire=plan_.bottom_up_wire, sieve=plan_.sieve,
                fused=plan_.use_fused_tail, on_tpu=on_tpu,
                on_trace=self._bump_trace)
            # only the auto hybrid's bottom-up level reads the in-edge
            # blocks and out-degrees; dense/queue engines neither build
            # nor upload them.  Group names carry the partition kind: a
            # to_2d view shares its parent's device-buffer dict, and the
            # two schemes' "edges" payloads differ.
            edge_groups = [("edges_2d", buf_owner.flat)]
            if opts.mode == "auto":
                edge_groups.append(("bottom_up_2d", buf_owner.bottom_up_flat))
        else:
            buf_owner = plan_.graph
            part = buf_owner.part
            edge_groups = [("edges", buf_owner.flat)]
            expand_fn, expand_packed, n_kernel_args = None, False, 0
            if opts.use_kernel:
                # the per-shard blocked adjacency rides the same sharded
                # upload path as the edge blocks (one more device group)
                expand_fn, expand_packed, kernel_arrays = \
                    self._build_kernel_expand(interpret=not on_tpu)
                edge_groups.append(("kernel_bsr", kernel_arrays))
                n_kernel_args = 3
            shard_fn = _make_shard_fn(
                part, buf_owner.n_edges, s, axis, plan_.axes_sizes, opts,
                plan_.max_levels, plan_.dense_strategy, plan_.queue_strategy,
                expand_fn=expand_fn, expand_emits_packed=expand_packed,
                n_kernel_args=n_kernel_args,
                bottom_up_wire=plan_.bottom_up_wire, sieve=plan_.sieve,
                fused=plan_.use_fused_tail, on_tpu=on_tpu,
                on_trace=self._bump_trace)
        n = part.n

        spec_edge = P(axis)
        spec_vert = P(axis, None)
        sh_edge = NamedSharding(mesh, spec_edge)
        sh_vert = NamedSharding(mesh, spec_vert)
        sh_repl = NamedSharding(mesh, P())
        self._sh_repl = sh_repl

        # Graph blocks + validity mask live on device for the engine's
        # lifetime; every run reuses them with zero H2D traffic.  They are
        # deduplicated per (mesh, axis, group) across engines — compiling
        # several option/S/mode variants of one graph must not duplicate
        # its largest buffers (a 2-D auto engine adds only the bottom-up
        # group on top of a dense engine's edge blocks).  The map holds
        # them *weakly* (engines hold the strong refs), so an evicted/
        # dropped engine set releases its device memory.  Engine compiles
        # run from multiple threads (EngineCache.get_or_compile holds no
        # lock while compiling), so the check-then-insert runs under the
        # cache's *per-graph* lock: concurrent engines of one graph
        # cannot upload a group twice, while compiles of unrelated
        # graphs never wait on each other's host bucketing + uploads.
        from repro.graphs.formats import device_block_cache

        self._block_holders = []
        blocks = device_block_cache(buf_owner)
        with blocks.lock:
            dev_cache = blocks.map

            def _cached(group, build):
                holder = dev_cache.get((mesh, axis, group))
                if holder is None:
                    holder = _BlockGroup(build())
                    dev_cache[(mesh, axis, group)] = holder
                self._block_holders.append(holder)
                return holder.arrays

            self._gbufs = ()
            for group, host_arrays in edge_groups:
                # dtype-preserving upload: edge/bottom-up blocks are int32,
                # the kernel group's adjacency tile values are float32
                self._gbufs += _cached(group, lambda ha=host_arrays: tuple(
                    jax.device_put(np.asarray(a), sh_edge)
                    for a in ha()))
            if plan_.partition == "1d" and takes_queue_levels(opts.mode, s):
                # the queue level's CSR view, sorted on the device from
                # the uploaded blocks rather than on the host
                self._gbufs += _cached("csr", lambda: jax.jit(jax.shard_map(
                    lambda sl, dg: fr.csr_view(sl, dg, part.shard_size),
                    mesh=mesh, in_specs=(spec_edge, spec_edge),
                    out_specs=(spec_edge, spec_edge), check_vma=False))(
                        *self._gbufs[:2]))
            self._valid = _cached("valid", lambda: jax.device_put(
                np.arange(n) < part.n_logical, sh_edge))
        n_edge_in = len(self._gbufs)

        mapped = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(spec_edge,) * n_edge_in + (spec_vert, spec_vert,
                                                 spec_edge),
            out_specs=(spec_vert, P(), P(), P(), P(), P()),
            check_vma=False,
        )

        dist_sds = jax.ShapeDtypeStruct((n, s), jnp.int32, sharding=sh_vert)
        front_sds = jax.ShapeDtypeStruct((n, s), jnp.uint8, sharding=sh_vert)
        src_sds = jax.ShapeDtypeStruct((s,), jnp.int32, sharding=sh_repl)

        self._run_c = jax.jit(mapped, donate_argnums=(n_edge_in,)).lower(
            *self._gbufs, dist_sds, front_sds, self._valid).compile()

        def init_fn(sources):
            self._bump_trace()
            with jax.named_scope(INIT_SCOPE):
                return fr.init_dist_frontier(sources, n, part.n_logical)

        self._init_c = jax.jit(
            init_fn, out_shardings=(sh_vert, sh_vert)).lower(src_sds).compile()

        # Traces spent building the two executables; run() must never add
        # to this (the engine-reuse tests pin trace_count to it).
        self.compile_traces = self._trace_count

    # ------------------------------------------------------------------ misc
    def estimated_device_bytes(self) -> int:
        """Device bytes this engine keeps live (plan-derived estimate;
        what the serving ``EngineCache`` charges against its budget)."""
        return self.plan.estimated_device_bytes()

    def compiled_hlo(self) -> str:
        """Optimized HLO text of the compiled traversal loop.

        What the wire-format benchmark parses (launch/hlo_stats
        ``collective_bytes``) to cross-check the analytic byte models
        against compiler-emitted collective buffer sizes — the measured
        half of the packed-vs-bytes ledger.
        """
        return self._run_c.as_text()

    def _bump_trace(self):
        self._trace_count += 1

    @property
    def trace_count(self) -> int:
        return self._trace_count

    def _build_kernel_expand(self, interpret: bool):
        """Pallas bsr_spmm frontier expansion, per shard.

        Each device's 128x128-blocked *transposed* adjacency slice
        (rows = global candidate ids, cols = the shard's local sources;
        candidates = A_shard^T @ f_local on the MXU, boolean semiring via
        sum + >0) travels as a shard_map operand like the edge blocks, so
        ``use_kernel=True`` runs on every shard of the multi-device 1-D
        loop — the old single-shard restriction baked the adjacency into
        the trace as a replicated constant.  With a packed dense wire the
        kernel path emits the per-shard-blocked uint32 candidate words
        directly (``frontier_expand_packed``), so the packed exchange
        consumes them with no separate pack step.

        Returns ``(expand_fn, emits_packed, host_arrays_fn)``;
        ``expand_fn(frontier, blocks_flat, block_rows, block_cols)`` runs
        inside the shard body on that shard's slices.
        """
        from repro.kernels.bsr_spmm import ops as spmm_ops

        graph = self.plan.graph
        part = graph.part
        p, shard, n = part.p, part.shard_size, part.n
        blocks, brs, bcs, row_pad, col_pad = graph.bsr_shards()
        kmax, blk = blocks.shape[1], blocks.shape[2]
        packed = self.plan.dense_strategy.wire == "packed"

        def host_arrays():
            return (blocks.reshape(-1), brs.reshape(-1), bcs.reshape(-1))

        def expand_fn(frontier, kb_flat, kbr, kbc):
            kb = kb_flat.reshape(kmax, blk, blk)
            f = frontier                                   # (shard, S)
            if col_pad > shard:
                f = jnp.pad(f, ((0, col_pad - shard), (0, 0)))
            if packed:
                return spmm_ops.frontier_expand_packed(
                    kb, kbr, kbc, f, n_rows_pad=row_pad, n_valid=n,
                    n_blocks=p, interpret=interpret)
            cand = spmm_ops.frontier_expand(kb, kbr, kbc, f,
                                            n_rows_pad=row_pad,
                                            interpret=interpret)
            return cand[:n]

        return expand_fn, packed, host_arrays

    # ------------------------------------------------------------------- run
    def run_async(self, sources) -> BFSResult:
        """Dispatch one traversal; returns un-blocked device arrays.

        ``sources`` may hold 1..S vertex ids; unused engine columns stay
        empty (their dist columns are all-INF and are sliced off by
        ``dist_host``).
        """
        s = self.plan.num_sources
        src_arr = validate_sources(sources, self.plan.graph.part.n_logical,
                                   max_sources=s)
        n_req = int(src_arr.shape[0])
        # ids are bounded by n_logical, which must fit the int32 dist/
        # source buffers — guard rather than let numpy wrap silently
        if src_arr.max() > np.iinfo(np.int32).max:
            raise ValueError("source ids exceed int32 range; the engine's "
                             "distance/source buffers are int32")
        padded = np.full((s,), -1, dtype=np.int32)
        padded[:n_req] = src_arr
        _faults.fire("engine.dispatch", _faults.plan_tag(self.plan))
        src_dev = jax.device_put(padded, self._sh_repl)

        dist0, frontier0 = self._init_c(src_dev)
        dist, levels, comm_bytes, overflowed, modes, sieve_hits = self._run_c(
            *self._gbufs, dist0, frontier0, self._valid)
        return BFSResult(
            dist=dist,
            run_stats=BFSRunStats(levels=levels, comm_bytes=comm_bytes,
                                  overflowed=overflowed, mode_counts=modes,
                                  sieve_hits=sieve_hits),
            n_logical=self.plan.graph.part.n_logical,
            n_sources=n_req,
        )

    def run(self, sources) -> BFSResult:
        """Run one traversal to completion (blocks until device work ends)."""
        return self.run_async(sources).block()
