"""Fused fold-merge + owner-update tail of a dense BFS level.

After the dense (1-D) or fold (2-D) collective, the unfused level tail is
three separate XLA ops serialized on the critical path:

    own  = frontier.unpack_bits(words, m)      # (m, S) uint8 materialized
    new  = (own > 0) & (dist == INF)           # (m, S) bool materialized
    dist = where(new, level, dist)

plus a fourth — ``pack_bits(new)`` — when the *next* level's expand-phase
collective wants packed words again.  This module fuses all of them into
one pass over the received candidate words: each uint32 word is bit-tested
directly against 32 rows of ``dist``, depths are written, and the next
frontier is emitted **both** as the byte mask the queue/stats paths read
and as packed words ready for the next level's collective — the
double-buffered frontier generation that lets XLA issue the expand
collective of level L+1 before the owner-update scatter of level L
retires (ISSUE 9 / ROADMAP "Profile-driven latency hiding").

Two implementations behind one dispatcher, mirroring ``bsr_spmm.ops``:

* ``_fold_update_pallas`` — the TPU kernel: grid ``(W8 / 8,)``, one
  ``(8, S)`` word block + one ``(256, S)`` dist tile per step (the
  smallest blocks Mosaic's (8, 128) tiling rule admits for any S), level
  via scalar prefetch.
* ``_fold_update_jnp`` — a single fused jnp expression for non-TPU
  backends.  Unlike ``bsr_spmm`` we do *not* run the Pallas kernel in
  interpret mode on the engine hot path: interpret mode executes the
  grid as a host loop, which would swamp the very tail latency this
  kernel exists to remove.  Tests ask for the Pallas path explicitly
  (``use_pallas=True, interpret=True``) on small shapes to keep both
  implementations bit-identical.

Layout contract (``frontier.pack_bits``): bit ``i`` of word ``w`` is
vertex ``w*32 + i`` (LSB-first); pad bits beyond ``m`` must be zero —
callers mask invalid vertices *before* the collective, so every set bit
is a genuine candidate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frontier import INF, packed_words

# Python-int mirror of frontier.INF: a closed-over jax array would trip
# pallas' captured-constant check inside the kernel body.
_INF = int(INF)


# word rows per grid step: Mosaic blocks need a second-minor dimension
# divisible by 8, so each step takes 8 word rows and their 256 dist rows
ROWS = 8


def _fold_update_kernel(level_ref, words_ref, dist_ref,
                        dist_out, new_out, words_out):
    """One grid step: bit-test 8 uint32 word rows against 256 dist rows.

    Emits the updated dist tile, the new-vertex byte mask, and the new
    frontier re-packed as 8 word rows (only newly discovered vertices
    carry into the next generation, so the output words are exactly
    ``pack_bits(new_mask)``).  The re-pack sums in int32 and bit-casts
    back: Mosaic has no unsigned reduction, and the 32 shifted bits are
    disjoint, so the sum is their OR.
    """
    lv = level_ref[0]
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (32, 1), 0)
    for r in range(ROWS):
        rows = pl.ds(r * 32, 32)
        d = dist_ref[rows, :]                        # (32, S) int32
        w = words_ref[pl.ds(r, 1), :]                # (1, S) uint32
        new = (((w >> shifts) & jnp.uint32(1)) > 0) & (d == _INF)
        dist_out[rows, :] = jnp.where(new, lv, d)
        new_out[rows, :] = new.astype(jnp.uint8)
        packed = (new.astype(jnp.uint32) << shifts).astype(jnp.int32)
        words_out[pl.ds(r, 1), :] = jax.lax.bitcast_convert_type(
            packed.sum(axis=0, keepdims=True), jnp.uint32)


def _fold_update_pallas(words, dist, level, *, interpret: bool):
    w, s = words.shape
    m = dist.shape[0]
    w8 = -(-w // ROWS) * ROWS
    if w8 != w:
        words = jnp.pad(words, ((0, w8 - w), (0, 0)))
    if w8 * 32 != m:
        # pad rows read INF but their word bits are zero, so new == 0 and
        # the padded dist rows round-trip untouched
        dist = jnp.pad(dist, ((0, w8 * 32 - m), (0, 0)),
                       constant_values=INF)
    level_arr = jnp.asarray(level, jnp.int32).reshape(1)
    tile = ROWS * 32
    dist2, new, new_words = pl.pallas_call(
        _fold_update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                   # level
            grid=(w8 // ROWS,),
            in_specs=[
                pl.BlockSpec((ROWS, s), lambda i, lv: (i, 0)),   # words
                pl.BlockSpec((tile, s), lambda i, lv: (i, 0)),   # dist
            ],
            out_specs=[
                pl.BlockSpec((tile, s), lambda i, lv: (i, 0)),   # dist'
                pl.BlockSpec((tile, s), lambda i, lv: (i, 0)),   # new mask
                pl.BlockSpec((ROWS, s), lambda i, lv: (i, 0)),   # new words
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((w8 * 32, s), jnp.int32),
            jax.ShapeDtypeStruct((w8 * 32, s), jnp.uint8),
            jax.ShapeDtypeStruct((w8, s), jnp.uint32),
        ],
        interpret=interpret,
        name="fold_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(level_arr, words, dist)
    return dist2[:m], new[:m], new_words[:w]


def _fold_update_jnp(words, dist, level):
    """Fused tail as one jnp expression (non-TPU backends).

    A single elementwise chain over the (W, 32, S) bit view — XLA fuses
    the unpack-test-update-repack into one loop with no (m, S) uint8
    candidate array or standalone repack between the collective and the
    next level's expand.
    """
    w, s = words.shape
    m = dist.shape[0]
    pad = w * 32 - m
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, None, :] >> shifts[None, :, None]) & jnp.uint32(1)
    bits = bits.reshape(w * 32, s)
    if pad:
        bits = bits[:m]
    new = (bits > 0) & (dist == INF)
    dist2 = jnp.where(new, jnp.int32(level), dist)
    nw = jnp.pad(new, ((0, pad), (0, 0))) if pad else new
    new_words = (nw.astype(jnp.uint32).reshape(w, 32, s)
                 << shifts[None, :, None]).sum(axis=1, dtype=jnp.uint32)
    return dist2, new.astype(jnp.uint8), new_words


def fold_update(words, dist, level, *, use_pallas: bool = False,
                interpret: bool = False):
    """Fused dense-tail update: merge words into dist, emit next frontier.

    Args:
      words: ``(W, S)`` uint32 merged candidate words for this shard's
        owned vertex block, ``W == packed_words(m)``, pad bits zero.
      dist: ``(m, S)`` int32 depths (INF = undiscovered).
      level: scalar int32 depth to write for newly discovered vertices.
      use_pallas: the Pallas kernel (the engine asks for it on a TPU
        mesh) or the fused jnp expression (every other backend).
      interpret: run the Pallas kernel in interpret mode — for tests on
        the CPU only; never on the engine path.

    Returns ``(dist', new_mask, new_words)`` — updated ``(m, S)`` int32
    depths, the ``(m, S)`` uint8 newly-discovered mask, and the ``(W, S)``
    uint32 packed next-frontier words (``pack_bits(new_mask)``).
    """
    w, s = words.shape
    m = dist.shape[0]
    if w != packed_words(m):
        raise ValueError(f"words rows {w} != packed_words({m})="
                         f"{packed_words(m)}")
    if dist.shape[1] != s:
        raise ValueError(f"dist batch {dist.shape[1]} != words batch {s}")
    if use_pallas:
        return _fold_update_pallas(words, dist, level, interpret=interpret)
    return _fold_update_jnp(words, dist, level)
