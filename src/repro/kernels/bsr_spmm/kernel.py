"""Block-sparse SpMM Pallas TPU kernel — the BFS/GNN expansion hot loop.

TPU adaptation of the paper's per-vertex frontier expansion (DESIGN.md
§Hardware-adaptation): instead of the GPU/CPU idiom of per-thread neighbor
queues (paper fig. 2 lines 13-16), the adjacency is stored as block-CSR
(only nonempty 128x128 tiles materialized, sorted by block-row) and one BFS
level for a *batch* of S sources is the boolean-semiring product

    Y[n, S] = A[n, n] @ F[n, S]   (candidates = Y > 0)

which runs on the MXU at full tile alignment.  The same kernel with plain
sum semantics is the SpMM ``Ã·X`` of GCN-family GNNs (kernel_taxonomy §B.3).

Pallas specifics:
  * block indices arrive via ``PrefetchScalarGridSpec`` (scalar prefetch),
    so the data-dependent tile schedule is resolved in SMEM before each
    grid step — the standard Pallas block-sparse pattern.
  * grid is (d_tiles, K) with K fastest: for a fixed feature tile j, all
    blocks of one block-row are consecutive, so the output tile (row, j)
    is revisited contiguously and accumulates in VMEM; it is zeroed on
    first visit (``row_changed``) and flushed automatically on the last.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 128


def _spmm_kernel(br_ref, bc_ref, blocks_ref, x_ref, y_ref):
    """One grid step: y[br[k], j] += blocks[k] @ x[bc[k], j]."""
    k = pl.program_id(1)

    # Zero the accumulator on the first visit of this output tile: either
    # the very first block, or the block-row just changed.
    row_changed = jnp.where(k == 0, True, br_ref[k] != br_ref[jnp.maximum(k - 1, 0)])

    @pl.when(row_changed)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    a = blocks_ref[0]          # (B, B)
    x = x_ref[...]             # (B, dt)
    y_ref[...] += jnp.dot(a, x.astype(a.dtype),
                          preferred_element_type=y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_rows_pad", "block", "d_tile", "interpret"))
def bsr_spmm(blocks: jnp.ndarray, block_rows: jnp.ndarray,
             block_cols: jnp.ndarray, x: jnp.ndarray, *, n_rows_pad: int,
             block: int = DEFAULT_BLOCK, d_tile: int = DEFAULT_BLOCK,
             interpret: bool = False) -> jnp.ndarray:
    """Y = A @ X with A in block-CSR (blocks sorted by block_rows).

    blocks: (K, B, B) tile values; block_rows/block_cols: (K,) int32;
    x: (n_cols_pad, d).  Returns (n_rows_pad, d) f32.
    """
    k_blocks, b0, b1 = blocks.shape
    assert b0 == b1 == block, (blocks.shape, block)
    n, d = x.shape
    assert n % block == 0 and n_rows_pad % block == 0
    d_pad = -(-d // d_tile) * d_tile
    if d_pad != d:
        x = jnp.pad(x, ((0, 0), (0, d_pad - d)))
    d_tiles = d_pad // d_tile

    grid = (d_tiles, k_blocks)
    out = pl.pallas_call(
        _spmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_rows, block_cols
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block, block),
                             lambda j, k, br, bc: (k, 0, 0)),
                pl.BlockSpec((block, d_tile),
                             lambda j, k, br, bc: (bc[k], j)),
            ],
            out_specs=pl.BlockSpec((block, d_tile),
                                   lambda j, k, br, bc: (br[k], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_rows_pad, d_pad), jnp.float32),
        interpret=interpret,
        name="bsr_spmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_rows, block_cols, blocks, x)
    return out[:, :d]


# word rows per bitpack grid step (Mosaic's second-minor block dimension
# must be divisible by 8): 8 output word rows from 256 mask rows
PACK_ROWS = 8


def _bitpack_kernel(x_ref, out_ref):
    """One grid step: fold a (256, S) 0/1 tile into 8 (1, S) uint32 word
    rows — bit ``i`` of word row ``r`` is tile row ``32*r + i``
    (LSB-first, the ``frontier.pack_bits`` layout).  Sums in int32 and
    bit-casts back: Mosaic has no unsigned reduction, and the shifted
    bits are disjoint, so the sum is their OR."""
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (32, 1), 0)
    for r in range(PACK_ROWS):
        bits = (x_ref[pl.ds(r * 32, 32), :] > 0).astype(jnp.uint32)
        packed = (bits << shifts).astype(jnp.int32)
        out_ref[pl.ds(r, 1), :] = jax.lax.bitcast_convert_type(
            packed.sum(axis=0, keepdims=True), jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitpack_words(mask: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Pack a ``(32*W, S)`` candidate mask into ``(W, S)`` uint32 words on
    device — the packed-wire emission of the kernel expansion path.

    The row count must be 32-aligned (the bsr_spmm output rows are padded
    to 128, so a whole-output pack always is); unaligned *segmented*
    packing falls back to ``frontier.pack_bits`` in the ops wrapper.
    """
    m, s = mask.shape
    assert m % 32 == 0, m
    w = m // 32
    w8 = -(-w // PACK_ROWS) * PACK_ROWS
    if w8 != w:
        mask = jnp.pad(mask, ((0, (w8 - w) * 32), (0, 0)))
    out = pl.pallas_call(
        _bitpack_kernel,
        grid=(w8 // PACK_ROWS,),
        in_specs=[pl.BlockSpec((PACK_ROWS * 32, s), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((PACK_ROWS, s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w8, s), jnp.uint32),
        interpret=interpret,
        name="bitpack_words",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(mask)
    return out[:w]
