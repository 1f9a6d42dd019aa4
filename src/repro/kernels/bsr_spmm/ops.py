"""Jit'd public wrappers around the block-sparse SpMM kernel.

Every wrapper compiles the Pallas kernel for the TPU unless the caller
passes ``interpret=True``; no wrapper picks interpret mode on its own.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.bsr_spmm.kernel import (DEFAULT_BLOCK, bitpack_words,
                                           bsr_spmm)
from repro.kernels.bsr_spmm.ref import bsr_spmm_ref


def spmm(blocks, block_rows, block_cols, x, *, n_rows_pad,
         block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Block-sparse A @ X through the Pallas kernel."""
    return bsr_spmm(blocks, block_rows, block_cols, x, n_rows_pad=n_rows_pad,
                    block=block, interpret=interpret)


def frontier_expand(blocks, block_rows, block_cols, frontier, *, n_rows_pad,
                    block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Batched BFS frontier expansion: (A @ F) > 0 over the MXU.

    frontier: (n_cols_pad, S) uint8 — S simultaneous sources.  For S < 128
    the lane dimension is padded; batching sources to a multiple of 128 is
    what makes the TPU formulation profitable.
    """
    y = spmm(blocks, block_rows, block_cols, frontier.astype(jnp.float32),
             n_rows_pad=n_rows_pad, block=block, interpret=interpret)
    return (y > 0).astype(jnp.uint8)


def pack_branch(n_valid: int, n_blocks: int) -> str:
    """Which pack ``frontier_expand_packed`` runs for these shapes:
    ``"pallas"`` (the ``bitpack_words`` kernel) when each owner segment
    is word-aligned, else ``"jnp"`` (``frontier.pack_bits``)."""
    seg = n_valid // n_blocks
    assert seg * n_blocks == n_valid, (n_valid, n_blocks)
    return "pallas" if seg % 32 == 0 else "jnp"


def frontier_expand_packed(blocks, block_rows, block_cols, frontier, *,
                           n_rows_pad, n_valid, n_blocks,
                           block: int = DEFAULT_BLOCK,
                           interpret: bool = False):
    """Kernel expansion emitting *packed* candidate words.

    Runs the bsr_spmm expansion, then packs the boolean candidates into
    the per-owner-blocked uint32 bitset layout the packed dense exchange
    ships (``n_blocks`` segments of ``n_valid / n_blocks`` bits, each
    padded to whole words — ``frontier.pack_bits`` semantics).  When the
    segment size is word-aligned the pack itself runs as the Pallas
    ``bitpack_words`` kernel (blocked == flat packing in that case); an
    unaligned segment falls back to the jnp pack, fused into the same
    jit (``pack_branch`` names the branch).  Returns
    ``(n_blocks * ceil(seg/32), S)`` uint32.
    """
    y = spmm(blocks, block_rows, block_cols,
             frontier.astype(jnp.float32), n_rows_pad=n_rows_pad,
             block=block, interpret=interpret)
    if pack_branch(n_valid, n_blocks) == "pallas":
        return bitpack_words(y[:n_valid], interpret=interpret)
    from repro.core.frontier import pack_bits
    return pack_bits((y[:n_valid] > 0).astype(jnp.uint8), n_blocks)


def spmm_reference(blocks, block_rows, block_cols, x, *, n_rows_pad):
    return bsr_spmm_ref(blocks, block_rows, block_cols, x,
                        n_rows_pad=n_rows_pad)
