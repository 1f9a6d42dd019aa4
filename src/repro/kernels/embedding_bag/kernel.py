"""EmbeddingBag Pallas TPU kernel — the recsys lookup hot path.

JAX has no native ``EmbeddingBag`` (kernel_taxonomy §B.6): the framework
implements it as gather + ``segment_sum`` (ref.py) and, for the hot path,
as this scalar-prefetch Pallas kernel: bag indices are prefetched to SMEM
and drive the ``index_map`` of the table operand, so each grid step DMAs
exactly one embedding row from HBM into VMEM and accumulates it into the
output row — no (B, L, D) gather intermediate is ever materialized.

Padding convention: ``index < 0`` marks an empty bag slot and contributes
zero (the row DMA still happens — data-independent schedule — but is
masked in the accumulate; on TPU this trades a wasted fetch for a fully
static pipeline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, table_ref, out_ref):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    valid = (idx_ref[b, l] >= 0).astype(out_ref.dtype)
    out_ref[...] += table_ref[...] * valid


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_sum(indices: jnp.ndarray, table: jnp.ndarray, *,
                      interpret: bool = True) -> jnp.ndarray:
    """Sum-mode bag lookup. indices: (B, L) int32 (-1 pads); table: (V, D).

    Returns (B, D) in the table dtype (f32 accumulation).
    """
    bsz, bag = indices.shape
    v, d = table.shape
    out = pl.pallas_call(
        _bag_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # indices
            grid=(bsz, bag),
            in_specs=[
                pl.BlockSpec((1, d), lambda b, l, idx: (jnp.maximum(idx[b, l], 0), 0)),
            ],
            out_specs=pl.BlockSpec((1, d), lambda b, l, idx: (b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(indices, table.astype(jnp.float32))
    return out.astype(table.dtype)
