"""The Graph500 generator (specification, section 3).

Each edge picks one of four quadrants per bit of its endpoints with
probabilities A/B/C/D; the vertex labels are then permuted with a
seeded permutation, so the hubs do not sit at low ids.  Sizes:
``scale`` (2**scale vertices), ``edgefactor`` (edges drawn per vertex),
``a``, ``b``, ``c``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness.graphgen import canonical_unique


def num_vertices(cfg: dict) -> int:
    return 1 << int(cfg["scale"])


def draw(cfg: dict, key):
    return _kronecker(key, int(cfg["scale"]), int(cfg["edgefactor"]),
                      float(cfg["a"]), float(cfg["b"]), float(cfg["c"]))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _kronecker(key, scale, edgefactor, a, b, c):
    n = 1 << scale
    m = edgefactor * n
    k_bits, k_perm = jax.random.split(key)

    def one_bit(i, carry):
        row, col = carry
        r = jax.random.uniform(jax.random.fold_in(k_bits, i), (m,))
        # quadrants in order A (0,0), B (0,1), C (1,0), D (1,1)
        row_bit = r >= a + b
        col_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        return (row | (row_bit.astype(jnp.int32) << i),
                col | (col_bit.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    row, col = jax.lax.fori_loop(0, scale, one_bit, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    return canonical_unique(perm[row], perm[col])
