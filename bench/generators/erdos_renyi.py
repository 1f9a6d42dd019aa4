"""G(n, M): M = n * avg_degree / 2 uniform vertex pairs.  Sizes: ``n``,
``avg_degree``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness.graphgen import canonical_unique


def num_vertices(cfg: dict) -> int:
    return int(cfg["n"])


def draw(cfg: dict, key):
    n = num_vertices(cfg)
    return _erdos_renyi(key, n, int(n * float(cfg["avg_degree"]) / 2))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _erdos_renyi(key, n, m):
    k_u, k_v = jax.random.split(key)
    u = jax.random.randint(k_u, (m,), 0, n, jnp.int32)
    v = jax.random.randint(k_v, (m,), 0, n, jnp.int32)
    return canonical_unique(u, v)
