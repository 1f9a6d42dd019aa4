"""Seeded graph generators, run on the device in one jitted call.

Each generator draws an undirected edge list from ``--seed`` and
returns it deduplicated, without self loops, and symmetrized (every
edge stored both ways), as host ``int32`` arrays ``(src, dst)``.  Where
the configuration gives ``undirected_edges``, a seeded random subset of
exactly that many distinct edges is kept: the engine compiles the edge
count into its program, so a count that moved with the seed would
compile a new program for every seed.

* ``kronecker``: the Graph500 generator (specification, section 3):
  each edge picks one of four quadrants per bit of its endpoints with
  probabilities A/B/C/D; the vertex labels are then permuted with a
  seeded permutation, so the hubs do not sit at low ids.
* ``erdos_renyi``: G(n, M) with M = n * avg_degree / 2 uniform pairs.

The draw, the label permutation and the sort that finds duplicates run
on the device; the host only compacts the kept edges.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.common import rng


def prng_key(seed: int):
    """A threefry key from any whole number (``PRNGKey`` truncates seeds
    beyond 32 bits when 64-bit mode is off)."""
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(words)


def _canonical_unique(lo, hi):
    """Sort canonical pairs and mark the first copy of each, minus self
    loops; returns ``(lo, hi, keep)`` on the device."""
    lo, hi = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           (lo[1:] == lo[:-1]) & (hi[1:] == hi[:-1])])
    return lo, hi, (~dup) & (lo != hi)


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
    return _canonical_unique(perm[row], perm[col])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _erdos_renyi(key, n, m):
    k_u, k_v = jax.random.split(key)
    u = jax.random.randint(k_u, (m,), 0, n, jnp.int32)
    v = jax.random.randint(k_v, (m,), 0, n, jnp.int32)
    return _canonical_unique(u, v)


def num_vertices(cfg: dict) -> int:
    if cfg["generator"] == "kronecker":
        return 1 << int(cfg["scale"])
    if cfg["generator"] == "erdos_renyi":
        return int(cfg["n"])
    raise KeyError(f"unknown generator {cfg['generator']!r}")


def generate(cfg: dict, seed: int):
    """``(src, dst, n)`` for a configuration ``cfg`` (its ``generator``
    names the kind, its other keys give the sizes) and ``seed``."""
    key = prng_key(seed)
    kind = cfg["generator"]
    n = num_vertices(cfg)
    gen = cfg
    if kind == "kronecker":
        lo, hi, keep = _kronecker(key, int(gen["scale"]),
                                  int(gen["edgefactor"]), float(gen["a"]),
                                  float(gen["b"]), float(gen["c"]))
    else:
        m = int(n * float(gen["avg_degree"]) / 2)
        lo, hi, keep = _erdos_renyi(key, n, m)
    keep = np.asarray(keep)
    u = np.asarray(lo)[keep]
    v = np.asarray(hi)[keep]
    target = cfg.get("undirected_edges")
    if target is not None:
        if u.size < target:
            raise ValueError(f"seed {seed} gave {u.size} distinct edges, "
                             f"fewer than undirected_edges={target}")
        pick = np.sort(rng(seed, 6).choice(u.size, int(target),
                                           replace=False))
        u, v = u[pick], v[pick]
    return np.concatenate([u, v]), np.concatenate([v, u]), n
