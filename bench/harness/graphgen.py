"""Seeded graphs, drawn on the device in one jitted call.

A configuration's ``generator`` names a graph family, a file
``generators/<generator>.py`` of its own with two functions:

* ``num_vertices(cfg)``: the vertex count;
* ``draw(cfg, key)``: the jitted device draw of an undirected edge list
  from a threefry ``key``, returned as ``canonical_unique`` returns it.

What every family shares is here: the key from ``--seed``, the seeded
subset of exactly ``undirected_edges`` distinct edges where the
configuration gives that count (the engine compiles the edge count into
its program, so a count that moved with the seed would compile a new
program for every seed), and the symmetrized host ``int32`` arrays
``(src, dst)``, deduplicated and without self loops, every edge stored
both ways.  The draw and the sort that finds duplicates run on the
device; the host only compacts the kept edges.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.common import rng
from harness.spec import BENCH_DIR, load


def prng_key(seed: int):
    """A threefry key from any whole number (``PRNGKey`` truncates seeds
    beyond 32 bits when 64-bit mode is off)."""
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(words)


def canonical_unique(lo, hi):
    """Sort canonical pairs and mark the first copy of each, minus self
    loops; returns ``(lo, hi, keep)`` on the device."""
    lo, hi = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           (lo[1:] == lo[:-1]) & (hi[1:] == hi[:-1])])
    return lo, hi, (~dup) & (lo != hi)


def family(cfg: dict):
    """The module of the benchmark's ``generators/<generator>.py``."""
    return load(BENCH_DIR, "generators", cfg["generator"])


def generate(cfg: dict, seed: int, gen=None):
    """``(src, dst, n)`` for a configuration ``cfg`` (its ``generator``
    names the family, its other keys give the sizes) and ``seed``;
    ``gen`` is the family's module, found by name where not given."""
    gen = gen or family(cfg)
    n = gen.num_vertices(cfg)
    lo, hi, keep = gen.draw(cfg, prng_key(seed))
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
