"""Ahead-of-time compiles of the BFS Pallas kernels for a described TPU v5e.

Interpret-mode parity tests cannot see what Mosaic refuses (block shapes
off the (8, 128) tiling, unsigned reductions, VMEM over-use).  These
tests compile ``fold_update``, ``bitpack_words`` and ``bsr_spmm`` for a
``v5e:2x2`` topology that is described, not attached, at the ``rmat_1m``
4-shard shape (m = 2**20 / 4 = 262,144 owned rows), and check that the
kernel reached the compiled program as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, so every worker
collects the same tests and only the worker running this file loads it.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.bsr_spmm.kernel import bitpack_words, bsr_spmm
from repro.kernels.fold_update import fold_update

M = (1 << 20) // 4          # rmat_1m owned rows per shard at p = 4
K_BLOCKS = 64               # adjacency tiles in the compiled bsr_spmm


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compiled_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("s", [1, 8, 128])
def test_fold_update_compiles_for_v5e(one_chip, s):
    words = jax.ShapeDtypeStruct((M // 32, s), jnp.uint32, sharding=one_chip)
    dist = jax.ShapeDtypeStruct((M, s), jnp.int32, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda w, d: fold_update(w, d, 3, use_pallas=True), words, dist)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("s", [1, 8, 128])
def test_bitpack_words_compiles_for_v5e(one_chip, s):
    mask = jax.ShapeDtypeStruct((M, s), jnp.float32, sharding=one_chip)
    hlo = _compiled_hlo(bitpack_words, mask)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("s", [1, 8, 128])
def test_bsr_spmm_compiles_for_v5e(one_chip, s):
    blocks = jax.ShapeDtypeStruct((K_BLOCKS, 128, 128), jnp.float32,
                                  sharding=one_chip)
    idx = jax.ShapeDtypeStruct((K_BLOCKS,), jnp.int32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((M, s), jnp.float32, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda b, r, c, f: bsr_spmm(b, r, c, f, n_rows_pad=M),
        blocks, idx, idx, x)
    assert "tpu_custom_call" in hlo
