"""Subprocess harness: analytic byte model vs HLO-parsed collective bytes.

Compiles each exchange strategy on 8 forced host devices, parses the
optimized HLO for collective ops, and checks the per-chip received-byte
model in core/exchange.py against what XLA actually emits.  This pins the
paper-reproduction numbers (benchmarks/run.py tables) to compiler ground
truth.  Exits nonzero on mismatch.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.launch import host_devices  # noqa: E402

host_devices(8)  # must precede the jax import below

import functools  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core import exchange as ex  # noqa: E402
from repro.core import frontier as fr  # noqa: E402
from repro.launch.hlo_stats import collective_bytes  # noqa: E402


def compile_and_parse(fn, in_specs, out_specs, arg_shapes, mesh):
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    lowered = jax.jit(mapped).lower(*arg_shapes)
    return collective_bytes(lowered.compile().as_text())


def main():
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs).reshape(8), ("p",))
    p = 8
    n, s = 4096, 4
    cap = 256
    ok = True

    for strategy in ex.DENSE_STRATEGIES:
        fn = functools.partial(ex.exchange_dense, axis="p", strategy=strategy)
        got = compile_and_parse(
            fn, P(None, None), P("p", None),
            (jax.ShapeDtypeStruct((n, s), jnp.uint8),), mesh)
        want = ex.dense_level_bytes(strategy, n, p, s, 1, axes_sizes=[p])
        # HLO counts the op's OUTPUT bytes once per device; relate the two:
        # all-gather output = p*n*s (received (p-1)/p of it); all-to-all
        # output = n*s; reduce-scatter output = n*s/p (bf16 -> 2B items).
        rel = got["total"] / max(want, 1)
        line = (f"dense/{strategy:16s} model={want:>12.0f}B "
                f"hlo_total={got['total']:>12.0f}B ratio={rel:6.3f} {got}")
        print(line)
        # sanity: the model must be within ~2.5x of HLO accounting and the
        # ORDERING must hold (baseline >> direct)
        ok &= 0.2 < rel < 2.6
    base = ex.dense_level_bytes("allgather_merge", n, p, s, 1)
    opt = ex.dense_level_bytes("alltoall_direct", n, p, s, 1)
    ok &= base / opt > p * 0.9  # paper claim: baseline grows ~linearly in p
    # packed-bitset claim: the _packed twin models 8x below its bytes twin
    # (exact here: the 512-vertex shard is word-aligned), and the HLO
    # ratios above already pinned the packed models to compiler output
    packed = ex.dense_level_bytes("alltoall_direct_packed", n, p, s, 1)
    print(f"dense/packed-vs-bytes ratio={opt / packed:.2f} (model)")
    ok &= opt / packed == 8.0

    for strategy in ex.QUEUE_STRATEGIES:
        fn = functools.partial(ex.exchange_queue, axis="p", strategy=strategy)
        if ex.get_exchange("queue", strategy).wire == "compressed":
            # compressed twins ship fixed-size uint8 payloads whose
            # capacity depends on the id range; density 0.5 = range 2*cap
            bc = fr.compressed_capacity(cap, 2 * cap)
            shapes = (jax.ShapeDtypeStruct((p, bc), jnp.uint8),)
            want = ex.queue_level_bytes(strategy, p, cap, 4, density=0.5)
        else:
            shapes = (jax.ShapeDtypeStruct((p, cap), jnp.int32),)
            want = ex.queue_level_bytes(strategy, p, cap)
        got = compile_and_parse(fn, P(None, None), P(None, None), shapes,
                                mesh)
        rel = got["total"] / max(want, 1)
        print(f"queue/{strategy:28s} model={want:>12.0f}B "
              f"hlo_total={got['total']:>12.0f}B ratio={rel:6.3f}")
        ok &= 0.2 < rel < 2.6
    # compressed-wire claim: the _compressed twin models well below its
    # raw-id twin at matched capacity (the sparse-phase byte cut)
    raw = ex.queue_level_bytes("alltoall_direct", p, cap, 4, density=0.5)
    comp = ex.queue_level_bytes("alltoall_direct_compressed", p, cap, 4,
                                density=0.5)
    print(f"queue/compressed-vs-raw ratio={raw / comp:.2f} (model)")
    ok &= raw / comp >= 2.0

    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
