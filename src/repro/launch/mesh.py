"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the pod axis is
pure data parallelism across the slower inter-pod links (DCN), so the only
cross-pod collective in steady state is the gradient all-reduce.

Functions, not module constants: importing this module never touches jax
device state (the dry-run pins the device count before any jax init).
"""

from __future__ import annotations

import dataclasses

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


@dataclasses.dataclass(frozen=True)
class Axes:
    """Logical axis-name bundles for a mesh (flat tuples for 1-D jobs)."""
    dp: tuple          # data-parallel axes (includes pod when present)
    model: str         # tensor/expert-parallel axis
    flat: tuple        # every axis (BFS/GNN vertex partitioning)

    @property
    def dp_size(self):
        return None  # resolved against a mesh via sizes()

    def sizes(self, mesh):
        import numpy as np
        dp = int(np.prod([mesh.shape[a] for a in self.dp]))
        return {"dp": dp, "model": mesh.shape[self.model],
                "flat": int(np.prod([mesh.shape[a] for a in self.flat]))}


def mesh_axes(mesh) -> Axes:
    names = tuple(mesh.axis_names)
    if "pod" in names:
        return Axes(dp=("pod", "data"), model="model", flat=names)
    return Axes(dp=("data",), model="model", flat=names)


def make_host_mesh(p: int = 1, name: str = "data"):
    """Small mesh over real local devices (tests, examples)."""
    import numpy as np
    devs = np.asarray(jax.devices()[:p]).reshape(p)
    return jax.sharding.Mesh(devs, (name,))


def default_grid(p: int) -> tuple:
    """Most-square ``(r, c)`` factorization of ``p`` (r <= c).

    The 2-D exchange cost scales with r + c, which a square grid
    minimizes; prime ``p`` degenerates to ``(1, p)`` (= 1-D expand-free).
    """
    r = int(p ** 0.5)
    while p % r:
        r -= 1
    return r, p // r


def make_grid_mesh(r: int = 2, c: int = 2, names: tuple = ("rows", "cols"),
                   devices=None):
    """``r x c`` device grid for the 2-D BFS edge partition.

    Device ``(i, j)`` owns vertex chunk ``i*c + j``; the expand phase
    allgathers frontiers over ``names[1]`` (within a grid row) and the
    fold phase merges candidates over ``names[0]`` (within a grid
    column).  Needs ``r*c`` local devices (``host_devices(n)`` /
    ``--devices n`` before the first jax import for CPU runs); takes the
    first ``r*c`` of ``devices`` (default: every visible device).
    """
    import numpy as np
    devs = jax.devices() if devices is None else list(devices)
    if len(devs) < r * c:
        raise ValueError(f"grid {r}x{c} needs {r*c} devices; "
                         f"have {len(devs)}")
    return jax.sharding.Mesh(np.asarray(devs[: r * c]).reshape(r, c), names)
