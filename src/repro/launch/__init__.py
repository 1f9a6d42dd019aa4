"""Launchers and host-topology helpers.

This package ``__init__`` must stay import-light (stdlib only at import):
the ``host_devices`` helper has to run *before* JAX is first imported, and
the launcher modules themselves import JAX at top level.  The helpers
that need JAX (``launch_devices``, ``use_compile_cache``) import it when
called.
"""

from __future__ import annotations

import os
import sys

_DEV_FLAG = "--xla_force_host_platform_device_count"

# repository checkout root (src/repro/launch/__init__.py -> three up)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def host_devices(n) -> None:
    """Force ``n`` host (CPU) devices for a local multi-shard run.

    Rewrites ``XLA_FLAGS`` (replacing any previous device-count flag, and
    preserving unrelated flags).  XLA reads the variable at backend
    initialization, so this must be called before JAX is first imported —
    launchers parse ``--devices`` from ``sys.argv`` ahead of their JAX
    imports, and the 8-device test harnesses call it at the top of the
    subprocess.  Raises if JAX is already loaded and the request differs
    from the current environment (a silent no-op there would *look* like
    a multi-shard run while executing on one device).
    """
    n = int(n)
    if n <= 0:
        return
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_DEV_FLAG)]
    flags.append(f"{_DEV_FLAG}={n}")
    new = " ".join(flags)
    if new == os.environ.get("XLA_FLAGS", ""):
        return
    if "jax" in sys.modules:
        raise RuntimeError(
            f"host_devices({n}) called after jax was imported; XLA has "
            "already fixed its device count. Call it before any jax "
            "import (or set XLA_FLAGS in the environment).")
    os.environ["XLA_FLAGS"] = new


def launch_devices(n: int = 0) -> list:
    """The devices a launcher meshes for ``--devices n``.

    ``n <= 0`` takes every visible device.  Otherwise exactly the first
    ``n``: on the CPU ``host_devices(n)`` forced that many before JAX
    started; on an accelerator host the machine fixes the count, and a
    request for more devices than are visible exits with an error
    instead of silently running on fewer shards.
    """
    import jax

    devs = jax.devices()
    if n <= 0:
        return devs
    if len(devs) < n:
        raise SystemExit(
            f"--devices {n}: only {len(devs)} {devs[0].platform} "
            f"device(s) are visible; pass --devices {len(devs)} or fewer")
    return devs[:n]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache`` in the
    checkout: a fixed path, so a later run of the same program finds
    what an earlier one compiled.  Call it from an entry point's
    ``main()``, never at import.  Returns the directory in use.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_graph_spec(spec: str, default_n: int):
    """Parse a launcher ``--graph`` spec: ``[name=]kind[:n][:RxC]``.

    Returns ``(name, kind, n, grid-or-None)``.  One grammar for every
    launcher (``bfs_serve`` serves the grid token as a 2-D lane;
    ``bfs_run`` rejects it in favor of its global ``--partition/--grid``
    flags) — a spec copied between their command lines either works or
    fails with a clear message, never a raw ``int()`` traceback.
    Stdlib-only on purpose: this module must stay importable before JAX.
    """
    name, _, rest = spec.partition("=") if "=" in spec else ("", "", spec)
    parts = rest.split(":")
    kind = parts[0]
    n, grid = default_n, None
    for tok in parts[1:]:
        if "x" in tok.lower():
            try:
                r, c = (int(x) for x in tok.lower().split("x"))
            except ValueError:
                raise SystemExit(f"bad grid token {tok!r} in --graph "
                                 f"{spec!r}; expected RxC, e.g. 2x2")
            grid = (r, c)
        else:
            try:
                n = int(tok)
            except ValueError:
                raise SystemExit(f"bad vertex count {tok!r} in --graph "
                                 f"{spec!r}; expected [name=]kind[:n][:RxC]")
    return (name or kind), kind, n, grid


def host_devices_from_argv(argv=None) -> None:
    """Apply ``--devices N`` (or ``--devices=N``) from a launcher command
    line, pre-JAX-import."""
    argv = sys.argv if argv is None else argv
    for i, arg in enumerate(argv):
        if arg == "--devices":
            if i + 1 >= len(argv):
                raise SystemExit("--devices requires a value")
            host_devices(argv[i + 1])
            return
        if arg.startswith("--devices="):
            host_devices(arg.split("=", 1)[1])
            return
