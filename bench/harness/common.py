"""Small helpers shared by the runners."""

from __future__ import annotations

import collections
import contextlib
import time

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use (``stream``) of a run's seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def say(phase: str, **fields) -> None:
    """One line of set-up or window facts on standard output."""
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class Annotator:
    """Host spans for the profiler: ``jax.profiler.TraceAnnotation`` in a
    traced run, nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class CompileCacheEvents:
    """Counts JAX's persistent compilation cache requests and hits."""

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.counts[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> dict:
        return {"requests": self.counts["compile_requests_use_cache"],
                "hits": self.counts["cache_hits"]}


class Stopwatch:
    """Named host-clock intervals of set-up, printed as they end."""

    def __init__(self, phase: str):
        self.phase = phase
        self.parts = {}

    @contextlib.contextmanager
    def part(self, name: str):
        t0 = time.perf_counter()
        yield
        self.parts[name] = time.perf_counter() - t0
        say(self.phase, **{f"{name}_s": f"{self.parts[name]:.3f}"})


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (``inf`` counts as a value)."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(1, int(np.ceil(q * len(v))))
    return v[k - 1]
