"""Shared benchmark utilities.

All benchmarks print ``name,us_per_call,derived`` CSV rows (spec).
These suites are CPU gates, not chip measurements: they run on XLA's
CPU backend with Pallas kernels in interpret mode, so every time they
print is a CPU proxy and names no device metric.  What they check —
orderings, canonicalization/caching behaviour (pure host code), byte
counts and modeled values — holds on any backend.  ``chip_smoke.py``
is the check that runs on the TPU.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, List

import jax

__all__ = ["trimean", "time_host_us", "time_jax_us", "emit"]


def trimean(xs: List[float]) -> float:
    """Tukey trimean, as the paper reports for Fig. 6."""
    xs = sorted(xs)
    q1 = xs[len(xs) // 4]
    q2 = xs[len(xs) // 2]
    q3 = xs[(3 * len(xs)) // 4]
    return (q1 + 2 * q2 + q3) / 4.0


def time_host_us(fn: Callable, iters: int = 1000, repeats: int = 7) -> float:
    """Trimean of per-call host time in us (for pure-python paths:
    create/commit/model-query)."""
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        samples.append((time.perf_counter() - t0) / iters * 1e6)
    return trimean(samples)


def time_jax_us(fn: Callable, *args, iters: int = 3, repeats: int = 5) -> float:
    """Trimean of per-call device time in us (jitted fns; first call
    compiles)."""
    out = fn(*args)
    jax.block_until_ready(out)
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / iters * 1e6)
    return trimean(samples)


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.2f},{derived}")
