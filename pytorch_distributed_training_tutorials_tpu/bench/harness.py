"""Benchmark harness: honest wall-clock timing for XLA programs.

Twin of the reference's ``timeit.repeat("train(model)", number=1, repeat=10)``
micro-benchmark (reference ``03.model_parallel.ipynb:1014-1037``, cell 28) —
with the correction TPU requires (SURVEY.md section 5.1): XLA dispatch is
asynchronous, so naive ``timeit`` measures enqueue time, not compute.
Every timed region here ends with ``block_until_ready`` and the first
(compile) iterations are excluded as warmup.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from statistics import mean, stdev

import jax


@dataclass
class BenchResult:
    name: str
    times_s: list[float] = field(default_factory=list)

    @property
    def mean_s(self) -> float:
        return mean(self.times_s)

    @property
    def std_s(self) -> float:
        return stdev(self.times_s) if len(self.times_s) > 1 else 0.0

    def throughput(self, items_per_call: int) -> float:
        """items/sec at the mean time."""
        return items_per_call / self.mean_s

    def __str__(self) -> str:
        return f"{self.name}: {self.mean_s * 1e3:.2f} ms +/- {self.std_s * 1e3:.2f} ms"


def slope_time(run, *, n1: int = 5, n2: int = 20, warmup: int = 2) -> float:
    """Seconds per step via two-point slope: ``(t(n2) - t(n1)) / (n2 - n1)``.

    ``run(k)`` must execute ``k`` *chained* device steps and end with a host
    fetch (e.g. ``float(loss)``). The slope cancels the fixed costs a single
    timed region carries — dispatch of the first launch and the host
    roundtrip of the final fetch — leaving the per-step device time.
    """
    for _ in range(warmup):
        run(1)
    t1 = _timed(run, n1)
    t2 = _timed(run, n2)
    return max((t2 - t1) / (n2 - n1), 1e-12)


def _timed(run, k: int) -> float:
    t0 = time.perf_counter()
    run(k)
    # graftcheck: disable=naive-timing -- slope_time's contract (docstring
    # above) requires the caller's run(k) to end with a real fetch; the
    # fetch lives in the closure, invisible to static analysis
    return time.perf_counter() - t0


def benchmark(fn, *, name: str = "bench", warmup: int = 2, repeat: int = 10) -> BenchResult:
    """Time ``fn()`` ``repeat`` times after ``warmup`` untimed calls.

    ``fn`` should return its result (or any array tied to the computation) so
    the harness can ``block_until_ready`` it inside the timed region.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn())
    res = BenchResult(name)
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        res.times_s.append(time.perf_counter() - t0)
    return res
