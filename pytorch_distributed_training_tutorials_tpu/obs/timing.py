"""Honest wall-clock timing: the measurement rules as library code.

CLAUDE.md's timing rules existed only as prose; every one of them is a
mistake someone actually made (timing the enqueue of an asynchronous
dispatch, stalls on individual launches of a shared host, drift between
two legs' windows, per-launch fixed cost misread as per-op time). This
module is their executable form:

- :class:`MinOfN` — min-of-N with stall *flagging*: samples > k x median
  are reported separately instead of silently averaged in;
- :class:`DriftBracket` — bench.py's ``h2d_window_drift`` pattern: run a
  ceiling leg before AND after the main leg; only same-window legs are
  comparable, and the bracket quantifies how much the window moved;
- :func:`launch_overhead_fit` — the two-chain-length fit
  ``wall = fixed + per_op * len`` (scripts/launch_overhead_probe.py),
  which is how "no per-op floor — the floor is per LAUNCH" is
  established: a chain naively divided by its length charges the
  roundtrip to every op.

None of these time anything themselves: the measured callable must obey
the repo's contract — end with a real device fetch (``float(x[...])`` /
``block_until_ready``), first fetch primed outside the timed region. The
``naive-timing`` graftcheck rule polices that contract statically.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Sequence


@dataclass
class TimingResult:
    """Samples from a min-of-N run, stalls separated from steady state."""

    samples_s: list[float]
    stall_factor: float

    @property
    def best_s(self) -> float:
        return min(self.samples_s)

    @property
    def median_s(self) -> float:
        s = sorted(self.samples_s)
        n = len(s)
        mid = n // 2
        return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])

    @property
    def stalled_s(self) -> list[float]:
        """Samples that hit a stall (> stall_factor x median)."""
        med = self.median_s
        return [s for s in self.samples_s if s > self.stall_factor * med]

    @property
    def n_stalled(self) -> int:
        return len(self.stalled_s)

    def to_dict(self) -> dict:
        return {
            "best_s": round(self.best_s, 6),
            "median_s": round(self.median_s, 6),
            "n": len(self.samples_s),
            "n_stalled": self.n_stalled,
            "stall_factor": self.stall_factor,
            "samples_s": [round(s, 6) for s in self.samples_s],
        }


class MinOfN:
    """min-of-N timer for a callable that ENDS WITH A REAL FETCH.

    A shared host can stall an individual launch — a single sample is
    meaningless, and a mean buries the steady state under the stalls.
    ``best_s`` is the honest steady-state
    estimate; stalled samples stay visible in the result instead of
    disappearing.

    ``fn`` is run once un-timed first when ``warmup`` is set (compile +
    first-fetch priming belongs OUTSIDE the timed region).
    """

    def __init__(self, n: int = 3, stall_factor: float = 5.0,
                 warmup: bool = True):
        if n < 1:
            raise ValueError("MinOfN needs n >= 1")
        self.n = n
        self.stall_factor = stall_factor
        self.warmup = warmup

    def measure(self, fn: Callable[[], object]) -> TimingResult:
        if self.warmup:
            fn()
        samples: list[float] = []
        for _ in range(self.n):
            t0 = time.perf_counter()
            fn()  # the contract: fn's last action is a device fetch
            samples.append(time.perf_counter() - t0)
        return TimingResult(samples_s=samples, stall_factor=self.stall_factor)


@dataclass
class BracketResult:
    """A main-leg measurement bracketed by before/after ceiling legs."""

    result: object
    before_s: float
    after_s: float
    payload_bytes: int = 0

    @property
    def drift(self) -> float:
        """max/min of the two ceiling legs — how much the window moved.

        Host-to-device bandwidth on a shared host can drift between legs;
        a drift near 1.0 certifies the main leg and its ceiling are
        same-window comparable.
        """
        lo = min(self.before_s, self.after_s)
        hi = max(self.before_s, self.after_s)
        return hi / lo if lo > 0 else float("inf")

    @property
    def ceiling_s(self) -> float:
        return min(self.before_s, self.after_s)

    def bandwidth_mbs(self) -> float | None:
        if not self.payload_bytes:
            return None
        return self.payload_bytes / self.ceiling_s / 1e6

    def to_dict(self) -> dict:
        d = {
            "ceiling_before_s": round(self.before_s, 4),
            "ceiling_after_s": round(self.after_s, 4),
            "window_drift": round(self.drift, 2),
        }
        bw = self.bandwidth_mbs()
        if bw is not None:
            d["ceiling_mb_s"] = round(bw, 2)
        return d


class DriftBracket:
    """Bracket a main measurement with a repeated ceiling leg.

    The bench.py ``h2d_window_drift`` pattern generalized: ``ceiling_fn``
    (seconds for a raw reference transfer/compute, fetch-closed) runs
    immediately before and immediately after ``main_fn``; the ratio of the
    two runs bounds how much the environment moved while the main leg ran.
    Comparisons against a ceiling measured in a different window are the
    error this exists to prevent.
    """

    def __init__(self, ceiling_fn: Callable[[], object],
                 payload_bytes: int = 0):
        self.ceiling_fn = ceiling_fn
        self.payload_bytes = payload_bytes

    def _time_ceiling(self) -> float:
        t0 = time.perf_counter()
        self.ceiling_fn()  # contract: ends with a real fetch
        return time.perf_counter() - t0

    def around(self, main_fn: Callable[[], object]) -> BracketResult:
        before = self._time_ceiling()
        result = main_fn()
        after = self._time_ceiling()
        return BracketResult(
            result=result,
            before_s=before,
            after_s=after,
            payload_bytes=self.payload_bytes,
        )


@dataclass
class LaunchFit:
    """``wall = fixed + per_op * len`` decomposition over chain lengths."""

    fixed_ms: float
    per_op_us: float
    lens: tuple[int, ...]
    wall_s: tuple[float, ...] = field(default_factory=tuple)

    def naive_per_op_us(self, length: int) -> float:
        """What naively dividing one chain of ``length`` would report."""
        return self.fixed_ms * 1e3 / length + self.per_op_us

    def to_dict(self) -> dict:
        return {
            "fixed_ms": round(self.fixed_ms, 3),
            "per_op_us": round(self.per_op_us, 3),
            "lens": list(self.lens),
            "wall_s": [round(w, 6) for w in self.wall_s],
        }


def launch_overhead_fit(
    time_chain: Callable[[int], float],
    lens: Sequence[int] = (64, 1024),
) -> LaunchFit:
    """Separate the fixed per-launch cost from true per-op device time.

    ``time_chain(n)`` must return wall seconds for ONE launch of an
    n-long compiled op chain, fetch-closed and already stall-filtered
    (min-of-N). Two lengths give the slope (per-op) and intercept
    (launch+fetch roundtrip): a floor that looks per-call on small ops
    is per LAUNCH, not per op (its size on the chip: not measured).
    """
    if len(lens) < 2:
        raise ValueError("need at least two chain lengths to fit")
    ls = sorted(set(int(n) for n in lens))
    walls = [time_chain(n) for n in ls]
    short_n, long_n = ls[0], ls[-1]
    short_t, long_t = walls[0], walls[-1]
    per_op_us = (long_t - short_t) / (long_n - short_n) * 1e6
    fixed_ms = (short_t - per_op_us * 1e-6 * short_n) * 1e3
    return LaunchFit(
        fixed_ms=fixed_ms,
        per_op_us=per_op_us,
        lens=tuple(ls),
        wall_s=tuple(walls),
    )
