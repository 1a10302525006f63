"""From the profiler's ``.xplane.pb`` to intervals, and from intervals to
busy time, idle gaps and sums by operation: the reduction every per-layer
reader shares.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A device
is a plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
for each operation the chip ran (start and duration in nanoseconds; the
event's name is the whole HLO instruction, operand shapes included).
Operations that only wrap others (a
``while`` around a scanned layer stack, a ``call``, a ``conditional``)
span their bodies: they count for the union of busy time and not for any
sum by name. Host threads are lines of the ``/host:CPU`` plane; the
benchmark's own spans are the events there whose names start with
``bench:`` (``jax.profiler.TraceAnnotation`` in the load loop), on the
same clock as the device's.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
_WRAPPERS = re.compile(r"^%?(while|call|conditional|async-start|async-done)\b")


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns
    end: int  # ns

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> list[Event] of its ops line, by start
    spans: list  # the benchmark's host spans, by start
    lines: dict  # plane name -> [line names]: for looking at a trace by hand


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _events(line) -> list[Event]:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        out.append(Event(str(ev.name), start, start + int(ev.duration_ns)))
    out.sort(key=lambda e: e.start)
    return out


def load(profile) -> Trace:
    """``profile`` is a ``ProfileData`` (or a path to an ``.xplane.pb``)."""
    if isinstance(profile, str):
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(profile)
    devices, spans, lines = {}, [], {}
    for plane in profile.planes:
        name = str(plane.name)
        lines[name] = [str(l.name) for l in plane.lines]
        if name.startswith("/device:TPU:"):
            for line in plane.lines:
                if str(line.name) == OPS_LINE:
                    devices[name] = _events(line)
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans += [
                    e for e in _events(line)
                    if e.name.startswith(SPAN_PREFIX)
                ]
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans, lines)


def is_wrapper(name: str) -> bool:
    return bool(_WRAPPERS.match(name))


def window_of(trace: Trace) -> tuple[int, int]:
    """The traced window: from the first to the last thing any device or
    the benchmark's loop did."""
    starts = [evs[0].start for evs in trace.devices.values() if evs]
    ends = [max(e.end for e in evs) for evs in trace.devices.values() if evs]
    if trace.spans:
        starts.append(trace.spans[0].start)
        ends.append(max(s.end for s in trace.spans))
    return min(starts), max(ends)


def busy_union(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged busy intervals of ``events`` (sorted by start) inside
    [lo, hi]."""
    merged: list[list[int]] = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_seconds(events, lo: int, hi: int) -> float:
    return sum(t - s for s, t in busy_union(events, lo, hi)) / 1e9


def gaps(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals inside [lo, hi], longest first."""
    out, at = [], lo
    for s, t in busy_union(events, lo, hi):
        if s > at:
            out.append((at, s))
        at = t
    if hi > at:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def attribute_gaps(gap_list, spans) -> dict[str, float]:
    """Seconds of idle time by what the benchmark's loop was doing: each
    gap is split over the spans that overlap it, and what no span covers
    is ``untracked``."""
    out: dict[str, float] = {}
    for lo, hi in gap_list:
        covered = 0
        for sp in spans:
            if sp.start >= hi:
                break
            s, t = max(sp.start, lo), min(sp.end, hi)
            if t > s:
                key = sp.name[len(SPAN_PREFIX):]
                out[key] = out.get(key, 0.0) + (t - s) / 1e9
                covered += t - s
        rest = (hi - lo) - covered
        if rest > 0:
            out["untracked"] = out.get("untracked", 0.0) + rest / 1e9
    return out


def sums_by(events, lo: int, hi: int, key) -> dict[str, float]:
    """Seconds inside [lo, hi] by ``key(event)``; wrappers and events for
    which ``key`` gives None are left out."""
    out: dict[str, float] = {}
    for e in events:
        if is_wrapper(e.name):
            continue
        k = key(e)
        s, t = max(e.start, lo), min(e.end, hi)
        if k is None or t <= s:
            continue
        out[k] = out.get(k, 0.0) + (t - s) / 1e9
    return out


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


_SHAPE = re.compile(r"(pred|[a-z]+\d+)\[([\d,]*)\]")


@dataclasses.dataclass
class CustomCall:
    """A ``tpu_custom_call`` (a Pallas kernel) as the trace names it: the
    event's name is the whole HLO instruction, operand shapes included."""

    event: Event
    instruction: str  # e.g. %up_proj.4
    results: list  # [(dtype, (dims...)), ...]
    operands: list


def _shapes(text: str) -> list:
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(text)]


def custom_calls(events, lo: int, hi: int) -> list[CustomCall]:
    """The Pallas kernels among ``events`` that ran inside [lo, hi]."""
    out = []
    for e in events:
        if 'custom_call_target="tpu_custom_call"' not in e.name:
            continue
        if e.end <= lo or e.start >= hi:
            continue
        head, _, rest = e.name.partition(" custom-call(")
        instruction, _, results = head.partition(" = ")
        operands = rest.split("), custom_call_target")[0]
        out.append(CustomCall(e, instruction, _shapes(results), _shapes(operands)))
    return out


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.286 fusion bf16[4,2048,2048]`` for a whole HLO instruction;
    a custom call keeps its operand shapes, which are what tells kernels
    apart."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:160]
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else "?"
    fmt = lambda sh: sh[0] + "[" + ",".join(map(str, sh[1])) + "]"  # noqa: E731
    result = _shapes(rest[: op.start()] if op else rest)
    out = f"{head} {opcode} {','.join(fmt(r) for r in result[:2])}"
    if opcode == "custom-call":
        operands = _shapes(rest[op.end():].split("), custom_call_target")[0])
        out += " (" + ",".join(fmt(o) for o in operands[:4]) + ")"
    return out[:200]


def roofline_share(calls_and_bounds) -> float | None:
    """Percent: the least seconds the chip could take for the calls over
    the seconds they took. ``calls_and_bounds`` is [(seconds, bound)]."""
    took = sum(s for s, _ in calls_and_bounds)
    if took <= 0:
        return None
    return 100.0 * sum(b for _, b in calls_and_bounds) / took


def idle_share(bundle) -> float | None:
    """Percent of the traced window in which no operation ran on the
    busiest chip: 1 - the union of its operations' intervals."""
    trace = bundle.get("trace")
    if trace is None or not trace.devices:
        return None
    lo, hi = bundle["trace_window"]
    busy = busy_seconds(trace.devices[bundle["busiest"]], lo, hi)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
