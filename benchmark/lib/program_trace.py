"""The program's own spans and names in the profiler's ``.xplane.pb``: what
``lib/xplane.py`` leaves out.

The program (``ServeEngine``, ``Trainer``) wraps its phases in host spans
named ``prog:<phase>`` whose integer fields (``rid``, ``slot``, ``chain``,
...) the trace keeps as the event's stats, names its kernels
(``pl.pallas_call(name=...)``) and puts ``jax.named_scope`` around the
cache, the sampler, the loss and the optimizer. All of it lands in the same
file as the device's ``XLA Ops`` line, on the same clock. From that file
this module gives:

- the host spans (``prog:`` and the benchmark's ``bench:``) as a tree a
  thread, each with its fields, its parent (the innermost span on its
  thread that encloses it) and its self time (its duration less what its
  children cover);
- each device operation with the program it ran in (its ``program_id``,
  named by the ``XLA Modules`` line: ``jit__chain_fn`` ...) and, where the
  trace carries it, its scope path (``.../attn/kv_cache/scatter``);
- idle gaps of the busiest chip put down to the INNERMOST span that covers
  each instant, so a span and its parent are never both charged;
- shares of device time by scope or by program: a union of intervals over
  the union of all non-wrapper operations, so no share can pass 100 %;
- the spans' fields joined: a request's spans by ``rid``, a chain's by
  ``chain``, optimizer steps counted by ``step`` (``chain_period_ms`` and
  the per-request and per-chain tables of ``__main__``).

The file is opened once a process. A trace from a program without these
spans and scopes reads as nothing (``None``), never as an error or a 0.

    python3 -m benchmark.lib.program_trace [<trace dir>]

prints the tables of ``PERF.md`` section 5 for the newest trace there.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import struct

from . import xplane

PROG = "prog:"
PREFIXES = (PROG, xplane.SPAN_PREFIX)
MODULES_LINE = "XLA Modules"
# the stats of an operation's metadata that hold its scope path and the id
# of its program (the number in the XLA Modules event's name)
PATH_STAT = "tf_op"
PROGRAM_STAT = "program_id"
# the scopes the program names, innermost wins; flax's module names follow
SCOPES = ("kv_cache", "sampling", "loss", "optimizer", "attn", "mlp", "moe",
          "lm_head", "tok_emb", "final_norm", "attn_norm", "mlp_norm",
          "layer_scan")


@dataclasses.dataclass(eq=False)
class Span:
    name: str  # with its prefix: prog:refill, bench:step
    start: int  # ns
    end: int
    fields: dict
    thread: str
    parent: "Span | None" = dataclasses.field(default=None, repr=False)
    children: list = dataclasses.field(default_factory=list, repr=False)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9

    def self_intervals(self) -> list[tuple[int, int]]:
        """What of the span no child covers."""
        out, at = [], self.start
        for s, t in xplane.busy_union(self.children, self.start, self.end):
            if s > at:
                out.append((at, s))
            at = t
        if self.end > at:
            out.append((at, self.end))
        return out

    @property
    def self_seconds(self) -> float:
        return sum(t - s for s, t in self.self_intervals()) / 1e9


@dataclasses.dataclass(eq=False)
class Op:
    name: str  # the whole HLO instruction, as xplane.Event.name
    start: int
    end: int
    program: str | None  # jit__chain_fn
    path: str | None  # jit(_chain_fn)/.../attn/kv_cache/scatter

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclasses.dataclass
class ProgramTrace:
    spans: list  # every span of every thread, by start
    devices: dict  # plane name -> [Op] of its ops line, by start

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == PROG + name]

    @property
    def main_thread(self) -> str | None:
        """The thread that holds the most ``prog:`` spans: the loop."""
        count: dict = {}
        for s in self.spans:
            if s.name.startswith(PROG):
                count[s.thread] = count.get(s.thread, 0) + 1
        return max(count, key=count.get) if count else None


# -- reading the file -------------------------------------------------------
#
# ``jax.profiler.ProfileData`` gives an event's own stats (a span's fields)
# and not those of its metadata, which is where the TPU's trace keeps an
# operation's scope path (``tf_op``) and program (``program_id``). So the
# file is read here, by the wire format of tsl's ``xplane.proto``: XSpace
# {planes=1}; XPlane {name=2, lines=3, event_metadata=4, stat_metadata=5};
# XLine {name=2, timestamp_ns=3, events=4}; XEvent {metadata_id=1,
# offset_ps=2, duration_ps=3, stats=4}; XEventMetadata {id=1, name=2,
# stats=5}; XStatMetadata {id=1, name=2}; XStat {metadata_id=1, double=2,
# uint64=3, int64=4, str=5, bytes=6, ref=7}.


def _varint(buf, i: int):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, the payload's bytes for anything with a length."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        else:  # a length, or fixed64 / fixed32
            if kind == 2:
                size, i = _varint(buf, i)
            else:
                size = 8 if kind == 1 else 4
            val = buf[i:i + size]
            i += size
        yield key >> 3, val


def _stat(buf, names: dict):
    """(name, value) of one XStat."""
    name = value = None
    for no, val in _fields(buf):
        if no == 1:
            name = names.get(val, str(val))
        elif no == 2:
            value = struct.unpack("<d", val)[0]
        elif no == 3:
            value = val
        elif no == 4:
            value = val - (1 << 64) if val >> 63 else val
        elif no == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif no == 7:
            value = names.get(val, str(val))
    return name, value


def _plane(buf) -> dict:
    """One XPlane: its name, ``lines`` as [(name, timestamp_ns, [event
    bytes])] and ``meta`` as id -> (name, {stat: value})."""
    name, lines, raw_meta, stat_names = "", [], [], {}
    for no, val in _fields(buf):
        if no == 2:
            name = bytes(val).decode()
        elif no == 3:
            lines.append(val)
        elif no == 4:
            raw_meta.append(val)
        elif no == 5:
            entry = dict(_fields(val))
            md = dict(_fields(entry[2]))
            stat_names[entry.get(1, md.get(1))] = bytes(md.get(2, b"")).decode()
    meta = {}
    for entry in raw_meta:
        key = md_name = None
        stats = {}
        for no, val in _fields(entry):
            if no == 1:
                key = val
            elif no == 2:
                for mno, mval in _fields(val):
                    if mno == 2:
                        md_name = bytes(mval).decode("utf-8", "replace")
                    elif mno == 5:
                        k, v = _stat(mval, stat_names)
                        stats[k] = v
        meta[key] = (md_name or "", stats)
    out_lines = []
    for line in lines:
        lname, t0, events = "", 0, []
        for no, val in _fields(line):
            if no == 2:
                lname = bytes(val).decode()
            elif no == 3:
                t0 = val
            elif no == 4:
                events.append(val)
        out_lines.append((lname, t0, events))
    return {"name": name, "lines": out_lines, "meta": meta, "stat_names": stat_names}


def _event(buf, t0: int):
    """(metadata id, start ns, end ns, [stat bytes]) of one XEvent."""
    mid = offset = duration = 0
    stats = []
    for no, val in _fields(buf):
        if no == 1:
            mid = val
        elif no == 2:
            offset = val
        elif no == 3:
            duration = val
        elif no == 4:
            stats.append(val)
    start = int(t0 + offset / 1000.0)
    return mid, start, start + int(duration / 1000.0), stats


def _nest(spans: list) -> None:
    """Parents and children by containment among one thread's spans."""
    stack = []
    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and sp.start >= stack[-1].end:
            stack.pop()
        if stack:
            sp.parent = stack[-1]
            stack[-1].children.append(sp)
        stack.append(sp)


_MODULE = re.compile(r"^([\w\.\-]+)\((\d+)\)")


def load(source) -> ProgramTrace:
    """``source`` is the path of an ``.xplane.pb`` or its bytes."""
    if isinstance(source, str):
        with open(source, "rb") as f:
            source = f.read()
    spans, devices = [], {}
    for no, val in _fields(memoryview(source)):
        if no != 1:
            continue
        plane = _plane(val)
        pname, meta = plane["name"], plane["meta"]
        if pname.startswith("/host:"):
            mine_ids = {k for k, (n, _) in meta.items() if n.startswith(PREFIXES)}
            for lname, t0, events in plane["lines"]:
                mine = []
                for ev in events:
                    mid, start, end, stats = _event(ev, t0)
                    if mid in mine_ids:
                        mine.append(Span(
                            meta[mid][0], start, end,
                            dict(_stat(s, plane["stat_names"]) for s in stats),
                            f"{pname}/{lname}"))
                _nest(mine)
                spans += mine
        elif pname.startswith("/device:TPU:"):
            programs, ops = {}, []  # program id -> jit__chain_fn
            for lname, t0, events in plane["lines"]:
                if lname == MODULES_LINE:
                    for ev in events:
                        m = _MODULE.match(meta[_event(ev, t0)[0]][0])
                        if m:
                            programs[int(m.group(2))] = m.group(1)
            for lname, t0, events in plane["lines"]:
                if lname != xplane.OPS_LINE:
                    continue
                for ev in events:
                    mid, start, end, _ = _event(ev, t0)
                    name, st = meta[mid]
                    path = st.get(PATH_STAT)
                    ops.append(Op(
                        name, start, end, programs.get(st.get(PROGRAM_STAT)),
                        path.rstrip(":") if isinstance(path, str) else None))
            devices[pname] = sorted(ops, key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return ProgramTrace(spans, devices)


_CACHE: dict = {}


def of(bundle) -> ProgramTrace | None:
    """The run's trace, opened once a process; None where the run traced
    nothing."""
    if bundle.get("trace") is None:
        return None
    from . import harness

    try:
        path = xplane.newest_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    if path not in _CACHE:
        _CACHE.clear()
        _CACHE[path] = load(path)
    return _CACHE[path]


# -- host spans ------------------------------------------------------------


def period_ms(spans, field: str) -> float | None:
    """Milliseconds a count of ``field`` between the first and the last of
    ``spans``, end to end: a mean over every gap between them, and over
    what the field counts (chains, optimizer steps), not over spans."""
    spans = sorted((s for s in spans if field in s.fields), key=lambda s: s.end)
    if len(spans) < 2 or spans[-1].fields[field] <= spans[0].fields[field]:
        return None
    count = spans[-1].fields[field] - spans[0].fields[field]
    return (spans[-1].end - spans[0].end) / count / 1e6


def chain_period_ms(bundle) -> float | None:
    """Mean pause a streaming client sees between bursts of
    ``tokens_per_launch`` tokens: from the end of the trace's first
    ``prog:chain_fetch`` to the end of its last, over the chains between
    them as their ``chain`` fields count them."""
    pt = of(bundle)
    return period_ms(pt.named("chain_fetch"), "chain") if pt else None


def step_host_ms(bundle) -> float | None:
    """Milliseconds of an engine step in which the busiest chip ran
    nothing, mean over the traced ``prog:step`` spans: every idle gap under
    a step, the launch and hand-over waits inside the two fetches
    included. At ``pipeline_depth`` 1 the chip waits all of it out."""
    pt = of(bundle)
    found = _busiest(pt, bundle)
    if found is None:
        return None
    ops, lo, hi = found
    steps = [s for s in pt.named("step") if s.end > lo and s.start < hi]
    if not steps:
        return None
    idle = sum(
        max(0, min(t, sp.end) - max(s, sp.start))
        for s, t in xplane.gaps(ops, lo, hi) for sp in steps)
    return idle / len(steps) / 1e6


def span_share(bundle, name: str) -> float | None:
    """Percent of the traced window spent inside ``prog:<name>`` spans."""
    pt = of(bundle)
    found = pt.named(name) if pt else []
    if not found:
        return None
    lo, hi = bundle.get("trace_window") or (
        pt.spans[0].start, max(s.end for s in pt.spans))
    return 100.0 * xplane.busy_seconds(found, lo, hi) / ((hi - lo) / 1e9)


def innermost_segments(pt: ProgramTrace, thread: str | None = None) -> list:
    """The thread's time cut into pieces that do not overlap, each under
    the name of the innermost span that covers it: [(start, end, name)]."""
    thread = thread or pt.main_thread
    out = []
    for sp in pt.spans:
        if sp.thread == thread:
            out += [(s, t, sp.name) for s, t in sp.self_intervals()]
    return sorted(out)


def attribute_gaps(gap_list, segments) -> dict[str, float]:
    """Seconds of idle time by the innermost span that covered them; what
    no span covers is ``untracked``. Never sums a span with its parent."""
    out: dict[str, float] = {}
    for lo, hi in gap_list:
        covered = 0
        for s, t, name in segments:
            if s >= hi:
                break
            a, b = max(s, lo), min(t, hi)
            if b > a:
                out[name] = out.get(name, 0.0) + (b - a) / 1e9
                covered += b - a
        if hi - lo > covered:
            out["untracked"] = out.get("untracked", 0.0) + (hi - lo - covered) / 1e9
    return out


# -- device operations -----------------------------------------------------


_IDENT = re.compile(r"[\w\.\-]+")


@functools.lru_cache(maxsize=None)  # a trace has a few hundred paths
def scopes_on(path: str | None) -> tuple[str, ...]:
    """The names on an operation's path, outermost first; a name that a
    transformation wrapped (``transpose(jvp(loss))``) reads as itself."""
    out = []
    for part in (path or "").split("/"):
        found = _IDENT.findall(part)
        if found:
            out.append(found[-1])
    return tuple(out)


def scope_of(path: str | None) -> str | None:
    """The innermost of the program's scopes on an operation's path."""
    for part in reversed(scopes_on(path)):
        if part in SCOPES:
            return part
    return None


def _busiest(pt: ProgramTrace, bundle):
    """(operations of the busiest chip, lo, hi) or None."""
    if pt is None or not pt.devices:
        return None
    name = bundle.get("busiest")
    if name not in pt.devices:
        name = max(pt.devices, key=lambda n: sum(o.seconds for o in pt.devices[n]))
    ops = pt.devices[name]
    if not ops:
        return None
    lo, hi = bundle.get("trace_window") or (ops[0].start, max(o.end for o in ops))
    return ops, lo, hi


def share_of_busy(bundle, pick) -> float | None:
    """Percent of the busiest chip's busy time (the union of every
    non-wrapper operation) taken by the operations ``pick`` chooses (their
    union): cannot pass 100. None where nothing is picked."""
    found = _busiest(of(bundle), bundle)
    if found is None:
        return None
    ops, lo, hi = found
    real = [o for o in ops if not xplane.is_wrapper(o.name)]
    mine = [o for o in real if pick(o)]
    busy = xplane.busy_seconds(real, lo, hi)
    if not mine or busy <= 0:
        return None
    return 100.0 * xplane.busy_seconds(mine, lo, hi) / busy


def scan_slicing_share(bundle) -> float | None:
    """Share of busy time under the scope ``layer_scan`` and outside the
    scanned cell ``layers``: ``lax.scan``'s own slicing and stacking."""
    def pick(o):
        on = scopes_on(o.path)
        return "layer_scan" in on and "layers" not in on

    return share_of_busy(bundle, pick)


def program_share(bundle, fragment: str) -> float | None:
    """Share of busy time inside the programs whose name holds
    ``fragment`` (``_prefill_fn``)."""
    return share_of_busy(
        bundle, lambda o: o.program is not None and fragment in o.program)


# -- the tables of PERF.md section 5 ---------------------------------------


def joined(pt: ProgramTrace, field: str, names) -> dict:
    """The spans called ``names`` by the value of their ``field``:
    {value: {name: its first span}}. What lies outside the traced window (a
    request submitted before it, a chain fetched after it) is missing."""
    out: dict = {}
    for name in names:
        for sp in pt.named(name):
            if sp.fields.get(field, -1) >= 0:
                out.setdefault(sp.fields[field], {}).setdefault(name, sp)
    return out


def _ms(a, b):
    """Milliseconds from a's start to b's end, where the trace has both."""
    return None if a is None or b is None else (b.end - a.start) / 1e6


def request_rows(pt: ProgramTrace) -> list[dict]:
    """A request a row, by ``rid``: what it asked for (``submit``), where
    it ran (``refill``'s ``slot``, the ``bucket`` its prefill was padded
    to), what it waited for and what it got (``complete``'s ``tokens``)."""
    rows = []
    found = joined(pt, "rid", ("submit", "queue_pop", "refill", "prefill_fetch", "complete"))
    for rid, sp in sorted(found.items()):
        get = lambda name, f: sp[name].fields.get(f) if name in sp else None  # noqa: E731
        rows.append({
            "rid": rid, "p_len": get("submit", "p_len"),
            "max_new": get("submit", "max_new"), "slot": get("refill", "slot"),
            "bucket": get("prefill_fetch", "bucket"),
            "queued_ms": _ms(sp.get("submit"), sp.get("queue_pop")),
            "refill_ms": _ms(sp.get("refill"), sp.get("refill")),
            "first_token_ms": _ms(sp.get("submit"), sp.get("prefill_fetch")),
            "tokens": get("complete", "tokens"),
            "served_ms": _ms(sp.get("submit"), sp.get("complete")),
        })
    return rows


def chain_rows(pt: ProgramTrace) -> list[dict]:
    """A chain a row, by ``chain``: slots in use at its launch, the launch,
    the host's wait in its fetch, launch to hand-over, tokens handed on."""
    rows = []
    found = joined(pt, "chain", ("chain_dispatch", "chain_fetch", "distribute"))
    for chain, sp in sorted(found.items()):
        d, f, h = (sp.get(n) for n in ("chain_dispatch", "chain_fetch", "distribute"))
        rows.append({
            "chain": chain, "occupancy": d.fields.get("occupancy") if d else None,
            "dispatch_ms": _ms(d, d), "fetch_ms": _ms(f, f),
            "launch_to_tokens_ms": _ms(d, f),
            "tokens": h.fields.get("tokens") if h else None,
        })
    return rows


def tables(pt: ProgramTrace) -> dict:
    out: dict = {"spans": {}, "idle_by_span": {}, "device_by_scope": {},
                 "device_by_program": {}, "requests": request_rows(pt),
                 "chains": chain_rows(pt),
                 "chain_period_ms": period_ms(pt.named("chain_fetch"), "chain"),
                 # the host's side of a train step: far under the device's
                 # step, the loop runs ahead and the chip never waits for it
                 "dispatch_period_ms": period_ms(pt.named("dispatch"), "step")}
    for sp in pt.spans:
        row = out["spans"].setdefault(sp.name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["total_s"] += sp.seconds
        row["self_s"] += sp.self_seconds
    found = _busiest(pt, {})
    if found is None:
        return out
    ops, lo, hi = found
    if pt.spans:
        lo, hi = min(lo, pt.spans[0].start), max(hi, max(s.end for s in pt.spans))
    real = [o for o in ops if not xplane.is_wrapper(o.name)]
    out["window_s"] = (hi - lo) / 1e9
    out["busy_s"] = xplane.busy_seconds(real, lo, hi)
    out["idle_by_span"] = attribute_gaps(
        xplane.gaps(ops, lo, hi), innermost_segments(pt))

    def scope_key(o):
        scope = scope_of(o.path)
        if scope is None:  # no scope of the program's: the path's own tail
            return "(" + "/".join((o.path or "no path").split("/")[-2:]) + ")"
        if scope == "layer_scan":
            return "layer_scan (lax.scan's own slicing and stacking)"
        return scope

    out["device_by_scope"] = xplane.sums_by(ops, lo, hi, scope_key)
    out["device_by_program"] = xplane.sums_by(
        ops, lo, hi, lambda o: o.program or "(no program)")
    return out


def main(argv=None) -> None:
    import sys

    from . import harness

    argv = sys.argv[1:] if argv is None else argv
    pt = load(xplane.newest_xplane(argv[0] if argv else harness.TRACE_DIR))
    t = tables(pt)
    print(f"window {t.get('window_s', 0):.3f} s, busy {t.get('busy_s', 0):.3f} s")
    print("| span | n | total s | self s | idle s put down to it |")
    print("|---|---|---|---|---|")
    for name, row in sorted(t["spans"].items(), key=lambda kv: -kv[1]["self_s"]):
        idle = t["idle_by_span"].get(name, 0.0)
        print(f"| `{name}` | {row['n']} | {row['total_s']:.4f} | "
              f"{row['self_s']:.4f} | {idle:.4f} |")
    print(f"| untracked | | | | {t['idle_by_span'].get('untracked', 0.0):.4f} |")
    for title, key in (("request", "requests"), ("chain", "chains")):
        if t[key]:
            cols = list(t[key][0])
            print(f"\n| {' | '.join(cols)} |\n|{'---|' * len(cols)}")
            for row in t[key][:24]:
                print("| " + " | ".join(
                    "" if v is None else f"{v:.2f}" if isinstance(v, float) else str(v)
                    for v in row.values()) + " |")
    for what in ("chain_period_ms", "dispatch_period_ms"):
        if t[what] is not None:
            print(f"\n{what} {t[what]:.3f}")
    for title, key in (("scope", "device_by_scope"), ("program", "device_by_program")):
        print(f"\n| {title} | device s | % of busy |")
        print("|---|---|---|")
        for name, sec in xplane.top(t[key], 16):
            print(f"| {name} | {sec:.4f} | {100 * sec / max(t.get('busy_s', 0), 1e-12):.1f} |")


if __name__ == "__main__":
    main()
