"""Looking at one trace by hand: planes, lines, the costliest operations,
every custom call, the benchmark's spans."""

from __future__ import annotations

import json

from . import xplane


def write(trace, path: str) -> None:
    out = {"lines": trace.lines, "n_spans": len(trace.spans),
           "spans_head": [[s.name, s.start, s.end] for s in trace.spans[:12]]}
    lo, hi = xplane.window_of(trace)
    out["window"] = [lo, hi]
    for name, evs in trace.devices.items():
        by = {}
        for e in evs:
            rec = by.setdefault(e.name, {"n": 0, "s": 0.0})
            rec["n"] += 1
            rec["s"] += e.seconds
        top = sorted(by.items(), key=lambda kv: -kv[1]["s"])
        out[name] = {
            "n_events": len(evs), "busy_s": xplane.busy_seconds(evs, lo, hi),
            "first": [evs[0].start, evs[0].end] if evs else None,
            "top": [[k, v["n"], v["s"]] for k, v in top[:40]],
            "custom": [[k, v["n"], v["s"]] for k, v in top if "custom-call" in k][:40],
        }
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
