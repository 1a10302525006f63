"""What every traffic kind shares: reading the cell's files, the device
gate, the compile log, the tracer, the per-layer readers and the checks
that decide ``correct``.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


DEFAULT_BLOCK = "gqa_swiglu"  # of a configuration whose file names none


class Block:
    """``benchmark/blocks/<name>/``: a configuration's file names it under
    ``"block"``. ``reference`` is the block's plain reference with its
    leaves and operation counts, ``program`` the model and the weights'
    mapping in the program; both are loaded when first asked for."""

    def __init__(self, name: str, root: str = ROOT):
        self.name = name
        self.dir = os.path.join(root, "benchmark", "blocks", name)
        if not os.path.isdir(self.dir):
            raise SystemExit(f"no block {name!r}: {self.dir} is not there")

    @functools.cached_property
    def reference(self):
        return load_module(os.path.join(self.dir, "reference.py"))

    @functools.cached_property
    def program(self):
        return load_module(os.path.join(self.dir, "program.py"))

    def needs(self, mode: str) -> None:
        """Exit where the block has no ``mode`` (``train`` or ``serve``)."""
        if mode not in self.reference.MODES:
            raise SystemExit(
                f"block {self.name!r} has no {mode!r} mode: it has "
                f"{list(self.reference.MODES)}")


class Cell:
    """One entry of ``BENCHMARK.json``'s workloads with its files."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.spec = read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        self.chips = self.workload["chips"]
        entry = next(
            c for c in self.spec["configs"] if c["name"] == self.workload["config"]
        )
        self.config = read_json(os.path.join(root, entry["file"]))
        self.block = Block(self.config.get("block", DEFAULT_BLOCK), root)
        self.traffic = read_json(
            os.path.join(root, "benchmark", "traffic",
                         self.workload["traffic"] + ".json")
        )
        self.peaks = read_json(os.path.join(root, "benchmark", "peaks.json"))
        limits = os.path.join(root, "benchmark", "limits", name + ".json")
        self.limits = read_json(limits) if os.path.exists(limits) else {"limits": {}}

    def limit(self, name: str):
        """The cell's limit for a number compared, None where it has none."""
        return self.limits["limits"].get(name)

    def _mine(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if self._mine(m)]

    @property
    def per_layer(self) -> list[dict]:
        return [m for m in self.spec["per_layer"] if self._mine(m)]


def rehearsal_sizes(cell: Cell) -> None:
    """Toy widths for the CPU tests: the configuration's and the mix's own
    ``rehearse`` overrides, applied in place."""
    cell.limits["limits"].update(cell.limits.get("rehearse", {}))
    for part in (cell.config, cell.traffic):
        over = part.get("rehearse", {})
        for k, v in over.items():
            if isinstance(v, dict) and isinstance(part.get(k), dict):
                part[k] = {**part[k], **v}
            else:
                part[k] = v


def gate_device(cell: Cell, rehearse: bool) -> dict:
    """The device as JAX reports it; exits with code 3 unless it is the
    TPU the cell asks for (a rehearsal takes the CPU and says so)."""
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if rehearse:
        return device
    why = None
    if device["platform"] != "tpu":
        why = f"no accelerator: platform is {device['platform']!r}"
    elif device["kind"] not in cell.peaks["devices"]:
        why = f"device kind {device['kind']!r} is not in benchmark/peaks.json"
    elif device["count"] != cell.chips:
        why = f"cell asks for {cell.chips} chips, JAX sees {device['count']}"
    if why:
        print(f"refusing to measure: {why}", file=sys.stderr)
        raise SystemExit(3)
    return device


def peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    return int(max(peaks))


class CompileLog:
    """Counts program builds and their seconds through ``jax.monitoring``
    (the listener ``chip_smoke.CompileLog`` wraps, without the program's
    sentry): one ``backend_compile`` duration event for each program built
    or read back from the persistent cache, one ``cache_hits`` event for
    each read back."""

    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += seconds
            if event.endswith("backend_compile_duration"):
                self.programs += 1

    def _event(self, event: str, **kw) -> None:
        if event.endswith("/cache_hits"):
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "compile_s": self.seconds,
                "cache_hits": self.cache_hits}


def span(name: str):
    """A host span on the profiler's clock (free when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


class Tracer:
    """Traces the window from ``after`` seconds in until :meth:`stop`."""

    def __init__(self, on: bool, after: float):
        self.after = after
        self.state = "idle" if on else "done"
        self.t_stop = None

    def poll(self, elapsed: float) -> None:
        """Call between units of work with the seconds since the window
        opened."""
        import jax

        if self.state == "idle" and elapsed >= self.after:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
            self.state = "tracing"

    def stop(self) -> None:
        import jax

        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.t_stop, self.state = time.perf_counter(), "done"


def load_module(path: str):
    """The file as a module of its own, found by path and entered in
    ``sys.modules``: ``dataclasses`` looks a class's module up there."""
    name = "bench_" + "_".join(path.split(os.sep)[-3:]).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def kernel_cost(root: str, kernel: str):
    """``benchmark/kernels/<kernel>.py``: operations and bytes from shapes."""
    return load_module(os.path.join(root, "benchmark", "kernels", kernel + ".py"))


def read_layer_metrics(cell: Cell, bundle: dict) -> dict:
    """Each per-layer metric of the cell through its own reader,
    ``benchmark/layer_metrics/<name>.py``: ``read(bundle)`` gives the value
    or None, and None leaves the metric out of the line."""
    out = {}
    for m in cell.per_layer:
        path = os.path.join(cell.root, "benchmark", "layer_metrics", m["name"] + ".py")
        value = load_module(path).read(bundle)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Checks:
    """The numbers ``correct`` compares, each beside its limit."""

    def __init__(self):
        self.rows: list[tuple[str, float, float, str]] = []

    def at_most(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit), "<="))

    def at_least(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit), ">="))

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(
            (v <= lim) if op == "<=" else (v >= lim)
            for _, v, lim, op in self.rows
        )

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim, "holds": op}
                for n, v, lim, op in self.rows}

    def print(self) -> None:
        for n, v, lim, op in self.rows:
            good = (v <= lim) if op == "<=" else (v >= lim)
            print(f"check {n}: {v!r} {op} {lim!r} {'ok' if good else 'FAILED'}",
                  file=sys.stderr)
