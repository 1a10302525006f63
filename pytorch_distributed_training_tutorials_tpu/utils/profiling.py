"""Profiling: jax.profiler tracing around the hot loop.

The reference declares profilers (py-spy, memory-profiler,
``environment.yml:78-79``) but never uses them; its only timing is naive
``timeit`` (SURVEY.md section 5.1), which lies under XLA's async dispatch.
This module is the gap fix: :func:`trace` captures a real device trace
(XLA ops, ICI collectives, host callbacks) viewable in TensorBoard/Perfetto,
and :func:`annotate` marks host-side regions so loader/step boundaries show
up in the timeline.
"""

from __future__ import annotations

import contextlib
import os

import jax


@contextlib.contextmanager
def trace(logdir: str = "/tmp/jax-trace"):
    """Capture a device+host profiler trace of the enclosed region.

    Usage::

        with profiling.trace("/tmp/tr"):
            trainer.train(1)

    View with ``tensorboard --logdir /tmp/tr`` (or load the ``.trace.json.gz``
    in Perfetto). Wrap *steady-state* steps — the first step's compile time
    dominates a cold trace.
    """
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named host-side region for the trace timeline (context manager)."""
    return jax.profiler.TraceAnnotation(name)


def device_op_durations(logdir: str) -> dict[str, float]:
    """Aggregate on-device op durations (microseconds) from a trace dir.

    Parses the ``.trace.json.gz`` files :func:`trace` wrote, keeps only
    complete events on device lanes (``/device:TPU:*`` / GPU — host python
    frames are excluded), and sums duration per op name. This is the
    programmatic answer to "where did the step time actually go" — naive
    wall-clock timing of individual dispatches measures the enqueue, while
    the device trace is ground truth.

    Returns ``{op_name: total_us}``, descending. Top-level module wrappers
    (``jit_*``) are included, so ``durations["jit_train_step(...)"] /
    num_calls`` gives honest per-step device time.
    """
    import collections
    import glob
    import gzip
    import json

    events: list[dict] = []
    for f in glob.glob(
        os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True
    ):
        with gzip.open(f, "rt") as fh:
            events.extend(json.load(fh).get("traceEvents", []))
    pid_names = {
        e["pid"]: e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    device_pids = {
        p
        for p, n in pid_names.items()
        if "/device:" in n or "TPU" in n or "GPU" in n
    }
    totals: collections.Counter = collections.Counter()
    if device_pids:
        for e in events:
            if (
                e.get("ph") == "X"
                and e.get("pid") in device_pids
                and "dur" in e
            ):
                totals[e.get("name", "?")] += e["dur"]
    else:
        # XLA:CPU (tests, virtual meshes): op events live on the host
        # process's executor threads, named "tf_XLA..."
        xla_threads = {
            (e["pid"], e["tid"])
            for e in events
            if e.get("ph") == "M"
            and e.get("name") == "thread_name"
            and e["args"].get("name", "").startswith("tf_XLA")
        }
        for e in events:
            if (
                e.get("ph") == "X"
                and (e.get("pid"), e.get("tid")) in xla_threads
                and "dur" in e
            ):
                totals[e.get("name", "?")] += e["dur"]
    return dict(totals.most_common())
