"""Profiling: jax.profiler tracing around the hot loop.

The reference declares profilers (py-spy, memory-profiler,
``environment.yml:78-79``) but never uses them; its only timing is naive
``timeit`` (SURVEY.md section 5.1), which lies under XLA's async dispatch.
This module is the gap fix: :func:`trace` captures a real device trace
(XLA ops, ICI collectives, host callbacks) viewable in TensorBoard/Perfetto,
and :func:`annotate` is the program's one host-span primitive on the
profiler's clock.

Which instrument is for what:

- **Profiler spans** (:func:`annotate`): always there. ``ServeEngine`` and
  ``Trainer`` wrap their phases in them unconditionally; they cost a flag
  test while nothing traces and land in the ``.xplane.pb`` beside the
  device's ``XLA Ops`` line when something does. The benchmark's per-layer
  readers (``benchmark/lib/program_trace.py``) read them.
- **FlightRecorder** (:mod:`..obs.flight`): opt-in ring of lifecycle
  events on its own ``perf_counter`` clock, jax-free, for post-mortems
  (``flight_stats()``, ``scripts/flight_view.py``). The spans here are
  named after its ``EVENT_KINDS`` where a kind exists, so a flight dump and
  a profiler trace name the same boundaries.
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


SPAN_PREFIX = "prog:"


def annotate(name: str, **fields: int):
    """Host span ``prog:<name>`` on the profiler's clock (context manager).

    ``fields`` are ints already at hand (``rid``, ``slot``, ``chain``...)
    and become the event's stats in the trace; a field known only at the
    end of the span is added with ``span.set_metadata(rid=...)`` before it
    closes. With no profiler running the span does nothing but construct.
    """
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **fields)

