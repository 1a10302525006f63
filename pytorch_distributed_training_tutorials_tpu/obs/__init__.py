"""obs: the observability layer — telemetry, trace reports, honest timing.

The reference tutorial's observability is one rank-tagged print
(ddp_gpus.py:44); this repo's replacement grew as scattered scripts plus
CLAUDE.md prose. ``obs`` is that lore as library code, in three pillars:

- :mod:`.metrics` — :class:`MetricsLogger`: typed step/epoch events, ring
  buffer + JSONL, process-0 gated, no per-step host sync;
- :mod:`.timing` — :class:`MinOfN` (stall flagging), :class:`DriftBracket`
  (the ``h2d_window_drift`` pattern), :func:`launch_overhead_fit`
  (``wall = fixed + per_op * len``);
- :mod:`.receipt` — the single schema'd envelope every number-producing
  entry point writes through (git sha, jax version, mesh, drift window).

Plus the production twin of the benchmarking pillars (ISSUE 10):

- :mod:`.flight` — :class:`FlightRecorder`: bounded request-lifecycle
  event ring + per-request spans + ``graft-flightlog/v1`` fault dumps,
  host-only and budget-neutral by contract;
- :mod:`.histogram` — :class:`LogHistogram`: streaming log2 histograms
  with mergeable state and bounded-error p50/p95/p99 (the serving
  percentile path — replaces sort-the-list);
- :mod:`.sentry` — :class:`ContractSentry` (ISSUE 19): runtime monitor
  for the three engine contracts — zero steady-state recompiles (JAX
  compilation events), the serve fetch budget (the production twin of
  the test monkeypatch spies), and no host-numpy re-uploads per
  dispatch; violations announce as typed flight events + auto-dumps.

Which instrument is for what (ISSUE 27). The profiler's spans are NOT
here: ``ServeEngine`` and ``Trainer`` wrap their phases in
:func:`..utils.profiling.annotate` (``prog:<phase>``, integer fields) —
always there, free while nothing traces, on the profiler's own clock
beside the device's ``XLA Ops`` line, and read by the benchmark's
per-layer readers (``benchmark/lib/program_trace.py``, which also answers
"where did the step go" from a traced run's ``.xplane.pb``).
:class:`FlightRecorder` is the opt-in ring for post-mortems: its own
``perf_counter`` clock, jax-free by contract, read by ``flight_stats()``
and ``scripts/flight_view.py``. The spans are named after its
``EVENT_KINDS`` where a kind exists, so both name the same boundaries.

``python -m pytorch_distributed_training_tutorials_tpu.obs --selftest`` smoke-runs them on a
tiny CPU-mesh workload.

The re-exports below are PEP 562 LAZY (same pattern as the top-level
package init): importing ``pytorch_distributed_training_tutorials_tpu.obs`` does not import
jax, so jax-free tooling (receipt validation in CI) can reach
:mod:`.receipt` without initializing a backend.
"""

import importlib

# name -> submodule; resolved on first access via __getattr__.
_LAZY_EXPORTS = {
    "MetricsLogger": "pytorch_distributed_training_tutorials_tpu.obs.metrics",
    "BracketResult": "pytorch_distributed_training_tutorials_tpu.obs.timing",
    "DriftBracket": "pytorch_distributed_training_tutorials_tpu.obs.timing",
    "LaunchFit": "pytorch_distributed_training_tutorials_tpu.obs.timing",
    "MinOfN": "pytorch_distributed_training_tutorials_tpu.obs.timing",
    "TimingResult": "pytorch_distributed_training_tutorials_tpu.obs.timing",
    "launch_overhead_fit": "pytorch_distributed_training_tutorials_tpu.obs.timing",
    "KINDS": "pytorch_distributed_training_tutorials_tpu.obs.receipt",
    "SCHEMA": "pytorch_distributed_training_tutorials_tpu.obs.receipt",
    "environment_stamp": "pytorch_distributed_training_tutorials_tpu.obs.receipt",
    "load_receipt": "pytorch_distributed_training_tutorials_tpu.obs.receipt",
    "make_receipt": "pytorch_distributed_training_tutorials_tpu.obs.receipt",
    "validate_receipt": "pytorch_distributed_training_tutorials_tpu.obs.receipt",
    "write_receipt": "pytorch_distributed_training_tutorials_tpu.obs.receipt",
    "EVENT_KINDS": "pytorch_distributed_training_tutorials_tpu.obs.flight",
    "FLIGHT_SCHEMA": "pytorch_distributed_training_tutorials_tpu.obs.flight",
    "FlightRecorder": "pytorch_distributed_training_tutorials_tpu.obs.flight",
    "load_flightlog": "pytorch_distributed_training_tutorials_tpu.obs.flight",
    "merge_snapshots": "pytorch_distributed_training_tutorials_tpu.obs.flight",
    "summarize_merged": "pytorch_distributed_training_tutorials_tpu.obs.flight",
    "validate_flightlog": "pytorch_distributed_training_tutorials_tpu.obs.flight",
    "LogHistogram": "pytorch_distributed_training_tutorials_tpu.obs.histogram",
    "ContractSentry": "pytorch_distributed_training_tutorials_tpu.obs.sentry",
}

__all__ = sorted(_LAZY_EXPORTS)


def __getattr__(name: str):
    try:
        module_name = _LAZY_EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    value = getattr(importlib.import_module(module_name), name)
    globals()[name] = value  # cache: __getattr__ runs once per name
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY_EXPORTS))
