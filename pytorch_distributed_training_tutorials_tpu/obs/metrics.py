"""MetricsLogger: typed per-step/per-epoch telemetry without host syncs.

The reference's entire log surface is one rank-tagged per-epoch print that
never includes the loss (ddp_gpus.py:44; SURVEY.md section 5.5). This is
the structured replacement: every event lands in an in-memory ring buffer
and (process 0 only) an optional JSONL sink, and the VERBOSE step line the
Trainer used to print directly now goes through the same code path — the
printed loss and the recorded loss are the same fetched float, so console
logging and structured metrics cannot diverge.

The hot-path contract (the whole point): ``log_step`` performs NO host
sync — device scalars are retained as-is and fetched in ONE batched
``jax.device_get`` at epoch/flush boundaries. With ``defer_host_fetch``
(the Trainer's deferred mode) even the epoch boundary skips the fetch;
pending scalars drain only at an explicit :meth:`flush`. The single
deliberate exception is a ``log_every``-opted verbose step line, which has
always cost one loss fetch (trainer.py's log_every docs).
"""

from __future__ import annotations

import collections
import json
from typing import IO

import jax

from pytorch_distributed_training_tutorials_tpu.utils.logging import log0


class MetricsLogger:
    """Ring buffer + JSONL sink for step/epoch events, process-0 gated.

    Parameters
    ----------
    jsonl_path: sink file (one JSON object per line); None = in-memory only.
    capacity: ring-buffer size for both flushed events and pending scalars.
    quiet: suppress ALL console lines (bench runs); events still record.
    defer_host_fetch: epoch boundaries do NOT fetch pending device
        scalars (the Trainer's defer contract) — only :meth:`flush` does.
    flops_per_token / peak_flops / tokens_per_sample: when set, epoch
        events gain ``tokens_per_sec`` and ``mfu`` derived from
        ``samples_per_sec``. The caller hands in its own analytic FLOPs
        a token (never ``cost_analysis`` on a scanned model: XLA counts
        a scan body once, not times its trip count).
    flight: optional :class:`..obs.flight.FlightRecorder`. Skip-step
        observations become ``step_skipped`` flight events AT DRAIN TIME
        — the skip flag already rides the batched fetch, so the recorder
        learns about a skipped step without any new per-step host sync
        (it is simply as late as the loss itself).
    """

    def __init__(
        self,
        *,
        jsonl_path: str | None = None,
        capacity: int = 4096,
        quiet: bool = False,
        defer_host_fetch: bool = False,
        flops_per_token: float | None = None,
        peak_flops: float | None = None,
        tokens_per_sample: int | None = None,
        flight=None,
    ):
        self.events: collections.deque[dict] = collections.deque(
            maxlen=capacity
        )
        self._pending: collections.deque[tuple[int, object, dict | None]] = (
            collections.deque(maxlen=capacity)
        )
        self.jsonl_path = jsonl_path
        self.quiet = quiet
        self.defer_host_fetch = defer_host_fetch
        self.flops_per_token = flops_per_token
        self.peak_flops = peak_flops
        self.tokens_per_sample = tokens_per_sample
        self.flight = flight
        self._sink: IO[str] | None = None

    # -- gating ------------------------------------------------------------

    @property
    def is_process_zero(self) -> bool:
        return jax.process_index() == 0

    def say(self, msg: str) -> None:
        """Console line: process-0 gated, silenced by ``quiet``."""
        if not self.quiet:
            log0(msg)

    # -- event intake ------------------------------------------------------

    def log_step(
        self, step: int, loss, verbose: bool = False, extra: dict | None = None
    ) -> None:
        """Record a step's loss. NO host sync unless ``verbose``.

        ``loss`` may be a device scalar — it is retained un-fetched. A
        verbose call (the Trainer's ``log_every`` opt-in) fetches ONCE and
        prints + records the same float, the one deliberate per-step sync
        this module permits. ``extra`` is an optional dict of additional
        scalars (device or host — e.g. the skip-step counter ISSUE 9's
        guardrails emit); its values ride the SAME batched drain fetch as
        the loss, so extras never add a host sync either.
        """
        if verbose:
            loss = float(loss)  # the single opted-in fetch
            self.say(f"  step {step}: loss {loss:.4f}")
        self._pending.append((int(step), loss, extra))

    def log_epoch(self, metrics: dict) -> dict:
        """Record an epoch event (and drain pending steps, fetch rules
        permitting); prints the Trainer's epoch line unless quiet."""
        if not self.defer_host_fetch:
            self._drain_pending()
        event = {"kind": "epoch", **metrics}
        if self.tokens_per_sample and "samples_per_sec" in metrics:
            event["tokens_per_sec"] = (
                metrics["samples_per_sec"] * self.tokens_per_sample
            )
        if (
            self.flops_per_token
            and self.peak_flops
            and "tokens_per_sec" in event
        ):
            event["mfu"] = (
                event["tokens_per_sec"] * self.flops_per_token
                / self.peak_flops
            )
        self._record(event)
        self.say(
            f"  epoch {metrics['epoch']}: loss {metrics['loss']:.4f} | "
            f"{metrics['steps_per_sec']:.1f} steps/s | "
            f"{metrics['samples_per_sec']:.0f} samples/s"
        )
        return event

    # -- draining ----------------------------------------------------------

    def _drain_pending(self) -> None:
        if not self._pending:
            return
        pending = list(self._pending)
        self._pending.clear()
        # ONE batched fetch for everything accumulated since the last drain
        # (device_get walks the pytree, so loss + extras fetch together;
        # None extras are empty subtrees).
        values = jax.device_get([(v, e) for _, v, e in pending])
        for (step, _, _), (val, ext) in zip(pending, values):
            event = {"kind": "step", "step": step, "loss": float(val)}
            if ext:
                event.update({k: float(v) for k, v in ext.items()})
            if self.flight is not None and event.get("skipped"):
                # the skip became host-visible with THIS drain; stamp it
                # (auto-dumps when the recorder has a dump_path)
                self.flight.step_skipped(step=event["step"])
            self._record(event)

    def flush(self) -> None:
        """Drain pending device scalars (even under defer_host_fetch — this
        IS the explicit fetch point) and flush the JSONL sink."""
        self._drain_pending()
        if self._sink is not None:
            self._sink.flush()

    def close(self) -> None:
        self.flush()
        if self._sink is not None:
            self._sink.close()
            self._sink = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- storage -----------------------------------------------------------

    def _record(self, event: dict) -> None:
        self.events.append(event)
        if self.jsonl_path and self.is_process_zero:
            if self._sink is None:
                self._sink = open(self.jsonl_path, "a")
            self._sink.write(json.dumps(event) + "\n")

    # -- views -------------------------------------------------------------

    @property
    def last_epoch(self) -> dict | None:
        for ev in reversed(self.events):
            if ev.get("kind") == "epoch":
                return ev
        return None

    def step_events(self) -> list[dict]:
        return [e for e in self.events if e.get("kind") == "step"]

    def epoch_events(self) -> list[dict]:
        return [e for e in self.events if e.get("kind") == "epoch"]
