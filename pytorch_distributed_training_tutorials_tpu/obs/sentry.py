"""Runtime contract sentry: compile / fetch / re-upload attribution.

Every engine contract the reference-reproduction depends on — "nothing
recompiles per request" (CLAUDE.md serving invariants), "fetch budget =
chains + prefills + splices (+ handoffs_in + counted swaps)", "no host-numpy leaf
re-uploads per call" (the round-4 decode trap) — is pinned
by monkeypatch spies and ``_cache_size()`` counts in CPU-mesh tests, but
on the real chip nothing watches them at runtime. :class:`ContractSentry`
is the production twin of those spies: threaded through ``ServeEngine``,
``FleetRouter`` and ``Trainer``, it makes a violation self-announcing
instead of silently eating a receipt round.

Three probes, all host-only bookkeeping (a counter bump and a dict walk
— never a device fetch, so the fetch budget it measures is unchanged by
measuring it):

- **Compile probe**: :meth:`install` subscribes to JAX's compilation
  events (``jax.monitoring.register_event_duration_secs_listener``,
  filtering to the ``backend_compile`` duration — the per-XLA-compile
  signal; a pjit-lower-wrapping fallback covers jax builds without the
  monitoring API). Every compilation becomes a typed ``compile`` flight
  event (phase label, wall ms). After :meth:`mark_steady` — the same
  warmup seam as ``flight.reset()`` — any further compilation is a
  VIOLATION: the event carries ``steady=True`` and the sentry explicitly
  dumps a ``graft-flightlog/v1`` snapshot naming it (warmup compiles are
  normal and never dump).
- **Fetch probe**: the installed ``jax.device_get`` wrapper counts every
  host fetch; the engine's budgeted call sites additionally route
  through :meth:`budgeted_fetch` (via ``ServeEngine._sentry_fetch``), so
  inside a :meth:`begin_round`/:meth:`end_round` window — one ``step()``
  scheduling round — ``fetched > budgeted`` means a stray sync leaked
  outside the budget (chains + prefills + splices + handoffs_in +
  swaps_out under SLO preemption, ISSUE 20; prefill-role budget 0). The
  violation records a ``budget_violation``
  event, which auto-dumps through the recorder's existing fault path.
- **Re-upload probe**: :meth:`check_args` walks a dispatched arg tree
  for host-``numpy`` leaves — the ``device_materialize`` trap, where a
  checkpoint-restored tree re-uploads per call (the whole tree's bytes,
  every launch). H2D bytes accumulate every occurrence; the
  FIRST occurrence per site label records a ``reupload`` event
  (auto-dumped) so repeated per-call uploads surface once, loudly, not
  once per step.

This module is jax-free at import (it joins
``analysis.hostonly.HOST_ONLY_MODULES`` and the no-jax subprocess pin):
``install``/``check_args`` import jax function-locally — the sanctioned
lazy idiom — and a sentry that is constructed but never installed
touches jax not at all. Sentry-off engines/trainers keep byte-identical
state trees and compiled programs (the standard ``is not None``
off-path gating); ``summary()`` feeds ``sentry_stats()`` into
``engine.stats()`` / ``router.stats()`` and every receipt.
"""

from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

# The monitoring event that fires once per real XLA compilation (the
# trace/MLIR-lowering siblings fire alongside it and would triple-count).
_COMPILE_EVENT_FRAGMENT = "backend_compile"


class ContractSentry:
    """Runtime monitor for the three engine contracts (ISSUE 19).

    Parameters
    ----------
    flight: a :class:`..obs.flight.FlightRecorder` to stamp ``compile``
        / ``budget_violation`` / ``reupload`` events into (and to dump
        post-steady recompile snapshots through). ``None`` keeps the
        sentry counters-only.
    label: initial phase label attributed to compile events (default
        ``"warmup"``; :meth:`set_phase` and :meth:`begin_round` move it).
    max_compile_records: how many per-compile ``(label, ms)`` records to
        retain for post-mortem context (counters never truncate).
    """

    def __init__(self, flight: Any = None, label: str = "warmup",
                 max_compile_records: int = 64):
        self._flight = flight
        self.phase = label
        self.steady = False
        # compile probe
        self.n_compiles = 0
        self.n_steady_recompiles = 0
        self.compile_ms_total = 0.0
        self.compile_records: List[dict] = []
        self._max_compile_records = int(max_compile_records)
        self.compile_probe = "off"   # "monitoring" | "pjit" | "off"
        self._listener = None
        self._pjit_orig = None
        # fetch probe
        self.installed = False
        self._real_device_get = None
        self.n_fetched = 0
        self.n_budgeted = 0
        self.n_rounds = 0
        self.n_budget_violations = 0
        self._in_round = False
        self._round_fetched = 0
        self._round_budgeted = 0
        self._round_label: Optional[str] = None
        # re-upload probe
        self.n_reuploads = 0
        self.reupload_bytes = 0
        self.n_checked = 0
        self._reupload_sites: set = set()

    # -- lifecycle ---------------------------------------------------------

    def install(self) -> "ContractSentry":
        """Activate the compile listener and the fetch-counting
        ``jax.device_get`` wrapper. Idempotent; pair with
        :meth:`uninstall` (or use the sentry as a context manager) so a
        test-scoped sentry never leaks its global hooks."""
        if self.installed:
            return self
        import jax

        self._install_compile_probe()
        real = jax.device_get
        sentry = self

        def _sentry_device_get(x):
            sentry.n_fetched += 1
            if sentry._in_round:
                sentry._round_fetched += 1
            return real(x)

        # marker so uninstall only restores OUR wrapper (a later
        # monkeypatch spy layered on top is the spy's to undo)
        _sentry_device_get._contract_sentry = self  # type: ignore
        self._real_device_get = real
        jax.device_get = _sentry_device_get
        self.installed = True
        return self

    def uninstall(self) -> None:
        if not self.installed:
            return
        import jax

        current = jax.device_get
        if getattr(current, "_contract_sentry", None) is self:
            jax.device_get = self._real_device_get
        self._real_device_get = None
        self._uninstall_compile_probe()
        self.installed = False

    def __enter__(self) -> "ContractSentry":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    def mark_steady(self) -> None:
        """Declare the warmup boundary (the ``flight.reset()`` seam):
        every compilation from here on is a steady-state recompile —
        the violation the zero-recompile serving contract forbids."""
        self.steady = True
        self.phase = "steady"

    def set_phase(self, label: str) -> None:
        """Attribute subsequent compile events to ``label``."""
        self.phase = str(label)

    # -- compile probe -----------------------------------------------------

    def _install_compile_probe(self) -> None:
        try:
            from jax import monitoring

            sentry = self

            def _listener(event: str, duration_secs: float, **kw):
                if _COMPILE_EVENT_FRAGMENT in event:
                    sentry._on_compile(duration_secs * 1000.0)

            monitoring.register_event_duration_secs_listener(_listener)
            self._listener = _listener
            self.compile_probe = "monitoring"
            return
        except Exception:
            pass
        try:
            # fallback for jax builds without the monitoring API: count
            # pjit cache-miss lowerings (one per compilation; wall ms
            # unknown from here, recorded as 0.0)
            from jax._src import pjit as _pjit

            orig = _pjit._pjit_lower
            sentry = self

            def _counting_lower(*args, **kwargs):
                sentry._on_compile(0.0)
                return orig(*args, **kwargs)

            _pjit._pjit_lower = _counting_lower
            self._pjit_orig = orig
            self.compile_probe = "pjit"
        except Exception:
            self.compile_probe = "off"

    def _uninstall_compile_probe(self) -> None:
        if self._listener is not None:
            try:
                from jax._src import monitoring as _mon

                _mon._unregister_event_duration_listener_by_callback(
                    self._listener
                )
            except Exception:
                pass
            self._listener = None
        if self._pjit_orig is not None:
            try:
                from jax._src import pjit as _pjit

                _pjit._pjit_lower = self._pjit_orig
            except Exception:
                pass
            self._pjit_orig = None
        self.compile_probe = "off"

    def _on_compile(self, ms: float) -> None:
        self.n_compiles += 1
        self.compile_ms_total += ms
        record = {
            "label": self.phase, "ms": round(ms, 3),
            "steady": self.steady,
        }
        if len(self.compile_records) < self._max_compile_records:
            self.compile_records.append(record)
        if self.steady:
            self.n_steady_recompiles += 1
        if self._flight is not None:
            ev = self._flight.record(
                "compile", label=self.phase, ms=round(ms, 3),
                steady=self.steady,
            )
            if self.steady:
                # the violation dump: plain compile events never dump
                # (warmup compiles are normal), a POST-STEADY one is the
                # zero-recompile contract breaking — snapshot it now,
                # named by its phase label
                self._flight.dump(reason="compile", trigger=ev)

    # -- fetch probe -------------------------------------------------------

    def begin_round(self, label: Optional[str] = None) -> None:
        """Open one scheduling-round accounting window (the engine calls
        this at the top of ``step()``). Fetches outside a round — warmup,
        reference decodes, receipt assembly — never count against the
        budget."""
        self._in_round = True
        self._round_label = label
        self._round_fetched = 0
        self._round_budgeted = 0
        if label is not None:
            self.phase = str(label)

    def budgeted_fetch(self) -> None:
        """A budgeted engine call site is about to fetch (routed through
        ``ServeEngine._sentry_fetch``) — the fetch it precedes is inside
        the declared budget."""
        self.n_budgeted += 1
        if self._in_round:
            self._round_budgeted += 1

    def end_round(self) -> None:
        """Close the round; ``fetched > budgeted`` is a violation (one
        ``budget_violation`` event, auto-dumped via the recorder's fault
        path)."""
        if not self._in_round:
            return
        self._in_round = False
        self.n_rounds += 1
        if self._round_fetched > self._round_budgeted:
            self.n_budget_violations += 1
            if self._flight is not None:
                self._flight.record(
                    "budget_violation",
                    fetched=self._round_fetched,
                    budgeted=self._round_budgeted,
                    round=self._round_label or f"round {self.n_rounds}",
                )

    # -- re-upload probe ---------------------------------------------------

    def check_args(self, tree: Any, label: str = "dispatch") -> int:
        """Walk ``tree`` for host-``numpy`` leaves (each one re-uploads
        H2D on EVERY dispatch — pin restored trees with
        ``utils.tree.device_materialize``). Returns the host bytes
        found; 0 means clean. Isinstance checks only — never fetches."""
        self.n_checked += 1
        import jax

        host = [
            leaf for leaf in jax.tree_util.tree_leaves(tree)
            if isinstance(leaf, np.ndarray)
        ]
        if not host:
            return 0
        nbytes = sum(int(leaf.nbytes) for leaf in host)
        self.n_reuploads += 1
        self.reupload_bytes += nbytes
        if label not in self._reupload_sites:
            self._reupload_sites.add(label)
            if self._flight is not None:
                # first occurrence per site announces (and auto-dumps);
                # later occurrences only accumulate the counters — the
                # per-call repetition is visible as n_reuploads >> sites
                self._flight.record(
                    "reupload", label=label, n_leaves=len(host),
                    bytes=nbytes,
                )
        return nbytes

    # -- receipt surface ---------------------------------------------------

    def summary(self) -> dict:
        """Flat receipt-ready aggregate (``sentry_*`` keys). ``sentry``
        itself is CONFIG; the rest are outcomes."""
        return {
            "sentry": 1,
            "sentry_compiles": self.n_compiles,
            "sentry_steady_recompiles": self.n_steady_recompiles,
            "sentry_compile_ms": round(self.compile_ms_total, 3),
            "sentry_rounds": self.n_rounds,
            "sentry_fetched": self.n_fetched,
            "sentry_budgeted": self.n_budgeted,
            "sentry_budget_violations": self.n_budget_violations,
            "sentry_fetch_budget_ok": int(self.n_budget_violations == 0),
            "sentry_reuploads": self.n_reuploads,
            "sentry_reupload_bytes": self.reupload_bytes,
        }
