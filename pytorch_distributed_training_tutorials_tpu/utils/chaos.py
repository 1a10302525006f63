"""Deterministic fault injection for the robustness layer (ISSUE 9).

The failure paths this repo guards — poison-slot quarantine in
:mod:`..serve.engine`, skip-step / loss-spike rollback in
:mod:`..train.trainer`, request-level prefill isolation — would
otherwise only ever run when real hardware misbehaves. This module
makes them testable on the 8-device CPU mesh: a :class:`ChaosConfig`
names *exactly where* a fault lands (slot, step, request id, chain
index) and the injectors fire there and nowhere else, so every chaos
test is reproducible bit-for-bit run to run.

Two injector families:

- **Device-side** (:func:`poison_logits`, :func:`poison_grads`): pure
  ``jnp.where`` selects inside compiled code — the fault condition is
  DATA (a traced step counter), never Python control flow, so the
  graftcheck ``traced-control-flow`` rule holds and nothing recompiles
  between faulty and clean steps. These are how a NaN *enters* the
  compiled program; the guards under test are how it is contained.
- **Host-side** (:func:`maybe_fail_prefill`, :func:`maybe_stall`,
  :func:`host_spike_loss`): plain Python against host counters —
  raise-at-prefill exercises request-level isolation, the simulated
  launch stall exercises deadline expiry without wall-clock flakiness,
  and the loss spike drives the Trainer's rollback monitor (host-keyed
  so a post-rollback replay does not re-trigger the same spike — the
  restore-and-continue semantics rollback implements).

A third family arrived with the fleet router (ISSUE 12):
**replica-level** injectors (:class:`FleetChaosConfig`,
:func:`replica_killed`, :func:`replica_stall_pending`) simulate a whole
replica dying at a fixed chain count or freezing for N scheduling
rounds — consumed by :class:`..serve.router.FleetRouter`, which is
jax-free, so these are plain host predicates.

The module is jax-free at import (``jax.numpy`` is imported inside the
device-side injectors only when they run): host-only consumers — the
scheduler tests, the selftest argument parser, the fleet router — can
use configs without touching XLA, per the import-purity hard rule.
"""

from __future__ import annotations

import dataclasses
import time


class ChaosError(RuntimeError):
    """The injected prefill failure (:func:`maybe_fail_prefill`). A
    distinct type so tests can assert the engine survived *this* fault
    rather than swallowing an unrelated bug."""


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Where faults land. ``-1`` (the default) disables an injector.

    - ``nan_logit_slot`` / ``nan_logit_step``: overwrite that slot's
      logits row with NaN at that global decode-step index (the engine
      counts scan iterations across chains: chain ``c``'s iteration
      ``i`` is step ``c * tokens_per_launch + i``).
    - ``nan_grad_step``: replace every gradient leaf with NaN at that
      ``TrainState.step`` value (device-side, survives grad-accum — the
      poison lands on the averaged grads). NOTE: with the skip-step
      guard on, ``step`` freezes at the poisoned value, so this injector
      re-fires on every later attempt — state stays protected (the
      guard's whole point) but no further update ever applies. Use it
      for single-step bitwise assertions; for continue-after-fault runs
      use ``nan_batch_step``.
    - ``nan_batch_step``: poison the input batch (first leaf all-NaN) at
      that 1-based host dispatch index — host-keyed and monotonic, so it
      fires exactly ONCE even though the skipped step leaves
      ``TrainState.step`` unchanged (the guarded run continues and its
      final model equals a clean run with that one update elided).
    - ``spike_loss_step`` / ``spike_loss_len`` / ``spike_loss_factor``:
      multiply the loss the Trainer's rollback monitor SEES for
      ``spike_loss_len`` consecutive host steps starting at host step
      ``spike_loss_step`` (1-based, monotonic across rollbacks).
    - ``fail_prefill_request``: raise :class:`ChaosError` when the
      engine is about to prefill that request id.
    - ``stall_chain`` / ``stall_s``: sleep ``stall_s`` seconds before
      dispatching chain index ``stall_chain`` — a deterministic stand-in
      for the multi-second launch stalls CLAUDE.md documents.
    - ``preempt_slot`` / ``preempt_at_chain``: force the SLO engine to
      preempt that slot (KV swap-out to host) at the chain-boundary
      check once its chain counter reaches ``preempt_at_chain`` — the
      swap path is testable without manufacturing real pool pressure.
      Fires exactly ONCE (the engine latches the firing); the victim
      resumes through the ordinary swap-in path, token-exact. Requires
      ``priority_classes > 0`` on the engine; ignored otherwise.
    - ``seed`` rides into receipts so chaos runs are self-describing;
      the injectors themselves are deterministic.
    """

    nan_logit_slot: int = -1
    nan_logit_step: int = -1
    nan_grad_step: int = -1
    nan_batch_step: int = -1
    spike_loss_step: int = -1
    spike_loss_len: int = 1
    spike_loss_factor: float = 100.0
    fail_prefill_request: int = -1
    stall_chain: int = -1
    stall_s: float = 0.0
    preempt_slot: int = -1
    preempt_at_chain: int = -1
    seed: int = 0

    @property
    def poisons_logits(self) -> bool:
        return self.nan_logit_slot >= 0 and self.nan_logit_step >= 0

    @property
    def poisons_grads(self) -> bool:
        return self.nan_grad_step >= 0

    @property
    def poisons_batch(self) -> bool:
        return self.nan_batch_step >= 1

    @property
    def spikes_loss(self) -> bool:
        return self.spike_loss_step >= 0

    @property
    def fails_prefill(self) -> bool:
        return self.fail_prefill_request >= 0

    @property
    def stalls(self) -> bool:
        return self.stall_chain >= 0 and self.stall_s > 0

    @property
    def preempts(self) -> bool:
        return self.preempt_slot >= 0 and self.preempt_at_chain >= 0


@dataclasses.dataclass(frozen=True)
class FleetChaosConfig:
    """Replica-level fault injection for the fleet router (ISSUE 12).
    Same philosophy as :class:`ChaosConfig`: ``-1`` disables an
    injector, every firing is keyed to deterministic host counters
    (replica index, the replica's chain count, the router's own round
    counter) so a chaos fleet run is reproducible bit for bit.

    - ``kill_replica`` / ``kill_at_chain``: the router declares that
      replica dead once its chain counter reaches ``kill_at_chain`` —
      PERMANENTLY (a half-open probe against a chaos-killed replica
      fails, exercising the circuit re-open path). The engine process
      is untouched; death is simulated at the router boundary, which is
      exactly where a real death is observed.
    - ``stall_replica`` / ``stall_from_chain`` / ``stall_rounds``: once
      the replica's chain counter reaches ``stall_from_chain``, the
      router skips stepping it for ``stall_rounds`` scheduling rounds —
      a progress freeze (heartbeat ages, suspicion and hedging fire)
      with no wall-clock sleep, so chaos tests stay fast and flake-free.
    - ``seed`` rides into receipts; the injectors are deterministic.

    The poison-a-replica path needs no new injector: hand ONE replica's
    engine an engine-level :class:`ChaosConfig` with
    ``nan_logit_slot``/``nan_logit_step`` and the router observes the
    resulting fault-stat deltas.
    """

    kill_replica: int = -1
    kill_at_chain: int = -1
    stall_replica: int = -1
    stall_from_chain: int = 0
    stall_rounds: int = 0
    seed: int = 0

    @property
    def kills(self) -> bool:
        return self.kill_replica >= 0 and self.kill_at_chain >= 0

    @property
    def stalls(self) -> bool:
        return self.stall_replica >= 0 and self.stall_rounds > 0


def replica_killed(cfg: FleetChaosConfig, replica: int,
                   n_chains: int) -> bool:
    """True once the configured victim replica has dispatched
    ``kill_at_chain`` chains — and forever after (monotonic counter, so
    a killed replica stays killed across probe attempts)."""
    return (
        cfg.kills
        and replica == cfg.kill_replica
        and n_chains >= cfg.kill_at_chain
    )


def replica_stall_pending(cfg: FleetChaosConfig, replica: int,
                          n_chains: int, rounds_consumed: int) -> bool:
    """True while the configured replica should stay frozen: its chain
    counter passed ``stall_from_chain`` and fewer than ``stall_rounds``
    scheduling rounds have been skipped so far (the router counts the
    skips it performs and passes them back as ``rounds_consumed``)."""
    return (
        cfg.stalls
        and replica == cfg.stall_replica
        and n_chains >= cfg.stall_from_chain
        and rounds_consumed < cfg.stall_rounds
    )


# ---------------------------------------------------------------- device side


def poison_logits(logits, step_index, slot: int, step: int):
    """Return ``logits`` with row ``slot`` set to NaN when the traced
    ``step_index`` equals ``step`` — a ``jnp.where`` select, so the
    fault condition is data and the clean-step program is the same
    program. ``logits`` is the per-slot row block, shape
    ``(n_slots, ...)``; ``slot``/``step`` are Python ints from the
    config (compile-time constants)."""
    import jax.numpy as jnp

    poisoned = logits.at[slot].set(jnp.nan)
    return jnp.where(step_index == step, poisoned, logits)


def poison_grads(grads, step_counter, step: int):
    """Return ``grads`` with every leaf NaN when the traced training
    ``step_counter`` equals ``step`` (otherwise untouched). Lands after
    grad-accum averaging, so the skip-step guard sees exactly what a
    real non-finite reduction would produce."""
    import jax
    import jax.numpy as jnp

    def leaf(g):
        return jnp.where(step_counter == step, jnp.full_like(g, jnp.nan),
                         g)

    return jax.tree_util.tree_map(leaf, grads)


# ------------------------------------------------------------------ host side


def maybe_poison_batch(cfg: ChaosConfig, host_step: int, batch):
    """Return ``batch`` with its first leaf all-NaN when ``host_step``
    (the Trainer's 1-based, monotonic dispatch counter) matches
    ``nan_batch_step``; the batch unchanged otherwise. Elementwise
    multiply, so the leaf keeps its mesh sharding — the NaN flows
    forward into the loss/grads exactly as a corrupt data batch would,
    and the host key guarantees a single firing (see the class
    docstring's livelock note on ``nan_grad_step``)."""
    if not (cfg.poisons_batch and host_step == cfg.nan_batch_step):
        return batch
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(batch)
    leaves[0] = leaves[0] * jnp.nan
    return treedef.unflatten(leaves)


def maybe_fail_prefill(cfg: ChaosConfig, request_id: int) -> None:
    """Raise :class:`ChaosError` when ``request_id`` is the configured
    prefill victim. Called by the engine just before it dispatches the
    prefill/splice for a request."""
    if cfg.fails_prefill and request_id == cfg.fail_prefill_request:
        raise ChaosError(
            f"injected prefill failure for request {request_id}"
        )


def maybe_stall(cfg: ChaosConfig, chain_index: int, flight=None) -> None:
    """Sleep ``stall_s`` before the configured chain index — wall time
    passes (deadlines expire) with zero device-side effect, mimicking a
    launch stall. When a :class:`..obs.flight.FlightRecorder` rides
    along it stamps a ``stall`` event first, so the post-mortem timeline
    shows the gap as INJECTED rather than as a mystery launch stall."""
    if cfg.stalls and chain_index == cfg.stall_chain:
        if flight is not None:
            flight.record(
                "stall", chain=chain_index, stall_s=cfg.stall_s
            )
        time.sleep(cfg.stall_s)


def host_spike_loss(loss_value: float, host_step: int,
                    cfg: ChaosConfig) -> float:
    """The loss value the rollback monitor should see at ``host_step``
    (1-based, never replayed): spiked by ``spike_loss_factor`` inside
    the configured window, untouched outside it. Host-only — the
    compiled step and the real training state never see the spike."""
    if cfg.spikes_loss and (
        cfg.spike_loss_step
        <= host_step
        < cfg.spike_loss_step + cfg.spike_loss_len
    ):
        return float(loss_value) * cfg.spike_loss_factor
    return float(loss_value)
