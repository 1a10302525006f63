"""Multi-process spawn launcher: the ``mp.spawn`` twin.

Twin of the reference's launcher (``ddp_gpus.py:104-105``): fork ``nprocs``
workers, inject the rank as the target's first argument, join, and surface
child failures. The TPU-native differences:

- each worker is a full jax.distributed *process* (one per host on a real
  pod); the worker body calls :func:`..parallel.distributed.init` itself —
  either explicitly (spawn contract) or from env (torchrun contract,
  ``env_contract=True`` here plays the torchrun agent and injects
  ``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``).
- ``platform="cpu"`` runs the world on CPU devices with gloo collectives —
  the hardware-free multi-process harness (SURVEY.md section 4's
  "multi-node testing without a cluster").
- several processes on ONE host's accelerators are refused: a chip belongs
  to one process at a time and nothing here binds a child to a chip of its
  own, so ``nprocs > 1`` needs ``platform="cpu"``. One process drives every
  local chip (``nprocs=1``); one process per HOST is :mod:`.pod`'s job.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import socket
from collections.abc import Callable, Sequence

DEFAULT_JOIN_TIMEOUT_S = 300.0


def pick_unused_port() -> int:
    """An OS-assigned free TCP port for the coordinator rendezvous."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _worker_env(
    rank: int,
    nprocs: int,
    coordinator: str,
    platform: str | None,
    env_contract: bool,
    devices_per_process: int,
) -> dict[str, str | None]:
    """Env delta for one child. ``None`` value = remove the variable."""
    env: dict[str, str | None] = {}
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
        if platform == "cpu":
            flags = os.environ.get("XLA_FLAGS", "")
            flags = " ".join(
                f for f in flags.split() if "host_platform_device_count" not in f
            )
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{devices_per_process}"
            ).strip()
    if env_contract:
        # Play the torchrun agent: rendezvous + env injection
        # (reference 02.ddp_toy_example.ipynb cells 11-12).
        env["JAX_COORDINATOR_ADDRESS"] = coordinator
        env["JAX_NUM_PROCESSES"] = str(nprocs)
        env["JAX_PROCESS_ID"] = str(rank)
    return env


def _bootstrap(env_delta: dict, target: Callable, rank: int, args: Sequence):
    """Child-process entry: apply the env delta *inside the child* (before
    jax import/init in ``target``), then run ``target(rank, *args)``.

    Keeping the delta out of the parent's ``os.environ`` means concurrent
    ``spawn()`` calls (or other parent threads reading env mid-launch) can
    never observe another rank's ``JAX_PROCESS_ID``/``JAX_PLATFORMS``.
    """
    for k, v in env_delta.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    if env_delta.get("JAX_PLATFORMS"):
        # The child unpickles ``target`` before this function runs, and
        # importing the target's module usually imports jax, which reads
        # JAX_PLATFORMS at import: the env var alone is then too late, so
        # forward it through the config API as well (backends are not
        # initialized yet).
        import jax

        jax.config.update("jax_platforms", env_delta["JAX_PLATFORMS"])
    target(rank, *args)


def _run_world(
    target: Callable,
    nprocs: int,
    args: Sequence,
    coordinator: str,
    platform: str | None,
    env_contract: bool,
    devices_per_process: int,
    join_timeout_s: float,
) -> list[tuple[int, int | None]]:
    """Fork one world and monitor it. Returns ``[(rank, exitcode|None)]``
    failures (empty on success).

    Monitoring is a poll loop with **early gang abort**: the moment any rank
    exits non-zero, the surviving ranks — likely blocked in a collective
    waiting for the dead peer — are terminated instead of being left to hang
    until the join timeout. This is the failure-*detection* half of the
    torchrun elastic agent's contract (SURVEY.md section 5.3).
    """
    import time

    ctx = mp.get_context("spawn")
    procs: list[mp.Process] = []
    try:
        for rank in range(nprocs):
            # Each child's env delta rides the process args and is applied by
            # _bootstrap inside the child — the parent's env is never touched.
            delta = _worker_env(
                rank, nprocs, coordinator, platform, env_contract,
                devices_per_process,
            )
            p = ctx.Process(
                target=_bootstrap,
                args=(delta, target, rank, tuple(args)),
                name=f"spawn-rank{rank}",
            )
            p.start()
            procs.append(p)
    except BaseException:
        # A failed start() mid-loop would leave earlier ranks blocked at the
        # rendezvous forever (their world can never reach nprocs) — reap them.
        for p in procs:
            if p.is_alive():
                p.terminate()
            p.join(10)
        raise

    deadline = time.monotonic() + join_timeout_s
    failed: list[tuple[int, int | None]] = []
    while True:
        alive = [p for p in procs if p.is_alive()]
        failed = [
            (r, p.exitcode)
            for r, p in enumerate(procs)
            if not p.is_alive() and p.exitcode != 0
        ]
        if not alive or failed:
            break
        if time.monotonic() > deadline:
            failed = [(r, None) for r, p in enumerate(procs) if p.is_alive()]
            break
        time.sleep(0.1)
    # gang abort: reap survivors of a failed/timed-out world; escalate to
    # SIGKILL for workers stuck in native code ignoring SIGTERM — a restart
    # must never fork a new world while zombies still hold the devices
    if failed:
        for p in procs:
            if p.is_alive():
                p.terminate()
    for p in procs:
        p.join(10)
        if p.is_alive():
            p.kill()
            p.join(10)
    return failed


def _failure_detail(failed: list[tuple[int, int | None]]) -> str:
    return ", ".join(
        f"rank {r}: {'timeout' if c is None else f'exit {c}'}"
        for r, c in failed
    )


def spawn(
    target: Callable,
    nprocs: int,
    args: Sequence = (),
    *,
    coordinator: str | None = None,
    platform: str | None = None,
    env_contract: bool = False,
    devices_per_process: int = 1,
    join_timeout_s: float = DEFAULT_JOIN_TIMEOUT_S,
    max_restarts: int = 0,
) -> None:
    """Fork ``nprocs`` workers running ``target(rank, *args)``; join all.

    Twin of ``mp.spawn(main, args=..., nprocs=world_size)``
    (reference ``ddp_gpus.py:105``): the rank is injected as argument 0.
    ``target`` must be a module-level (picklable) callable; it is responsible
    for calling :func:`..parallel.distributed.init` — with explicit
    ``(coordinator, nprocs, rank)`` for the spawn contract, or bare ``init()``
    with ``env_contract=True`` for the torchrun contract.

    ``max_restarts`` > 0 is the torchrun elastic-agent behavior the reference
    delegates to its launcher (``/root/reference/ddp_gpus_torchrun.py:12-14``
    is written against an agent that rendezvous, monitors, and *restarts*
    workers): when any rank dies, the whole gang is torn down and re-forked —
    same semantics as torchrun, which always restarts the full world — up to
    ``max_restarts`` times, with a fresh rendezvous endpoint per attempt.
    Stateful targets resume from their latest checkpoint
    (:meth:`..train.trainer.Trainer.restore`), turning restart-from-scratch
    into restart-and-resume; proven end-to-end in
    ``tests/test_restart_resume.py``.

    Raises ``RuntimeError`` naming the failed ranks if the final attempt
    fails (the reference inherits this from mp.spawn's error propagation).
    """
    if nprocs < 1:
        raise ValueError(f"nprocs must be >= 1, got {nprocs}")
    if nprocs > 1 and platform != "cpu":
        raise ValueError(
            f"spawn(nprocs={nprocs}, platform={platform!r}): several "
            "processes cannot share one host's accelerators — each child "
            "would load the device library for every local chip, and a "
            "chip belongs to one process at a time. Use nprocs=1 (one "
            "process drives all local chips), platform='cpu' for a "
            "hardware-free world, or launch.pod for one process per host."
        )
    if max_restarts < 0:
        raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
    if max_restarts > 0 and not env_contract and nprocs > 1:
        import warnings

        # Spawn-contract targets receive their rendezvous endpoint through
        # `args`, which the launcher cannot refresh between attempts — a
        # restart would rendezvous on the dead world's endpoint. The env
        # contract (launcher-injected JAX_COORDINATOR_ADDRESS) restarts
        # cleanly; that asymmetry is exactly torchrun's (elasticity lives in
        # the agent, not in mp.spawn).
        warnings.warn(
            "spawn(max_restarts>0) with the explicit-coordinator contract "
            "reuses the coordinator baked into `args` across restarts; use "
            "env_contract=True for restart-safe rendezvous",
            stacklevel=2,
        )
    for attempt in range(max_restarts + 1):
        # Fresh rendezvous port per attempt unless the caller pinned one (a
        # dead world's coordinator socket may linger in TIME_WAIT).
        att_coordinator = coordinator or f"localhost:{pick_unused_port()}"
        failed = _run_world(
            target, nprocs, args, att_coordinator, platform, env_contract,
            devices_per_process, join_timeout_s,
        )
        if not failed:
            return
        if attempt < max_restarts:
            print(
                f"spawn: world failed ({_failure_detail(failed)}); "
                f"restarting ({attempt + 1}/{max_restarts})"
            )
            continue
        raise RuntimeError(
            f"spawn: {len(failed)}/{nprocs} workers failed "
            f"({_failure_detail(failed)})"
        )


def coordinator_for_spawn(port: int | None = None) -> str:
    """The spawn contract's rendezvous endpoint (twin of the reference's
    hardcoded ``MASTER_ADDR=localhost, MASTER_PORT=12345``,
    ``ddp_gpus.py:13-14``) — but with an OS-assigned port by default, since
    a hardcoded port is exactly what makes the reference flaky to re-run."""
    return f"localhost:{port if port is not None else pick_unused_port()}"
