"""Distributed runtime bootstrap: the TPU twin of the reference's L1 layer.

The reference has two bootstrap flavors (SURVEY.md C1/C2) whose *only* delta is
where rank/world-size/rendezvous come from:

- **spawn flavor** (reference ``ddp_gpus.py:12-17``): explicit
  ``rank``/``world_size`` arguments plus a hardcoded
  ``MASTER_ADDR=localhost, MASTER_PORT=12345`` TCPStore rendezvous.
- **torchrun flavor** (reference ``ddp_gpus_torchrun.py:12-14``): everything is
  read from launcher-injected environment variables.

:func:`init` keeps that seam but with one code path: pass explicit
``coordinator_address``/``num_processes``/``process_id`` for the spawn
contract, pass nothing for the environmental contract
(``jax.distributed.initialize()`` autodetects on TPU pods from the runtime
metadata, and honors ``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/
``JAX_PROCESS_ID`` env vars — the torchrun contract). Single-process runs
(one host, N local chips — the reference's ``nn.DataParallel`` setting) need no
initialization at all, and :func:`init` detects that and no-ops.

Teardown (reference ``destroy_process_group()``, ``ddp_gpus.py:93``) is
:func:`shutdown`.
"""

from __future__ import annotations

import os

import jax

# Default rendezvous endpoint for the spawn-style contract; twin of the
# reference's hardcoded MASTER_ADDR/MASTER_PORT (ddp_gpus.py:13-14).
DEFAULT_COORDINATOR = "localhost:12355"

_initialized = False


def init(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    local_device_ids: list[int] | None = None,
) -> None:
    """Initialize the multi-process runtime (no-op for single-process runs).

    Spawn contract (explicit args, reference ``ddp_gpus.py:12-17``)::

        init("localhost:12355", num_processes=4, process_id=rank)

    Environmental contract (reference ``ddp_gpus_torchrun.py:12-14``; the
    launcher — a pod launcher or :mod:`..launch` — injects
    ``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``, or a
    TPU pod autodetects from runtime metadata)::

        init()
    """
    global _initialized
    if _initialized:
        return

    env_keys = [
        "JAX_COORDINATOR_ADDRESS",
        "JAX_NUM_PROCESSES",
        "JAX_PROCESS_ID",
        "COORDINATOR_ADDRESS",
    ]
    env_driven = any(k in os.environ for k in env_keys)
    # TPU pod metadata only counts as a topology signal when we're actually
    # going to run on TPU — a CPU-forced run (tests, notebooks) on a TPU VM
    # must not try to rendezvous against the pod runtime — and only when it
    # names MORE than one host: a one-host TPU machine sets
    # TPU_WORKER_HOSTNAMES too (to its own name), and there one process
    # drives every local chip with nothing to rendezvous with.
    if not os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
        env_driven = (
            env_driven
            or len([h for h in hosts if h.strip()]) > 1
            or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ
        )
    explicit = coordinator_address is not None or num_processes is not None

    if not explicit and not env_driven:
        # Single-process, possibly multi-chip: the nn.DataParallel setting.
        # jax.distributed.initialize is unnecessary and would hang waiting for
        # peers; device "pinning" is implicit in the TPU topology.
        return

    # The env contract: jax reads JAX_COORDINATOR_ADDRESS natively, but has
    # no JAX_NUM_PROCESSES/JAX_PROCESS_ID autodetection outside managed
    # clusters (SLURM/MPI/Cloud TPU metadata) — so this layer provides it,
    # completing the torchrun-style env seam (RANK/WORLD_SIZE twin).
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])

    kwargs: dict = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)
    _initialized = True


def shutdown() -> None:
    """Tear down the multi-process runtime.

    Twin of the reference's ``destroy_process_group()`` (``ddp_gpus.py:93``,
    ``ddp_gpus_torchrun.py:88``). Safe to call when :func:`init` no-opped.
    """
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def process_index() -> int:
    """This process's rank. Twin of ``RANK`` / ``dist.get_rank()``."""
    return jax.process_index()


def process_count() -> int:
    """Number of processes. Twin of ``WORLD_SIZE`` / ``dist.get_world_size()``."""
    return jax.process_count()


def is_primary() -> bool:
    """True on the logging process (the reference's rank-0 convention)."""
    return jax.process_index() == 0
