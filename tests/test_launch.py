"""Multi-process launch tests: the spawn and torchrun contracts, hardware-free.

The reference proves its two launch contracts by running them on one host
(``mp.spawn`` 4-proc, ``torchrun`` 1- and 4-proc — SURVEY.md section 3.1/3.2).
The JAX-native analog (SURVEY.md section 4c): fork real OS processes that form
a jax.distributed world over CPU devices with gloo collectives, and run the
actual training workload through it. Assertions live *inside* the workers —
a failed assert exits non-zero and :func:`spawn` surfaces it.
"""

import os
import subprocess
import sys

import pytest

from pytorch_distributed_training_tutorials_tpu.launch import (
    coordinator_for_spawn,
    spawn,
)

NPROCS = 2


def _spawn_worker(rank: int, world: int, coordinator: str) -> None:
    """Spawn-contract worker: explicit (coordinator, world, rank) init —
    the reference's ddp_setup(rank, world_size) twin (ddp_gpus.py:12-17)."""
    from pytorch_distributed_training_tutorials_tpu.parallel import distributed

    distributed.init(coordinator, num_processes=world, process_id=rank)
    import jax
    import optax

    from pytorch_distributed_training_tutorials_tpu.data import (
        ShardedLoader,
        synthetic_regression,
    )
    from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor
    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
    from pytorch_distributed_training_tutorials_tpu.train import Trainer

    assert jax.process_count() == world, jax.process_count()
    mesh = create_mesh()
    assert mesh.devices.size == world  # 1 CPU device per process
    loader = ShardedLoader(synthetic_regression(256), 32, mesh)
    trainer = Trainer(LinearRegressor(), loader, optax.sgd(1e-2), loss="mse")
    metrics = trainer.train(2)
    # steps-per-epoch math across a REAL process boundary:
    # 256 samples / 32 per device / `world` devices
    assert metrics["steps"] == 256 // 32 // world, metrics
    assert metrics["loss"] == metrics["loss"]  # not NaN
    distributed.shutdown()


@pytest.mark.skip(
    reason="this jaxlib's CPU backend rejects multiprocess collectives "
    "('Multiprocess computations aren't implemented on the CPU backend') "
    "— the contract needs a real multi-host runtime"
)
def test_spawn_contract_two_process_training():
    coordinator = coordinator_for_spawn()
    spawn(
        _spawn_worker,
        NPROCS,
        args=(NPROCS, coordinator),
        coordinator=coordinator,
        platform="cpu",
    )


@pytest.mark.skip(
    reason="this jaxlib's CPU backend rejects multiprocess collectives "
    "('Multiprocess computations aren't implemented on the CPU backend') "
    "— the contract needs a real multi-host runtime"
)
def test_env_contract_two_process_training():
    """The torchrun twin: workers never see a rank argument — topology comes
    entirely from launcher-injected env (JAX_COORDINATOR_ADDRESS/...)."""
    from pytorch_distributed_training_tutorials_tpu.launch.train_ddp_env import (
        env_worker,
    )

    spawn(
        env_worker,
        NPROCS,
        args=(1, 32),  # max_epochs, batch_size
        env_contract=True,
        platform="cpu",
    )


def test_spawn_surfaces_worker_failure():
    with pytest.raises(RuntimeError, match="workers failed"):
        spawn(_failing_worker, 1, platform="cpu")


def _failing_worker(rank: int) -> None:
    raise SystemExit(3)


@pytest.mark.parametrize(
    "hosts,rendezvous", [("localhost", False), ("host-a,host-b", True)]
)
def test_init_rendezvous_only_for_a_multi_host_tpu_env(
    monkeypatch, hosts, rendezvous
):
    """A one-host TPU machine sets TPU_WORKER_HOSTNAMES to its own name:
    bare ``init()`` must not call ``jax.distributed.initialize()`` there
    (it would look for peers that do not exist); a list of several hosts
    is a pod and does rendezvous."""
    import jax

    from pytorch_distributed_training_tutorials_tpu.parallel import (
        distributed,
    )

    calls = []
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: calls.append(kw)
    )
    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", hosts)
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID", "COORDINATOR_ADDRESS",
              "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(k, raising=False)
    distributed.init()
    assert bool(calls) is rendezvous
    monkeypatch.setattr(distributed, "_initialized", False)


@pytest.mark.parametrize("platform", [None, "tpu"])
def test_spawn_refuses_several_processes_on_one_hosts_chips(platform):
    """A chip belongs to one process at a time and no child is bound to a
    chip of its own: more than one process needs platform='cpu'. The
    refusal comes before any child starts."""
    with pytest.raises(ValueError, match="one process at a time"):
        spawn(_failing_worker, 4, platform=platform)


@pytest.mark.slow
def test_cli_end_to_end_subprocess():
    """The full CLI surface: `python -m ...train_ddp --nprocs 2 --platform
    cpu` reproduces the reference's sharding proof (Steps 32 = 2048/32/2,
    the `Steps 16` lesson of 02.ipynb cell 10 at a 2-device world)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytorch_distributed_training_tutorials_tpu.launch.train_ddp",
            "--max_epochs", "1", "--batch_size", "32",
            "--nprocs", "2", "--platform", "cpu",
        ],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[Chips: 2 Epoch: 0, Batch size: 32 | Steps 32]" in out.stdout, (
        out.stdout
    )
