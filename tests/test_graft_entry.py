"""The driver contract: ``__graft_entry__`` must certify on any host.

A driver may import ``dryrun_multichip`` and call it under an ambient
``JAX_PLATFORMS`` that names a backend which does not load; certification
would then record ``ok=false`` even though the sharding code is correct on
a CPU mesh. The function re-execs itself into a scrubbed virtual-CPU-mesh
child; these tests pin that posture.
"""

import importlib.util
import os
import sys

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "graft_entry_under_test",
    os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"),
)
graft = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(graft)


def test_child_env_forces_cpu_mesh():
    hostile = {
        "JAX_PLATFORMS": "no_such_backend",
        "XLA_FLAGS": "--foo --xla_force_host_platform_device_count=2",
        "PATH": "/usr/bin",
    }
    env = graft._child_env(8, base=hostile)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env[graft._CHILD_MARKER] == "1"
    # stale force-count replaced, unrelated flags kept
    assert env["XLA_FLAGS"] == "--foo --xla_force_host_platform_device_count=8"
    assert env["PATH"] == "/usr/bin"


def test_dryrun_reexecs_unless_child(monkeypatch):
    calls = []
    monkeypatch.delenv(graft._CHILD_MARKER, raising=False)
    monkeypatch.setattr(graft, "_certify_in_child", calls.append)
    monkeypatch.setattr(
        graft, "_dryrun_impl", lambda n: pytest.fail("impl ran in parent")
    )
    graft.dryrun_multichip(8)
    assert calls == [8]


def test_dryrun_runs_impl_in_child(monkeypatch):
    calls = []
    monkeypatch.setenv(graft._CHILD_MARKER, "1")
    monkeypatch.setattr(graft, "_dryrun_impl", calls.append)
    monkeypatch.setattr(
        graft,
        "_certify_in_child",
        lambda n: pytest.fail("re-exec loop in child"),
    )
    graft.dryrun_multichip(4)
    assert calls == [4]


def test_certify_prefers_real_hardware(monkeypatch):
    """A healthy ambient backend with enough devices certifies on hardware."""
    runs = []
    monkeypatch.setattr(graft, "_ambient_device_count", lambda: 8)
    monkeypatch.setattr(
        graft,
        "_run_child",
        lambda n, env, what: runs.append((n, env.get("JAX_PLATFORMS"), what))
        or 0,
    )
    graft._certify_in_child(8)
    assert len(runs) == 1 and runs[0][2] == "ambient backend"
    assert runs[0][1] == os.environ.get("JAX_PLATFORMS")


def test_certify_falls_back_to_cpu_mesh(monkeypatch):
    """Broken/insufficient ambient backend -> scrubbed CPU-mesh child."""
    runs = []
    monkeypatch.setattr(graft, "_ambient_device_count", lambda: 1)
    monkeypatch.setattr(
        graft,
        "_run_child",
        lambda n, env, what: runs.append((env["JAX_PLATFORMS"], what)) or 0,
    )
    graft._certify_in_child(8)
    assert runs == [("cpu", "CPU mesh")]


def test_certify_ambient_failure_falls_back(monkeypatch):
    """Ambient backend has the devices but dies at run time (round-1 libtpu
    mismatch fires only on execution) -> still certifies on the CPU mesh."""
    runs = []
    monkeypatch.setattr(graft, "_ambient_device_count", lambda: 8)
    monkeypatch.setattr(
        graft,
        "_run_child",
        lambda n, env, what: runs.append(what) or (1 if what == "ambient backend" else 0),
    )
    graft._certify_in_child(8)
    assert runs == ["ambient backend", "CPU mesh"]


def test_entry_is_jittable():
    import jax

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)


@pytest.mark.slow
def test_dryrun_end_to_end_under_hostile_env(monkeypatch):
    """Full certification path under a hostile ambient platform."""
    monkeypatch.delenv(graft._CHILD_MARKER, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_backend")
    graft.dryrun_multichip(2)  # raises on child failure
