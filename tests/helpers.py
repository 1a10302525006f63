"""Shared test fixtures-as-functions (imported, not auto-injected)."""

import functools

import numpy as np
import pytest

from pytorch_distributed_training_tutorials_tpu.data.datasets import ArrayDataset


@functools.lru_cache(maxsize=1)
def pallas_interpret_available() -> bool:
    """Probe (once) whether Pallas Mosaic-interpret mode can execute a
    trivial kernel on this host — the CPU-mesh execution mode of every TPU
    kernel test (flash attention, int8 matmul, fused loss/optimizer).
    False on builds whose jax ships without the Pallas interpreter."""
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:] + 1.0

        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=True,
        )(jnp.zeros((8, 128), jnp.float32))
        return bool((np.asarray(out) == 1.0).all())
    except Exception:
        return False


# module-level `pytestmark = requires_pallas_interpret` (or per-test) skips
# kernel tests cleanly where the interpreter is unavailable
requires_pallas_interpret = pytest.mark.skipif(
    not pallas_interpret_available(),
    reason="Pallas Mosaic-interpret mode unavailable on this host",
)


def make_cls_dataset(n=256, dim=16, classes=4, seed=0, noise=0.1):
    """Class-separable synthetic classification data: fixed random class
    centers + gaussian noise (the same recipe as datasets._synthetic_images,
    in flat-feature form)."""
    rng = np.random.Generator(np.random.PCG64(seed))
    labels = rng.integers(0, classes, n).astype(np.int32)
    centers = rng.standard_normal((classes, dim)).astype(np.float32) * 3
    x = centers[labels] + noise * rng.standard_normal((n, dim)).astype(
        np.float32
    )
    return ArrayDataset((x, labels))


def pallas_operands(fn, *args, name=None):
    """``[(dtype, shape), ...]`` of the operands of every ``pallas_call``
    in the jaxpr of ``fn(*args)``, inner jaxprs (a scan's or a
    ``shard_map``'s body) included; ``name`` keeps the kernels of that
    ``name=`` alone."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" and name in (
                None, eqn.params["name"]
            ):
                found.append(
                    [(str(v.aval.dtype), v.aval.shape) for v in eqn.invars]
                )
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found
