"""Fused AdamW: the optimizer update as one Pallas pass per parameter.

The reference's update is an opaque ``optimizer.step()`` (reference
``ddp_gpus.py:39``); the optax twin (``optax.adamw``) traces to a chain of
~10 elementwise HLO ops per leaf — moment decay, bias correction, rsqrt,
weight decay, learning-rate scale — whose fusion boundaries XLA draws per
op-group, re-reading moments and params from HBM along the way. The
optimizer tail does zero matmul work; its floor is pure HBM bandwidth:
read each of grad/m/v/param once, write update/m/v once. This module
states that floor as a single Pallas kernel per leaf (``interpret=True``
off-TPU, the house pattern), with the moment buffers aliased in-place
(``input_output_aliases``) so XLA doesn't double-buffer them.

``fused_adamw`` is a drop-in :class:`optax.GradientTransformation` with
``optax.adamw``'s exact update math (``scale_by_adam`` with bias-corrected
moments, decoupled weight decay, ``-lr`` scaling): 100-step trajectory
equivalence is pinned by ``tests/test_fused_optim.py``. The Trainer's
``_apply_update`` consumes it unchanged — including the ISSUE 9 skip-step
guard (``Trainer(skip_nonfinite=True)``): the guard's ``jnp.where``
select runs AFTER ``tx.update`` on the update's outputs, so even with the
moment buffers aliased in-place here, a skipped step keeps params,
``mu``/``nu``, and ``count`` bitwise unchanged (XLA copies a donated
buffer whose pre-update value is still live in the select;
``tests/test_trainer.py::test_skip_step_through_grad_accum_and_fused_adamw``
pins it).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128  # fixed lane width; leaves are repacked to (rows, 128)


class FusedAdamWState(NamedTuple):
    """``optax.adamw``'s state fields (count + first/second moments)."""

    count: jax.Array
    mu: optax.Updates
    nu: optax.Updates


def _adamw_kernel(
    g_ref, m_ref, v_ref, p_ref, c_ref, u_ref, mo_ref, vo_ref,
    *, lr: float, b1: float, b2: float, eps: float, wd: float,
):
    """One row-block: grad/m/v/param in, update/m/v out — every value is
    touched exactly once."""
    g = g_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:].astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_ref[:].astype(jnp.float32) + (1.0 - b2) * g * g
    # c = (1 - b1^t, 1 - b2^t), precomputed on host-side scalars (SMEM)
    m_hat = m / c_ref[0, 0]
    v_hat = v / c_ref[0, 1]
    p = p_ref[:].astype(jnp.float32)
    u = -lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)
    u_ref[:] = u.astype(u_ref.dtype)
    mo_ref[:] = m.astype(mo_ref.dtype)
    vo_ref[:] = v.astype(vo_ref.dtype)


def _leaf_update(
    g, m, v, p, c,
    *, lr, b1, b2, eps, wd, block_rows: int, interpret: bool,
):
    """Run the kernel over one (arbitrary-shape) leaf: flatten to
    (rows, 128) lanes, pad to an 8-aligned row block, unpack after."""
    shape, size = p.shape, p.size
    rows = -(-size // _LANES)
    rows8 = -(-max(rows, 8) // 8) * 8
    br = min(-(-block_rows // 8) * 8, rows8)
    rp = -(-rows8 // br) * br

    def pack(a):
        flat = jnp.pad(a.reshape(-1), (0, rp * _LANES - size))
        return flat.reshape(rp, _LANES)

    spec = pl.BlockSpec(
        (br, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    u2, m2, v2 = pl.pallas_call(
        functools.partial(
            _adamw_kernel, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd
        ),
        grid=(rp // br,),
        in_specs=[
            spec, spec, spec, spec,
            pl.BlockSpec(
                (1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rp, _LANES), p.dtype),
            jax.ShapeDtypeStruct((rp, _LANES), m.dtype),
            jax.ShapeDtypeStruct((rp, _LANES), v.dtype),
        ],
        # moments update in place — no double-buffered m/v in HBM
        input_output_aliases={1: 1, 2: 2},
        name="fused_adamw",
        interpret=interpret,
    )(pack(g), pack(m), pack(v), pack(p), c)

    def unpack(a):
        return a.reshape(-1)[:size].reshape(shape)

    return unpack(u2), unpack(m2), unpack(v2)


def fused_adamw(
    learning_rate: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-4,
    *,
    mask=None,
    block_rows: int = 1024,
    interpret: bool | None = None,
) -> optax.GradientTransformation:
    """Drop-in ``optax.adamw`` with the update fused to one kernel pass
    per leaf (same defaults and update math as ``optax.adamw``; decay is
    applied to every updated leaf).

    ``mask`` (a boolean pytree matching params, or a callable producing
    one — e.g. :func:`..adapters.lora.lora_param_mask`) restricts the
    update to the True leaves: masked-out leaves get a hard-zero update
    (``optax.set_to_zero``, not a pass-through of the raw gradient) AND
    no moment buffers — a LoRA fine-tune pays optimizer memory only for
    the factor leaves, exactly like ``optax.masked(optax.adamw(...),
    mask)``.

    ``learning_rate`` must be a static float (it is baked into the
    kernel); schedules would need a per-step scalar operand — wrap with
    ``optax.inject_hyperparams`` upstream or use stock ``optax.adamw``
    when a schedule is required. ``interpret=None`` auto-selects Pallas
    interpreter mode off-TPU (the CPU-mesh test path).
    """
    if callable(learning_rate):
        raise TypeError(
            "fused_adamw takes a static float learning_rate (it is baked "
            "into the kernel); use optax.adamw for schedules"
        )
    lr = float(learning_rate)

    def init_fn(params):
        return FusedAdamWState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(jnp.zeros_like, params),
            nu=jax.tree_util.tree_map(jnp.zeros_like, params),
        )

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError(
                "fused_adamw requires params (decoupled weight decay)"
            )
        itp = (
            interpret
            if interpret is not None
            else jax.default_backend() != "tpu"
        )
        count = optax.safe_int32_increment(state.count)
        t = count.astype(jnp.float32)
        # bias corrections (1 - b^t) as a (1, 2) SMEM scalar pair
        c = jnp.stack(
            [1.0 - jnp.float32(b1) ** t, 1.0 - jnp.float32(b2) ** t]
        ).reshape(1, 2)
        leaf = functools.partial(
            _leaf_update,
            lr=lr, b1=b1, b2=b2, eps=eps, wd=weight_decay,
            block_rows=block_rows, interpret=itp,
        )
        flat_g, treedef = jax.tree_util.tree_flatten(updates)
        flat = [
            leaf(g, m, v, p, c)
            for g, m, v, p in zip(
                flat_g,
                jax.tree_util.tree_leaves(state.mu),
                jax.tree_util.tree_leaves(state.nu),
                jax.tree_util.tree_leaves(params),
            )
        ]
        new_u = jax.tree_util.tree_unflatten(treedef, [f[0] for f in flat])
        new_m = jax.tree_util.tree_unflatten(treedef, [f[1] for f in flat])
        new_v = jax.tree_util.tree_unflatten(treedef, [f[2] for f in flat])
        return new_u, FusedAdamWState(count=count, mu=new_m, nu=new_v)

    tx = optax.GradientTransformation(init_fn, update_fn)
    if mask is None:
        return tx

    def inverted(params):
        m = mask(params) if callable(mask) else mask
        return jax.tree_util.tree_map(lambda b: not b, m)

    # masked kernel on the trainable leaves + hard zero on the frozen
    # ones: apply_updates then adds exact 0.0, so frozen leaves never
    # drift (a bare optax.masked would pass the RAW GRADIENT through as
    # the masked-out "update")
    return optax.chain(
        optax.masked(tx, mask),
        optax.masked(optax.set_to_zero(), inverted),
    )
