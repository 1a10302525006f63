"""Separate the fixed per-launch cost from true per-op device time.

Motivation: a short-chain timing divided by its length reads as a per-op
floor at decode shapes (M=4) whatever the path — Pallas int8, XLA dequant
and plain bf16 dots alike. This probe shows whether that number is an
ARTIFACT: wall(chain) fits ``fixed + per_op * len``, and varying the chain
length separates the terms:

- fixed per-launch (launch + one-element fetch roundtrip), probed for
  2 vs 256 argument buffers and for 1 GB vs 1 KB of resident argument
  bytes;
- per-op device time at (4, 2048) x (2048, 8192): bf16 dot against the
  Pallas int8 kernel (which reads half the bytes);
- stalls of individual launches (min-of-N or the fit below are
  mandatory).

On the chip the tool provides none of the three is measured yet; the
ROADMAP asks for this probe before ``tokens_per_launch`` is tuned. *Bigger
timed regions* (longer chains, fused decode loops) are the honest way to
measure serving-decode latency either way. The measurement machinery lives
in ``obs.timing`` (:class:`MinOfN` rejects stalls,
:func:`launch_overhead_fit` is the two-length fit); each probe prints an
``obs.receipt``-schema'd JSON line.

Usage: python scripts/launch_overhead_probe.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tutorials_tpu.obs import MinOfN, launch_overhead_fit, make_receipt
    from pytorch_distributed_training_tutorials_tpu.ops.quant import (
        int8_matmul,
        quantize_int8,
    )

    key = jax.random.PRNGKey(0)
    m, k, n = 4, 2048, 8192
    kx, kw = jax.random.split(key)
    x = jax.device_put(jax.random.normal(kx, (m, k), jnp.float32))
    wb = jax.device_put(jax.random.normal(kw, (k, n), jnp.bfloat16))
    wq = jax.device_put(quantize_int8(jax.random.normal(kw, (k, n), jnp.float32)))

    def make_time_chain(body):
        def time_chain(length: int) -> float:
            @jax.jit
            def run(x0):
                return jax.lax.scan(body, x0, None, length=length)

            def timed():
                _, ys = run(x)
                float(ys[-1])  # the honest close: one real fetch

            # MinOfN's warmup run compiles + primes the first fetch
            return MinOfN(n=4).measure(timed).best_s

        return time_chain

    def bf16_body(c, _):
        y = jnp.dot(c.astype(jnp.bfloat16), wb).astype(jnp.float32)
        return c + y[:, :1] * 1e-9, y[0, 0]

    def int8_body(c, _):
        y = int8_matmul(c, wq)
        return c + y[:, :1] * 1e-9, y[0, 0]

    for name, body in [("bf16_dot", bf16_body), ("pallas_int8", int8_body)]:
        fit = launch_overhead_fit(make_time_chain(body), lens=(64, 1024))
        receipt = make_receipt("launch_probe", {
            "body": name,
            "shape": [m, k, n],
            "wall_ms": {
                str(ln): round(w * 1e3, 1)
                for ln, w in zip(fit.lens, fit.wall_s)
            },
            "per_op_us": round(fit.per_op_us, 1),
            "fixed_launch_ms": round(fit.fixed_ms, 1),
            "naive_32chain_would_report_ms_per_op": round(
                fit.naive_per_op_us(32) / 1e3, 2
            ),
        })
        print(json.dumps(receipt))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
