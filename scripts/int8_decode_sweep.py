"""Sweep int8_matmul block sizes at serving-decode shapes (real chip).

HISTORICAL NOTE (round 4): this sweep's original premise — a per-matmul
"floor" at decode shapes — was wrong. Its 32-long chains divided a fixed
per-LAUNCH roundtrip by their length, which is why every
size/path/blocking "measured" the same: see
scripts/launch_overhead_probe.py for the corrected methodology (fit
wall = fixed + per_op * len over two chain lengths). Kept because the RELATIVE
blocking comparison at fixed chain length is still valid (the fixed term
cancels), just not the absolute per-op numbers.

Decode matmuls are (M=batch, K) @ (K, N) with M tiny (4-8). This sweep
asks: at the 1b preset's decode matmul shapes, which (block_n, block_k)
minimizes time?

Timing: each config runs a jitted ``lax.scan`` chain of 32 applications
(one launch + one terminal fetch), min-of-3. Prints one JSON line per
(shape, config).

Usage: python scripts/int8_decode_sweep.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tutorials_tpu.ops.quant import (
        int8_matmul,
        quantize_int8,
    )

    key = jax.random.PRNGKey(0)
    m = 4
    # the 1b preset's decode matmul shapes (d_model=2048, d_ff=8192,
    # vocab=32000): attention proj, FFN up/down, lm_head
    shapes = [(2048, 2048), (2048, 8192), (8192, 2048), (2048, 32000)]
    configs = [
        (256, 512),     # current default
        (512, 512),
        (512, 1024),
        (1024, 1024),
        (2048, 1024),
        (1024, 2048),
        (512, 2048),
        (256, 2048),
    ]

    chain_len = 32
    for k, n in shapes:
        kx, kw = jax.random.split(jax.random.fold_in(key, k * 7 + n))
        x = jax.random.normal(kx, (m, k), jnp.float32)
        w = quantize_int8(jax.random.normal(kw, (k, n), jnp.float32))
        w = jax.device_put(w)
        x = jax.device_put(x)
        for bn, bk in configs:
            if bk > k or bn > n:
                continue
            # VMEM residency per grid step: bm*bk (x f32) + bk*bn (q int8)
            # + bm*bn + scratch; keep under ~8 MB
            vmem = 8 * bk * 4 + bk * bn + 2 * 8 * bn * 4
            if vmem > 8 * 1024 * 1024:
                continue

            mm = functools.partial(int8_matmul, block_n=bn, block_k=bk)

            @jax.jit
            def chain(x0):
                def body(c, _):
                    y = mm(c, w)
                    # feed a slice back so the chain is sequential (same
                    # M, K) without letting XLA collapse it
                    c2 = c + y[:, :1] * 1e-9
                    return c2, y[0, 0]

                return jax.lax.scan(
                    body, x0, None, length=chain_len
                )

            try:
                _, ys = chain(x)
                float(ys[-1])  # compile + prime fetch
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    _, ys = chain(x)
                    float(ys[-1])
                    best = min(best, time.perf_counter() - t0)
                ms = best * 1e3 / chain_len
                gbs = w.q.nbytes * chain_len / best / 1e9
                print(json.dumps({
                    "shape": [m, k, n], "block_n": bn, "block_k": bk,
                    "ms_per_matmul": round(ms, 3),
                    "weight_gb_per_s": round(gbs, 2),
                }))
            except Exception as e:  # noqa: BLE001 — report and keep sweeping
                print(json.dumps({
                    "shape": [m, k, n], "block_n": bn, "block_k": bk,
                    "error": repr(e)[:120],
                }))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
