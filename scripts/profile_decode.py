"""Device-trace the 1.2B int8 serving decode and print the per-op table.

This script answers "where does the decode step actually spend device
time" the same way scripts/profile_step.py does for the train step: capture a
jax.profiler trace of one compiled generate() call and classify
on-device op durations with ``obs.StepReport`` (the same
fusion-body-aware classifier every other trace consumer uses — no local
name heuristics). Round-4 finding: the 1.2B decode
executes ~3.6 ms/step on device; the original 2.7 tok/s receipt was
numpy-leaf re-upload (fixed by utils.tree.device_materialize), not
device time — this trace was the evidence (device busy 0.08 s inside a
16 s wall, one 16.18 s idle gap before the main program's first op).

Requires the cached 1b checkpoint (run examples/serve_llm_int8.py
--preset 1b once). Usage:

    python scripts/profile_decode.py [new_tokens=8]
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.models.generate import generate
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        load_quantized_lm,
    )
    from pytorch_distributed_training_tutorials_tpu.obs import (
        StepReport,
        make_receipt,
    )
    from pytorch_distributed_training_tutorials_tpu.utils import profiling

    new_tokens = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    cfg = TransformerConfig(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
        d_ff=8192, max_seq_len=512,
    )
    ckpt = os.path.join(os.environ.get("TMPDIR", "/tmp"), "llm_int8_1b")
    if not os.path.isfile(os.path.join(ckpt, "COMPLETE")):
        sys.exit(f"no cached checkpoint at {ckpt}; run the serve example first")

    print("loading...", file=sys.stderr)
    # the checkpoint is one orbax dir per top-level subtree
    # (examples/serve_llm_int8.py write_synthetic_checkpoint layout)
    params = {}
    for name in sorted(os.listdir(ckpt)):
        if name != "COMPLETE":
            params.update(load_quantized_lm(os.path.join(ckpt, name)))
    lm = TransformerLM(dataclasses.replace(cfg, quantized=True))
    rng = np.random.Generator(np.random.PCG64(7))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)

    int(jnp.zeros((), jnp.int32) + 1)  # prime first fetch
    print("compiling...", file=sys.stderr)
    out = generate(lm, params, prompt, new_tokens)
    int(out[0, -1])

    logdir = "/tmp/decode-trace"
    with profiling.trace(logdir):
        out = generate(lm, params, prompt, new_tokens)
        int(out[0, -1])

    # wrapper exclusion + fusion classification live in obs.trace now:
    # wrappers (jit_*, while, ThunkExecutor::*) are split out so they
    # can't double-count their children, and a pallas int8 kernel shows
    # up as matmul, not "other"
    steps = max(new_tokens - 1, 1)
    report = StepReport.from_trace(logdir, steps=steps)
    pallas_us = sum(
        us for op, us, _ in report.ops
        if "int8" in op or "pallas" in op or "matmul_kernel" in op
    )
    receipt = make_receipt("profile_decode", {
        "new_tokens": new_tokens,
        "device_ms_total_incl_wrappers":
            round((report.total_us + report.wrapper_us) / 1e3, 1),
        "device_ms_ops": round(report.total_us / 1e3, 1),
        "by_class_ms": {
            k: round(v / 1e3, 1) for k, v in sorted(
                report.by_category.items(), key=lambda kv: -kv[1])},
        "pallas_int8_kernel_ms": round(pallas_us / 1e3, 1),
        "per_decode_step_ms_ops": round(report.step_us / 1e3, 1),
        "unclassified_fraction": round(report.unclassified_fraction, 3),
    })
    print(json.dumps(receipt))
    print("\ntop 40 ops (ms):")
    for op, us, cls in sorted(report.ops, key=lambda r: -r[1])[:40]:
        print(f"  {us/1e3:10.2f}  [{cls}] {op[:100]}")


if __name__ == "__main__":
    main()
