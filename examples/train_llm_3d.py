"""LLM training with composed parallelism: dp x pp (or dp x tp x sp).

Development run on a virtual mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_llm_3d.py --mode pp --max_epochs 2

Modes:
- ``pp``: {'data': N/2, 'stage': 2} — the one-program shard_map GPipe
  pipeline (layer stack sharded over stage, ppermute hops, microbatched).
- ``tp_sp``: {'data': 2, 'seq': 2, 'model': N/4} — Megatron tensor split +
  ring-attention sequence parallelism, tokens sharded (B over data, S over
  seq).
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable from a checkout without installation
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--mode", choices=["pp", "tp_sp"], default="pp")
    parser.add_argument("--max_epochs", type=int, default=3)
    parser.add_argument("--batch_size", type=int, default=8,
                        help="per data-parallel device")
    args = parser.parse_args()

    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_training_tutorials_tpu import create_mesh
    from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader, synthetic_lm
    from pytorch_distributed_training_tutorials_tpu.models import (
        TP_RULES, TransformerConfig, TransformerLM,
    )
    from pytorch_distributed_training_tutorials_tpu.parallel import (
        PipelinedTransformerLM, PipelineParallel, TensorParallel,
        make_ring_attention,
    )
    from pytorch_distributed_training_tutorials_tpu.train import Trainer

    n = len(jax.devices())
    ds = synthetic_lm(size=512, seq_len=32, vocab_size=64)

    if args.mode == "pp":
        mesh = create_mesh({"data": max(n // 2, 1), "stage": 2})
        cfg = TransformerConfig(
            vocab_size=64, d_model=64, n_layers=4, n_heads=4,
            max_seq_len=64, scan_layers=True,
        )
        model = PipelinedTransformerLM(cfg, mesh, num_microbatches=2)
        strategy = PipelineParallel(mesh, num_microbatches=2)
        loader = ShardedLoader(ds, args.batch_size, mesh)
    else:
        mesh = create_mesh({"data": 2, "seq": 2, "model": -1})
        cfg = TransformerConfig(
            vocab_size=64, d_model=64, n_layers=4, n_heads=4,
            max_seq_len=64, attention_fn=make_ring_attention(mesh),
        )
        model = TransformerLM(cfg)
        strategy = TensorParallel(mesh, TP_RULES, seq_axis="seq")
        loader = ShardedLoader(
            ds, args.batch_size, mesh, batch_spec=P("data", "seq")
        )

    trainer = Trainer(
        model, loader, optax.adam(3e-3), strategy=strategy,
        loss="cross_entropy",
    )
    trainer.train(args.max_epochs)


if __name__ == "__main__":
    main()
