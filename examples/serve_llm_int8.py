"""Serve a billion-parameter LM int8-quantized from a streamed checkpoint.

The reference's flagship model-parallel demo loads Llama-7B with
``from_pretrained(..., BitsAndBytesConfig(load_in_8bit=True),
device_map="auto")`` — 33 float shards streamed through bitsandbytes into
int8 matmul weights + float norms (``/root/reference/03.model_parallel.ipynb``
cells 2-4). This example is that loop at reference scale, TPU-native:

1. materialize a synthetic f32 checkpoint of a ~1B-param Llama-style config
   on disk (written once, in layer-sized slabs so the full f32 model is
   never resident anywhere);
2. stream it back leaf-by-leaf through
   :func:`...models.transformer.load_quantized_lm` — each kernel is
   restored, quantized to int8 (+ per-column f32 scales), placed on device,
   and freed before the next leaf is read. Host peak stays one-leaf-bounded
   (reported via max RSS); device holds 1/4 the f32 bytes;
3. serve: batched-prefill + KV-cache generation through the Pallas int8
   MXU kernel, reporting decode tokens/s.

Run on the real chip::

    python examples/serve_llm_int8.py --preset 1b

``--preset toy`` runs the same loop at CPU-test scale (seconds);
``--tp N`` shards the int8 weights over a ``{'model': N}`` mesh
(INT8_TP_RULES / shard_map kernel) when N devices are available.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import resource
import sys
import time

# runnable from a checkout without installation
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def presets():
    from pytorch_distributed_training_tutorials_tpu.models import TransformerConfig

    return {
        # ~1.20B params (16 layers x 67.1M + 2 x 65.5M embed/head):
        # Llama-ish shape scaled to one v5e chip's HBM — f32 checkpoint
        # 4.8 GB on disk, int8+scales+norms ~1.4 GB resident
        "1b": TransformerConfig(
            vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
            d_ff=8192, max_seq_len=512,
        ),
        # the Llama-2/3 serving layout: 4 KV heads shared by 16 query
        # heads — k/v projections and the KV cache shrink 4x (GQA;
        # models/transformer.py n_kv_heads)
        "1b-gqa": TransformerConfig(
            vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=4, d_ff=8192, max_seq_len=512,
        ),
        "toy": TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4,
            max_seq_len=64,
        ),
    }


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def count_params(cfg, abstract=None) -> int:
    """Schema-derived param count (no weights materialized) — the one
    definition shared by the checkpoint writer and the reuse receipt.
    Pass ``abstract`` (an eval_shape params tree) to skip re-tracing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.models import TransformerLM

    if abstract is None:
        abstract = jax.eval_shape(
            TransformerLM(cfg).init, jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32),
        )["params"]
    return sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(abstract)
    )


def write_synthetic_checkpoint(cfg, path: str, seed: int = 0) -> int:
    """Materialize a random-init f32 checkpoint WITHOUT ever holding the
    full model: each top-level param subtree (one block ~67M params at the
    1b preset) is initialized on device, appended to the on-disk tree, and
    freed. Returns the total param count.

    (A real deployment starts from a trained checkpoint; the synthetic one
    exercises the identical IO/quantize path at identical byte counts —
    the reference's demo similarly never trains its Llama.)
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import orbax.checkpoint as ocp

    from pytorch_distributed_training_tutorials_tpu.models import TransformerLM

    model = TransformerLM(cfg)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    total = count_params(cfg, abstract)

    # init one top-level subtree at a time: eval_shape gives the schema,
    # real PRNG init would need the whole model — random normals at the
    # init scale are byte-identical work for the IO/quantize loop
    rng = np.random.Generator(np.random.PCG64(seed))
    if os.path.isdir(path):  # torn previous attempt: regenerate from clean
        import shutil

        shutil.rmtree(path)
    os.makedirs(path)
    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
        for name, sub in abstract.items():
            part = jax.tree_util.tree_map(
                lambda l: (rng.standard_normal(l.shape) * 0.02).astype(
                    np.float32
                ),
                sub,
            )
            # saved as {name: subtree} so restored key paths match the full
            # model's (load_quantized_lm keys quantization off 'parent/
            # kernel' paths — lm_head/kernel must keep its parent)
            ckptr.save(
                os.path.join(path, name),
                args=ocp.args.PyTreeSave({name: part}),
            )
            del part
    # marker = every subtree landed; reuse checks (an interrupted write
    # would otherwise look complete and poison every later run)
    with open(os.path.join(path, "COMPLETE"), "w") as f:
        f.write("ok\n")
    return total


def load_streamed(cfg, path: str, mesh):
    """Stream-quantize every top-level subtree checkpoint back into the
    int8 serving layout (placed per INT8_TP_RULES when ``mesh``).

    ``materialize=False`` per subtree: main() materializes the final
    assembled (and possibly stacked) tree in ONE pass instead of paying
    a jit trace + launch per subtree here."""
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        load_quantized_lm,
    )

    params = {}
    for name in sorted(os.listdir(path)):
        if name == "COMPLETE":
            continue
        params.update(
            load_quantized_lm(
                os.path.join(path, name), mesh=mesh, materialize=False
            )
        )
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--preset", choices=("1b", "1b-gqa", "toy"), default="toy"
    )
    ap.add_argument("--tp", type=int, default=1,
                    help="model-axis width for sharded int8 serving")
    ap.add_argument("--ckpt_dir", default=None)
    ap.add_argument(
        "--hf_checkpoint", default=None, metavar="DIR",
        help="serve a published HF-layout Llama checkpoint (config.json "
        "+ *.safetensors) instead of the synthetic orbax one: streamed "
        "tensor-by-tensor and quantized on load (parallel.hf_llama) — "
        "the from_pretrained(load_in_8bit=True) path, offline",
    )
    ap.add_argument("--prompt_len", type=int, default=32)
    ap.add_argument("--new_tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write a machine-readable receipt (params, bytes, load "
        "time, decode tok/s) to PATH",
    )
    ap.add_argument(
        "--max_seq_len", type=int, default=None,
        help="serve with a different context window than the preset "
        "trained at — weights are window-agnostic (RoPE is computed, the "
        "KV cache is config-sized), so the same checkpoint serves any "
        "window",
    )
    ap.add_argument(
        "--temperature", type=float, default=0.0,
        help="0 = greedy (the receipt default); > 0 samples at this "
        "temperature (optionally filtered by --top_k / --top_p)",
    )
    ap.add_argument("--top_k", type=int, default=0,
                    help="keep only the k highest logits when sampling")
    ap.add_argument("--top_p", type=float, default=1.0,
                    help="nucleus sampling mass when sampling")
    ap.add_argument(
        "--kv_cache_dtype", choices=("f32", "bf16", "int8"), default="f32",
        help="KV-cache storage dtype: bf16 halves per-step cache traffic, "
        "int8 quarters it (per-token absmax scales stored alongside) — "
        "decode at long windows is cache-bound (round 4); reduced "
        "dtypes round stored K/V, so greedy tokens can diverge at "
        "near-ties (int8 more than bf16)",
    )
    ap.add_argument(
        "--flash", action="store_true",
        help="prefill through the Pallas flash-attention kernel "
        "(ops.flash_attention) instead of dense causal attention — "
        "sub-quadratic attention temp memory; the long-prompt path "
        "(round 4). Decode always uses the cached dense path.",
    )
    ap.add_argument(
        "--server", action="store_true",
        help="serve a REQUEST STREAM through the continuous-batching "
        "engine (serve.ServeEngine: slot-indexed KV cache, chained "
        "decode launches) instead of the one-shot batch generate leg — "
        "the receipt gains p50/p95 per-request latency and aggregate "
        "tok/s over mixed prompt lengths",
    )
    ap.add_argument("--requests", type=int, default=12,
                    help="request count for the --server stream")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent cache slots for --server")
    ap.add_argument(
        "--tokens_per_launch", type=int, default=8,
        help="decode chain length per dispatch for --server (the launch "
        "floor is per DISPATCH — longer chains amortize it)",
    )
    ap.add_argument(
        "--prefix-overlap", type=float, default=0.0, dest="prefix_overlap",
        help="for --server: fraction [0..1] of each prompt drawn from one "
        "shared prefix family (the rest is a per-request random tail) — "
        "synthesizes the shared-system-prompt workload the radix prefix "
        "cache (serve.PrefixIndex) targets; the receipt gains hit rate, "
        "splice counts, and TTFT p50/p95",
    )
    ap.add_argument(
        "--prefix-cache-mb", type=int, default=None, dest="prefix_cache_mb",
        help="prefix-cache byte budget in MiB for --server (0 disables; "
        "default: 512 when --prefix-overlap > 0, else 0)",
    )
    ap.add_argument(
        "--spec-k", type=int, default=0, dest="spec_k",
        help="for --server: self-speculative decoding with k n-gram "
        "draft tokens per verify step (serve.ServeEngine speculative_k; "
        "0 disables). Greedy output is token-identical either way; the "
        "win — fewer sequential decode steps per token — shows on "
        "templated/repetitive streams, so pair with --prefix-overlap. "
        "The receipt gains acceptance-rate/verify-forward counters",
    )
    ap.add_argument(
        "--spec-ngram", type=int, default=3, dest="spec_ngram",
        help="suffix length the n-gram draft matches on (--spec-k)",
    )
    ap.add_argument(
        "--adapters", type=int, default=0,
        help="for --server: serve a MULTI-TENANT stream through an N-row "
        "LoRA adapter bank (adapters.AdapterBank; 0 disables). Rows "
        "1..N-1 are registered as synthetic tenants and requests cycle "
        "through all ids (0 = base model) — heterogeneous tenants "
        "co-batch in the one compiled decode program; the receipt gains "
        "bank geometry and per-tenant traffic counters",
    )
    ap.add_argument(
        "--lora-rank", type=int, default=8, dest="lora_rank",
        help="LoRA rank of the adapter bank rows (--adapters)",
    )
    ap.add_argument(
        "--deadline-s", type=float, default=None, dest="deadline_s",
        help="for --server: per-request deadline in seconds "
        "(serve.ServeEngine default_deadline_s; None disables). Expired "
        "requests complete finish_reason='deadline' at the next chain "
        "boundary keeping the tokens they earned — the receipt gains "
        "fault_stats() counters (deadline_expired, cancelled, "
        "nonfinite_quarantined)",
    )
    ap.add_argument(
        "--flight-log", default=None, dest="flight_log",
        help="for --server: write graft-flightlog/v1 snapshots (fault "
        "auto-dumps + one end-of-stream dump) to this JSONL path; render "
        "with scripts/flight_view.py. The recorder itself is always on "
        "for --server (host-only, zero extra device fetches) — this "
        "flag only adds the on-disk dump",
    )
    ap.add_argument(
        "--no-sentry", action="store_true", dest="no_sentry",
        help="for --server: disable the runtime contract sentry "
        "(ISSUE 19). On by default — host-only counters watching the "
        "zero-steady-recompile, fetch-budget, and no-host-numpy "
        "contracts at runtime; a violation auto-dumps a flight "
        "snapshot and the receipt carries sentry_* fields",
    )
    ap.add_argument(
        "--pipeline-depth", type=int, default=1, dest="pipeline_depth",
        help="for --server: decode chains kept in flight before the host "
        "fetches the oldest (serve.ServeEngine pipeline_depth; 1 = "
        "serial, today's loop). Depth 2 dispatches chain i+1 before "
        "fetching chain i, hiding the per-launch roundtrip — on "
        "launch-bound runtimes the whole win, tokens byte-identical",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=0, dest="prefill_chunk",
        help="for --server: prefill long prompts in bounded chunks of "
        "this many tokens interleaved with decode chains (pow2 >= 8; 0 "
        "disables) — caps the decode stall any single long prompt can "
        "inject between chains",
    )
    ap.add_argument(
        "--paged", action="store_true",
        help="for --server: paged KV cache (ISSUE 13) — the slot caches "
        "become one shared page pool + per-slot page tables, admission "
        "counts PAGES not slots, and prefix hits pin shared pages "
        "copy-free. The receipt gains hbm_high_water_bytes (the honest "
        "peak pool claim) and the pages_* counters. Real-chip recipe "
        "(not yet measured): --preset 1b --max_seq_len 4096 "
        "--server --paged",
    )
    ap.add_argument(
        "--page-size", type=int, default=64, dest="page_size",
        help="for --server --paged: tokens per KV page (must divide "
        "max_seq_len)",
    )
    ap.add_argument(
        "--pool-pages", type=int, default=0, dest="pool_pages",
        help="for --server --paged: pages in the pool; 0 (default) "
        "sizes it to slots * window / page_size — the whole-slot HBM "
        "footprint. Set it LOWER to oversubscribe slots against HBM "
        "(requests queue for pages; ones that can never fit shed at "
        "submit)",
    )
    ap.add_argument(
        "--kv-bits", type=int, choices=(8, 4), default=None, dest="kv_bits",
        help="quantized KV storage width (ISSUE 17): 8 = int8 + f32 "
        "scales (same as --kv_cache_dtype int8), 4 = packed-nibble "
        "uint8 + bf16 scales — EXACTLY half int8's bytes per "
        "token-head, so a paged pool fits 2x the pages at fixed HBM. "
        "Replaces --kv_cache_dtype (pass only one). Reduced dtypes "
        "round stored K/V, so greedy tokens can diverge at near-ties "
        "(int4 more than int8)",
    )
    ap.add_argument(
        "--paged-kernel", action="store_true", dest="paged_kernel",
        help="for --server --paged: decode attention through the fused "
        "Pallas page-walk kernel (ops.paged_attention) instead of the "
        "jnp.take gather — pages stream through an online-softmax "
        "accumulator, no dense (slots, window, ...) KV window is ever "
        "materialized. Engine-static (never per request); the gather "
        "path stays the numerics oracle",
    )
    ap.add_argument(
        "--replicas", type=int, default=1,
        help="for --server: serve through a FleetRouter over N replica "
        "engines (N KV-cache footprints in HBM — the same checkpoint "
        "params are shared). 1 (default) keeps the plain single-engine "
        "arm byte-for-byte; >1 adds fleet receipt fields (exactly-once "
        "ledger, health states, merged flight histograms)",
    )
    ap.add_argument(
        "--qps", type=float, default=0.0,
        help="for --server: offered load in requests/s — an "
        "OPEN-loop Poisson arrival process (seeded exponential "
        "inter-arrivals; QueueFull arrivals are shed and counted, the "
        "honest overload behavior). 0 (default) submits the whole "
        "stream up front (the closed-loop burst the single-engine arm "
        "uses)",
    )
    ap.add_argument(
        "--hedge-after", type=float, default=None, dest="hedge_after",
        help="for --server --replicas: duplicate a request stuck on a "
        "SUSPECT replica after this many seconds (first completion "
        "wins, the loser is cancelled and absorbed); default off",
    )
    ap.add_argument(
        "--disaggregate", default=None, metavar="NpMd",
        help="for --server: prefill/decode-disaggregated fleet (ISSUE "
        "18), e.g. 1p2d = one prefill-specialized replica (admission + "
        "bucketed/chunked prefill + the prefix cache) feeding two "
        "decode-specialized replicas (slots, speculation, paged pool) "
        "through device-side KV handoffs routed by the FleetRouter. "
        "Overrides --replicas; the receipt gains "
        "n_prefill/n_decode_replicas + handoffs_moved, and the "
        "interesting fields are ttft_p95 under mixed traffic and "
        "ledger_ok (exactly-once across the transfer)",
    )
    ap.add_argument(
        "--slo", action="store_true",
        help="for --server: two SLO priority classes (ISSUE 20) — every "
        "4th request submits class 0 (interactive), the rest class 1 "
        "(batch). When a class-0 arrival finds all slots busy, the "
        "engine preempts the lowest-class active slot at the chain "
        "boundary (its KV segment swaps to host and later resumes "
        "token-exact); the receipt gains slo_stats() (n_preemptions, "
        "swap counters) and the preempt_wait histogram. Pair with "
        "--qps so arrivals are spaced — an up-front burst is drained "
        "in strict class order and never needs to preempt. "
        "Single-engine arm only",
    )
    ap.add_argument(
        "--unrolled", action="store_true",
        help="serve with L unrolled block copies instead of the default "
        "stacked nn.scan body (the unrolled program is O(L) larger to "
        "compile and to load — see "
        "models.transformer.stack_quantized_lm_params)",
    )
    args = ap.parse_args()

    if args.slo and (args.replicas > 1 or args.disaggregate):
        # preemption swaps are a single-engine contract (the engine
        # forbids role= + priority_classes; a fleet would also need
        # class-aware routing the router spells class_deadline_s /
        # per-class hedge_after_s) — keep the receipt arm honest
        ap.error("--slo is the single-engine arm (ISSUE 20); drop "
                 "--replicas/--disaggregate")

    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.models import TransformerLM
    from pytorch_distributed_training_tutorials_tpu.models.generate import generate
    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh

    cfg = presets()[args.preset]
    if args.hf_checkpoint:
        from pytorch_distributed_training_tutorials_tpu.parallel.hf_llama import (
            config_from_hf,
        )

        cfg = config_from_hf(args.hf_checkpoint)
    if args.max_seq_len is not None:
        # params are window-agnostic: only the cache shapes and the RoPE
        # offsets derive from max_seq_len, so the same checkpoint serves
        # any window (the generate() window trim still applies per request)
        cfg = dataclasses.replace(cfg, max_seq_len=args.max_seq_len)
    if args.flash:
        from pytorch_distributed_training_tutorials_tpu.ops import flash_attention

        cfg = dataclasses.replace(cfg, attention_fn=flash_attention)
    if args.kv_cache_dtype != "f32":
        import jax.numpy as _jnp

        cfg = dataclasses.replace(
            cfg,
            kv_cache_dtype=(
                _jnp.bfloat16 if args.kv_cache_dtype == "bf16" else _jnp.int8
            ),
        )
    if args.kv_bits is not None:
        # --kv-bits is the ISSUE 17 spelling of quantized KV storage
        # (8 = the int8 family above, 4 = packed nibbles + bf16 scales);
        # it sets the SAME cfg field, so passing both is ambiguous
        if args.kv_cache_dtype != "f32":
            ap.error("--kv-bits replaces --kv_cache_dtype; pass only one")
        import jax.numpy as _jnp

        cfg = dataclasses.replace(
            cfg,
            kv_cache_dtype="int4" if args.kv_bits == 4 else _jnp.int8,
        )
    if args.paged_kernel and not args.paged:
        ap.error("--paged-kernel requires --server --paged")
    ckpt = args.ckpt_dir or os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"llm_int8_{args.preset}"
    )

    mesh = None
    if args.tp > 1:
        mesh = create_mesh({"model": args.tp})

    t0 = time.perf_counter()
    # kv_bits/paged_kernel ride every receipt (0/False off)
    receipt = {
        "preset": args.preset, "tp": args.tp,
        "kv_bits": args.kv_bits or 0,
        "paged_kernel": bool(args.paged_kernel),
    }
    if args.hf_checkpoint:
        receipt["hf_checkpoint"] = os.path.abspath(args.hf_checkpoint)
        receipt["preset"] = "hf"
        n_params = count_params(cfg)
        receipt["n_params"] = n_params
        receipt["checkpoint_gb_f32"] = round(4 * n_params / 1e9, 2)
        print(f"checkpoint: HF layout at {args.hf_checkpoint} "
              f"({n_params/1e9:.2f}B params)")
    elif not os.path.isfile(os.path.join(ckpt, "COMPLETE")):
        n_params = write_synthetic_checkpoint(cfg, ckpt)
        receipt["n_params"] = n_params
        receipt["checkpoint_gb_f32"] = round(4 * n_params / 1e9, 2)
        receipt["checkpoint_write_s"] = round(time.perf_counter() - t0, 1)
        print(
            f"checkpoint: wrote {n_params/1e9:.2f}B params "
            f"({4*n_params/1e9:.1f} GB f32) to {ckpt} "
            f"in {time.perf_counter()-t0:.0f}s, peak RSS {rss_gb():.1f} GB"
        )
    else:
        # reuse: still report the checkpoint facts (schema-derived, cheap)
        n_params = count_params(cfg)
        receipt["n_params"] = n_params
        receipt["checkpoint_gb_f32"] = round(4 * n_params / 1e9, 2)
        receipt["checkpoint_reused"] = True
        print(f"checkpoint: reusing {ckpt}")

    scan_layers = not args.unrolled
    rss_before = rss_gb()
    t0 = time.perf_counter()
    if args.hf_checkpoint:
        from pytorch_distributed_training_tutorials_tpu.parallel.hf_llama import (
            load_hf_llama,
        )

        if mesh is not None and not scan_layers:
            raise SystemExit(
                "--hf_checkpoint with --tp requires the scanned layout "
                "(drop --unrolled): tensor-parallel placement of HF "
                "weights runs through place_int8_lm_params on the "
                "stacked tree"
            )
        # materialize=False: main() device-materializes ONCE after
        # placement below, same as the orbax path
        _, params = load_hf_llama(
            args.hf_checkpoint, cfg=cfg, quantize=True,
            scan_layers=scan_layers, materialize=False,
        )
    else:
        params = load_streamed(cfg, ckpt, mesh)
    n_bytes = sum(
        l.size * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params)
    )
    # graftcheck: disable=naive-timing -- loader timing is informational:
    # restored leaves land host-side (numpy) and the device materialization
    # they feed is timed separately by the decode legs, which fetch
    load_s = time.perf_counter() - t0
    f32_gb = 4 * sum(
        l.size for l in jax.tree_util.tree_leaves(params)
        if l.dtype == jnp.int8
    ) / 1e9
    receipt.update(
        load_s=round(load_s, 1),
        resident_gb=round(n_bytes / 1e9, 2),
        f32_equivalent_gb=round(f32_gb, 2),
        peak_rss_gb=round(rss_gb(), 2),
        rss_before_load_gb=round(rss_before, 2),
    )
    print(
        f"load: streamed+quantized in {load_s:.0f}s — resident "
        f"{n_bytes/1e9:.2f} GB (int8+scales+float norms), peak RSS "
        f"{rss_gb():.1f} GB (was {rss_before:.1f} before load; the full "
        f"f32 tree would be {f32_gb:.1f} GB)"
    )

    if scan_layers:
        # one scanned block body instead of n_layers unrolled copies:
        # O(1) program size and compile time in depth (what the unrolled
        # program costs per launch on the chip: not measured).
        from pytorch_distributed_training_tutorials_tpu.models.transformer import (
            stack_quantized_lm_params,
        )

        if not args.hf_checkpoint:  # the HF loader stacked already
            params = stack_quantized_lm_params(params)
        if mesh is not None:
            from pytorch_distributed_training_tutorials_tpu.models.transformer import (
                place_int8_lm_params,
            )

            params = place_int8_lm_params(params, mesh)
    # ONE device-materialize pass over the final tree: loaded leaves are
    # rewritten as device-computed buffers so that no launch can depend on
    # a host-side copy (what a host-put leaf costs per launch on the chip:
    # not measured), and doing it here — after stacking/placement — avoids
    # re-materializing per subtree or materializing buffers stacking
    # replaces
    from pytorch_distributed_training_tutorials_tpu.utils.tree import (
        device_materialize,
    )

    params = device_materialize(params)
    serve_cfg = dataclasses.replace(
        cfg, quantized=True, tp_mesh=mesh, scan_layers=scan_layers
    )
    lm = TransformerLM(serve_cfg)
    receipt["scan_layers"] = scan_layers
    rng = np.random.Generator(np.random.PCG64(7))
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32,
    )

    sample_kw = {}
    if args.temperature > 0:
        import jax as _jax

        sample_kw = dict(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, rng=_jax.random.PRNGKey(7),
        )

    # prime the process's first D2H fetch OUTSIDE any timed region
    int(jnp.zeros((), jnp.int32) + 1)
    if args.server:
        if args.replicas > 1 or args.disaggregate:
            serve_fleet_stream(args, cfg, lm, params, receipt)
        else:
            serve_request_stream(args, cfg, lm, params, receipt)
        if args.json:
            from pytorch_distributed_training_tutorials_tpu.obs import (
                make_receipt,
                write_receipt,
            )

            write_receipt(args.json, make_receipt("serving", receipt))
            print(f"receipt -> {args.json}")
        return
    t0 = time.perf_counter()
    out = generate(lm, params, prompt, args.new_tokens, **sample_kw)
    int(out[0, -1])  # close the region with a real fetch
    compile_s = time.perf_counter() - t0
    # min-of-2 via obs.timing.MinOfN: a launch on a shared host can stall.
    # All samples are reported so the receipt shows its own spread;
    # MinOfN additionally flags samples > 5x median as stalls.
    from pytorch_distributed_training_tutorials_tpu.obs import MinOfN

    holder = {"out": out}

    def run_gen():
        holder["out"] = generate(
            lm, params, prompt, args.new_tokens, **sample_kw
        )
        # close the timed region with a one-element D2H
        int(holder["out"][0, -1])

    timing = MinOfN(n=2, warmup=False).measure(run_gen)
    out = holder["out"]
    gen_samples = timing.samples_s
    gen_s = timing.best_s
    toks = args.batch * args.new_tokens
    receipt.update(
        batch=args.batch,
        prompt_len=args.prompt_len,
        new_tokens=args.new_tokens,
        max_seq_len=cfg.max_seq_len,
        flash_prefill=bool(args.flash),
        kv_cache_dtype=args.kv_cache_dtype,
        # a sampled run's decode_tok_per_s is not comparable to the greedy
        # headline — make every receipt self-describing
        temperature=args.temperature,
        **(
            dict(top_k=args.top_k, top_p=args.top_p)
            if args.temperature > 0
            else {}
        ),
        decode_tok_per_s=round(toks / gen_s, 1),
        decode_s_samples=[round(s, 2) for s in gen_samples],
        decode_stalled_samples=timing.n_stalled,
        first_call_incl_compile_s=round(compile_s, 1),
        backend=jax.default_backend(),
    )
    print(
        f"serve: {args.batch}x({args.prompt_len} prompt + "
        f"{args.new_tokens} new) in {gen_s:.2f}s "
        f"({toks/gen_s:.1f} tok/s; first call incl. compile {compile_s:.0f}s)"
    )
    print("sample:", np.asarray(out[0, args.prompt_len:args.prompt_len+12]))
    if args.json:
        from pytorch_distributed_training_tutorials_tpu.obs import (
            make_receipt,
            write_receipt,
        )

        # schema'd envelope: git sha / jax version / device stamp ride
        # with every SERVING_rXX.json so receipts stay self-describing
        write_receipt(args.json, make_receipt("serving", receipt))
        print(f"receipt -> {args.json}")


def _reset_serving_counters(engine) -> None:
    """Zero the engine's traffic counters after the compile warmup so
    the timed stream's receipt measures serving, not tracing."""
    engine.n_chains = engine.n_prefills = engine.generated_tokens = 0
    engine.n_splices = engine.prefix_hit_tokens = 0
    engine.n_verify_forwards = engine.spec_steps_consumed = 0
    engine.spec_drafts_accepted = 0
    engine.adapter_requests = 0
    engine.n_deadline_expired = engine.n_cancelled = 0
    engine.nonfinite_quarantined = engine.n_prefill_errors = 0
    engine.n_chunks = 0
    engine.n_handoffs_out = engine.n_handoffs_in = 0
    if hasattr(engine, "n_swaps_out"):
        # SLO engines only (priority-off engines don't grow the attrs)
        engine.n_swaps_out = engine.n_swaps_in = 0
    if engine.prefix is not None:
        engine.prefix.hits = engine.prefix.misses = 0


def _serving_strategy(lm):
    """TensorParallel strategy for the ``--server`` engines when the
    model carries a TP mesh (ISSUE 15): the slot/KV state shards
    head-wise with the int8 Megatron split the params already use, so
    each chip holds 1/tp of the cache and the decode chain's only
    collectives are the forward's existing all-reduces. None (the
    replicated engine, byte-identical off-path) without a model axis."""
    mesh = getattr(lm.cfg, "tp_mesh", None)
    if mesh is None or mesh.shape.get("model", 1) <= 1:
        return None
    from pytorch_distributed_training_tutorials_tpu.models.transformer import (
        INT8_TP_RULES,
    )
    from pytorch_distributed_training_tutorials_tpu.parallel import (
        TensorParallel,
    )

    return TensorParallel(mesh, INT8_TP_RULES)


def _parse_disaggregate(spec: str) -> tuple[int, int]:
    """``"1p2d"`` -> ``(1, 2)``: the role geometry of a disaggregated
    fleet (ISSUE 18). Both counts must be >= 1 — a fleet missing either
    role can never complete a request."""
    import re

    m = re.fullmatch(r"(\d+)p(\d+)d", spec)
    if not m or int(m.group(1)) < 1 or int(m.group(2)) < 1:
        raise SystemExit(
            f"--disaggregate wants NpMd with N,M >= 1 (e.g. 1p2d), "
            f"got {spec!r}"
        )
    return int(m.group(1)), int(m.group(2))


def _paged_kwargs(args, window: int) -> dict:
    """ServeEngine paged-geometry kwargs from the CLI flags. --pool-pages
    0 sizes the pool to the whole-slot footprint (slots * window worth of
    pages) — same HBM, page-granular accounting; a smaller explicit pool
    oversubscribes slots against HBM."""
    if not args.paged:
        return {}
    pool = args.pool_pages or args.slots * window // args.page_size
    return dict(
        paged=True, page_size=args.page_size, pool_pages=pool,
        paged_kernel=bool(args.paged_kernel),
    )


def serve_fleet_stream(args, cfg, lm, params, receipt: dict) -> None:
    """The ``--server --replicas N`` leg (ISSUE 12): the same request
    stream through a :class:`...serve.FleetRouter` over N replica
    engines sharing one checkpoint's params (N KV-cache footprints in
    HBM — tenants-per-chip economics, but for whole replicas).

    ``--disaggregate NpMd`` (ISSUE 18) builds a ROLE-split fleet
    instead: N prefill-specialized replicas (prefix cache + chunked
    prefill, no decode machinery) and M decode-specialized replicas
    (spec/paged/pipelining, no prefix cache) joined by the router's
    device-side KV handoff — ``--replicas`` is ignored in that mode and
    the interesting receipt fields become ``ttft_p95`` under mixed
    traffic, ``handoffs_moved`` (== completed requests), and
    ``ledger_ok``.

    ``--qps`` makes the stream OPEN loop: Poisson arrivals from a
    seeded exponential inter-arrival process, submitted at their
    arrival instants regardless of completion progress; a ``QueueFull``
    arrival (every replica saturated) is SHED and counted — the honest
    overload behavior, vs a closed loop that politely self-throttles.
    ``--qps 0`` submits everything up front (the single-engine arm's
    burst).

    Every replica carries its own flight recorder on ONE shared t0, so
    the receipt's percentiles come from the bucket-wise MERGED
    histograms (``FleetRouter.stats``) — summing per-replica p95s would
    be meaningless — and ``--flight-log`` writes the merged
    ``graft-flightlog/v1`` snapshot (``dump_fleet``), which
    scripts/flight_view.py renders with ``replica=i`` tags and
    ``[dead]``/``[draining]`` health annotations."""
    import jax
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.obs import FlightRecorder
    from pytorch_distributed_training_tutorials_tpu.serve import (
        FleetRouter,
        QueueFull,
        Request,
        ServeEngine,
    )

    window = int(cfg.max_seq_len)
    new = args.new_tokens
    lengths = sorted(
        {
            max(1, args.prompt_len // 2),
            min(args.prompt_len, window - new),
            min(args.prompt_len + args.prompt_len // 2, window - new),
        }
    )
    cache_mb = args.prefix_cache_mb
    if cache_mb is None:
        cache_mb = 512 if args.prefix_overlap > 0 else 0

    def mk_bank():
        # per-replica banks with IDENTICAL tenants (deterministic
        # seeds), so a re-dispatched tenant request decodes under the
        # same factors wherever it lands
        if not args.adapters:
            return None
        from pytorch_distributed_training_tutorials_tpu.adapters import AdapterBank

        bank = AdapterBank(lm, n_adapters=args.adapters,
                           rank=args.lora_rank)
        frng = np.random.Generator(np.random.PCG64(13))
        for aid in range(1, args.adapters):
            bank.register(
                f"tenant-{aid}",
                jax.tree_util.tree_map(
                    lambda leaf: (
                        frng.standard_normal(leaf.shape) * 0.02
                    ).astype(np.float32),
                    bank.row_zeros(),
                ),
            )
        return bank

    t0 = time.perf_counter()
    n_pre, n_dec = (
        _parse_disaggregate(args.disaggregate)
        if args.disaggregate else (0, 0)
    )

    # contract sentry (ISSUE 19): ONE sentry shared by every replica —
    # compile/fetch hooks are process-global, and FleetRouter.stats()
    # dedupes the shared instance by identity instead of summing it N
    # times. It stamps into the ROUTER's recorder so violations land in
    # the merged fleet dump. --no-sentry reverts to the bare fleet.
    router_flight = FlightRecorder(capacity=4096, t0=t0)
    sentry = None
    if not args.no_sentry:
        from pytorch_distributed_training_tutorials_tpu.obs import ContractSentry

        sentry = ContractSentry(flight=router_flight).install()

    def mk_engine(role: str | None = None) -> ServeEngine:
        kw = dict(
            n_slots=args.slots,
            tokens_per_launch=args.tokens_per_launch,
            max_queue=max(64, args.requests),
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            prefix_cache_bytes=cache_mb * 1024 * 1024,
            speculative_k=args.spec_k,
            spec_ngram=args.spec_ngram,
            adapter_bank=mk_bank(),
            default_deadline_s=args.deadline_s,
            pipeline_depth=args.pipeline_depth,
            prefill_chunk=args.prefill_chunk,
            flight=FlightRecorder(capacity=4096, t0=t0),
            sentry=sentry,
            strategy=_serving_strategy(lm),
            **_paged_kwargs(args, window),
        )
        if role == "prefill":
            # the prefill specialist keeps the prefix cache + chunked
            # prefill (its whole job) and sheds decode-side machinery —
            # spec/pipelining/paged pools never run on this replica
            kw.update(role="prefill", speculative_k=0, pipeline_depth=1)
            for k in ("paged", "page_size", "pool_pages", "paged_kernel"):
                kw.pop(k, None)
        elif role == "decode":
            # the decode specialist keeps spec/paged/pipelining and
            # sheds the prefix cache + chunking (prefill-side work it
            # never performs)
            kw.update(role="decode", prefix_cache_bytes=0,
                      prefill_chunk=0)
        return ServeEngine(lm, params, **kw)

    if args.disaggregate:
        engines = ([mk_engine("prefill") for _ in range(n_pre)]
                   + [mk_engine("decode") for _ in range(n_dec)])
    else:
        engines = [mk_engine() for _ in range(args.replicas)]
    if args.tp > 1:
        # homogeneous fleet: one replica's compiled chain speaks for all
        # (FleetRouter.stats passes the tp_* config keys through); in a
        # disaggregated fleet the decode role owns the chain, so audit
        # the first decode replica
        engines[n_pre if args.disaggregate else 0].audit_decode_hlo()
    router = FleetRouter(
        engines,
        hedge_after_s=args.hedge_after,
        flight=router_flight,
    )
    rng = np.random.Generator(np.random.PCG64(11))
    shared = rng.integers(0, cfg.vocab_size, (max(lengths),)).tolist()

    def mk_request(i: int, deadline_s: float | None = None) -> Request:
        p_len = lengths[i % len(lengths)]
        k = min(p_len, int(round(args.prefix_overlap * p_len)))
        tail = rng.integers(0, cfg.vocab_size, (p_len - k,)).tolist()
        return Request(
            prompt=shared[:k] + tail, max_new_tokens=new, seed=i,
            deadline_s=deadline_s,
            adapter=(i % args.adapters) if args.adapters else 0,
        )

    # compile warmup: the replicas share one set of jitted programs ONLY
    # per engine object, so every replica prefills each prompt bucket
    # once before the timed stream (same compile/serve split as the
    # single-engine arm, N times over)
    t_compile = time.perf_counter()
    warm_dl = 1e9 if args.deadline_s is not None else None
    if args.disaggregate:
        # role warmup drives the handoff path directly (prefill ->
        # take_handoff -> decode accept), so each prefill replica
        # compiles every prompt bucket and each decode replica compiles
        # its accept splice + chain before the timed stream
        import dataclasses

        pre, dec = engines[:n_pre], engines[n_pre:]
        for j in range(max(n_pre, n_dec)):
            pe, de = pre[j % n_pre], dec[j % n_dec]
            for i in range(len(lengths)):
                req = mk_request(i, deadline_s=warm_dl)
                rid = pe.submit(dataclasses.replace(req))
                pe.run_until_idle()
                de.accept(req, pe.take_handoff(rid))
            de.run_until_idle()
    else:
        for eng in engines:
            for i in range(len(lengths)):
                eng.submit(mk_request(i, deadline_s=warm_dl))
            eng.run_until_idle()
    compile_s = time.perf_counter() - t_compile
    for eng in engines:
        _reset_serving_counters(eng)
        eng._flight.reset()
    router.n_handoffs_moved = 0
    router._flight.reset()
    if sentry is not None:
        # same seam as the recorder resets: warmup compiles were legal,
        # anything past here is a steady-state violation
        sentry.mark_steady()

    # open-loop Poisson arrivals (qps > 0) or the up-front burst (0)
    arng = np.random.Generator(np.random.PCG64(17))
    t_arr = 0.0
    arrivals = []
    for _ in range(args.requests):
        if args.qps > 0:
            t_arr += float(arng.exponential(1.0 / args.qps))
        arrivals.append(t_arr)

    shed = 0
    next_i = 0
    t_start = time.perf_counter()
    while next_i < len(arrivals):
        due = t_start + arrivals[next_i]
        if time.perf_counter() >= due:
            try:
                router.submit(mk_request(len(lengths) + next_i))
            except QueueFull:
                shed += 1  # overload: shed at the door, keep serving
            next_i += 1
            continue
        if router.idle:
            time.sleep(min(0.001, max(0.0, due - time.perf_counter())))
        else:
            router.step()
    router.run_until_idle()
    for eng in engines:
        # close the timed region with a real fetch per replica
        jax.device_get(eng._state["remaining"])
    wall_s = time.perf_counter() - t_start

    rstats = router.stats()
    if sentry is not None:
        sentry.uninstall()
    toks = sum(e.generated_tokens for e in engines)
    receipt.update(
        server=True,
        server_requests=args.requests,
        server_slots=args.slots,
        tokens_per_launch=args.tokens_per_launch,
        server_prompt_lengths=lengths,
        new_tokens=new,
        max_seq_len=window,
        temperature=args.temperature,
        qps=args.qps,
        server_shed=shed,
        server_wall_s=round(wall_s, 2),
        server_tok_per_s=round(toks / wall_s, 1),
        server_generated_tokens=toks,
        server_chains=sum(e.n_chains for e in engines),
        server_prefills=sum(e.n_prefills for e in engines),
        server_handoffs=sum(
            getattr(e, "n_handoffs_in", 0) for e in engines
        ),
        server_p50_latency_s=round(rstats.get("e2e_p50_s", 0.0), 3),
        server_p95_latency_s=round(rstats.get("e2e_p95_s", 0.0), 3),
        server_ttft_p50_s=round(rstats.get("ttft_p50_s", 0.0), 3),
        server_ttft_p95_s=round(rstats.get("ttft_p95_s", 0.0), 3),
        server_compile_s=round(compile_s, 1),
        prefix_overlap=args.prefix_overlap,
        prefix_cache_mb=cache_mb,
        **rstats,
        backend=jax.default_backend(),
    )
    ledger_problems = router.ledger.verify()
    receipt["ledger_ok"] = not ledger_problems
    if ledger_problems:
        receipt["ledger_problems"] = ledger_problems
    if args.flight_log:
        router.dump_fleet(args.flight_log, reason="end_of_stream")
        print(f"fleet flight log -> {args.flight_log}")
    geometry = (
        f"{n_pre}p+{n_dec}d role replicas" if args.disaggregate
        else f"{args.replicas} replicas"
    )
    print(
        f"fleet: {args.requests} requests over {geometry} "
        f"x {args.slots} slots in {wall_s:.2f}s — {toks / wall_s:.1f} "
        f"tok/s aggregate, qps {args.qps or 'burst'} ({shed} shed), "
        f"p95 {receipt['server_p95_latency_s']}s, ttft p95 "
        f"{receipt['server_ttft_p95_s']}s, states "
        f"{router.replica_states()}, {rstats['redispatched']} "
        f"re-dispatched, {rstats['hedged']} hedged "
        f"(compile {compile_s:.0f}s)"
    )


def serve_request_stream(args, cfg, lm, params, receipt: dict) -> None:
    """The ``--server`` leg: a staggered stream of mixed-prompt-length
    requests through :class:`...serve.ServeEngine` — the continuous-
    batching arm of the serving receipt.

    Reports p50/p95 per-request latency (submit to completion; every
    completion's tokens come off a fetched chain block, so latencies are
    fetch-backed, not async mirages) and aggregate generated tok/s.
    Compile happens on a warmup request per prompt bucket BEFORE the
    timed stream, mirroring the one-shot leg's compile/serve split.

    ``--prefix-overlap r`` draws the first ``round(r * p_len)`` tokens of
    every prompt from ONE shared token family (the shared-system-prompt
    workload), so the radix prefix cache (serve.PrefixIndex) can retain
    and splice it; the warmup stream uses the same family, so the timed
    stream measures the STEADY state (cache warm, splice path compiled)
    and the receipt gains hit rate, splice counts, and TTFT p50/p95
    (submit to first token, the latency prefix reuse actually moves)."""
    import jax
    import numpy as np

    from pytorch_distributed_training_tutorials_tpu.obs import FlightRecorder
    from pytorch_distributed_training_tutorials_tpu.serve import Request, ServeEngine

    # flight recorder (ISSUE 10): always on for the server arm — host
    # bookkeeping only, zero extra device fetches — so every serving
    # receipt carries streaming-histogram percentiles and the lifecycle
    # counters. --flight-log additionally dumps graft-flightlog/v1
    # snapshots (fault auto-dumps + one end-of-stream dump) to disk.
    flight = FlightRecorder(capacity=4096, dump_path=args.flight_log)

    # contract sentry (ISSUE 19): on by default for every --server arm —
    # host-only counters, zero extra device fetches — so the receipt
    # carries sentry_steady_recompiles / sentry_fetch_budget_ok /
    # sentry_reupload_bytes and a contract break on the real chip
    # auto-dumps a flight snapshot instead of silently eating the round.
    # --no-sentry reverts to the bare engine.
    sentry = None
    if not args.no_sentry:
        from pytorch_distributed_training_tutorials_tpu.obs import ContractSentry

        sentry = ContractSentry(flight=flight).install()

    bank = None
    if args.adapters:
        # multi-tenant arm: N-1 synthetic tenants (small random factors)
        # in one bank; requests cycle through ids 0..N-1 so the stream
        # mixes the base model with every tenant in the same slots
        from pytorch_distributed_training_tutorials_tpu.adapters import AdapterBank

        bank = AdapterBank(
            lm, n_adapters=args.adapters, rank=args.lora_rank
        )
        frng = np.random.Generator(np.random.PCG64(13))
        for aid in range(1, args.adapters):
            bank.register(
                f"tenant-{aid}",
                jax.tree_util.tree_map(
                    lambda leaf: (
                        frng.standard_normal(leaf.shape) * 0.02
                    ).astype(np.float32),
                    bank.row_zeros(),
                ),
            )
            flight.record(
                "adapter_register", adapter=aid, tenant=f"tenant-{aid}"
            )

    window = int(cfg.max_seq_len)
    new = args.new_tokens
    lengths = sorted(
        {
            max(1, args.prompt_len // 2),
            min(args.prompt_len, window - new),
            min(args.prompt_len + args.prompt_len // 2, window - new),
        }
    )
    cache_mb = args.prefix_cache_mb
    if cache_mb is None:
        cache_mb = 512 if args.prefix_overlap > 0 else 0
    engine = ServeEngine(
        lm, params,
        n_slots=args.slots,
        tokens_per_launch=args.tokens_per_launch,
        max_queue=max(64, args.requests),
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        prefix_cache_bytes=cache_mb * 1024 * 1024,
        speculative_k=args.spec_k,
        spec_ngram=args.spec_ngram,
        adapter_bank=bank,
        default_deadline_s=args.deadline_s,
        flight=flight,
        sentry=sentry,
        pipeline_depth=args.pipeline_depth,
        prefill_chunk=args.prefill_chunk,
        priority_classes=2 if args.slo else 0,
        strategy=_serving_strategy(lm),
        **_paged_kwargs(args, window),
    )
    if args.tp > 1:
        # one extra AOT chain compile, once per receipt run: the
        # zero-unexpected-collectives verdict (tp_hlo_ok) rides into
        # the receipt via engine.stats()'s tp part
        audit = engine.audit_decode_hlo()
        print(
            f"tp={args.tp} decode HLO audit: ok={audit['ok']} "
            f"collectives={audit['collectives']}"
        )
    rng = np.random.Generator(np.random.PCG64(11))
    # one shared token family: request i's prompt = shared[:k] + tail,
    # k = round(overlap * p_len) — every prompt of the stream shares its
    # head with every other, the trie's best case at overlap 1.0 and a
    # plain random stream at 0.0
    shared = rng.integers(0, cfg.vocab_size, (max(lengths),)).tolist()

    def mk_request(i: int, deadline_s: float | None = None) -> Request:
        p_len = lengths[i % len(lengths)]
        k = min(p_len, int(round(args.prefix_overlap * p_len)))
        tail = rng.integers(0, cfg.vocab_size, (p_len - k,)).tolist()
        return Request(
            prompt=shared[:k] + tail, max_new_tokens=new, seed=i,
            deadline_s=deadline_s,
            # cycle every bank row (0 = base) through the shared slots
            adapter=(i % args.adapters) if bank is not None else 0,
            # SLO arm (ISSUE 20): every 4th request is interactive
            # (class 0), the rest batch (class 1) — the mix that makes
            # a class-0 arrival find the slots full of class-1 work
            priority=(0 if i % 4 == 0 else 1) if args.slo else 0,
        )

    # compile warmup: one request per prompt bucket + the decode chain,
    # outside the timed stream (compile is the multi-second cost; the
    # stream receipt should measure serving, not tracing). With overlap
    # the warmup also compiles the suffix splice buckets and leaves the
    # shared family resident, so the timed stream is steady-state.
    t0 = time.perf_counter()
    for i in range(len(lengths)):
        # warmup is COMPILE time (minutes at 1B) — exempt it from any
        # --deadline-s so the timed stream starts with live programs
        engine.submit(mk_request(
            i, deadline_s=1e9 if args.deadline_s is not None else None,
        ))
    engine.run_until_idle()
    compile_s = time.perf_counter() - t0
    _reset_serving_counters(engine)
    # the warmup's compile-dominated spans would poison the percentile
    # histograms — reset the recorder with the counters above
    flight.reset()
    if sentry is not None:
        # same seam: warmup compiles were legal and attributed; from
        # here any compilation is a steady-state violation (auto-dumped)
        sentry.mark_steady()

    t0 = time.perf_counter()
    if args.qps > 0:
        # open-loop Poisson arrivals (same seeded process as the fleet
        # arm): requests land at their arrival instants regardless of
        # progress. The SLO arm needs this spacing — an up-front burst
        # is drained in strict class order by the PriorityScheduler and
        # never needs to preempt an occupied slot
        arng = np.random.Generator(np.random.PCG64(17))
        arrivals, t_arr = [], 0.0
        for _ in range(args.requests):
            t_arr += float(arng.exponential(1.0 / args.qps))
            arrivals.append(t_arr)
        next_i = 0
        while next_i < len(arrivals):
            due = t0 + arrivals[next_i]
            if time.perf_counter() >= due:
                engine.submit(mk_request(len(lengths) + next_i))
                next_i += 1
                continue
            if engine.idle:
                time.sleep(min(0.001, max(0.0, due - time.perf_counter())))
            else:
                engine.step()
    else:
        for i in range(args.requests):
            engine.submit(mk_request(len(lengths) + i))
    engine.run_until_idle()
    # the drain's last chain ended in a real fetch (engine.step's
    # device_get), but close the region explicitly so wall-clock honesty
    # doesn't hinge on engine internals
    jax.device_get(engine._state["remaining"])
    wall_s = time.perf_counter() - t0

    # percentiles come from the recorder's streaming histograms (bounded
    # memory, mergeable across processes) rather than sorting the
    # completion list — same samples (the engine records each
    # Completion's own latency/ttft), bounded-error buckets
    lat_h, ttft_h = flight.hist["e2e"], flight.hist["ttft"]
    toks = engine.generated_tokens
    receipt.update(
        server=True,
        server_requests=args.requests,
        server_slots=args.slots,
        tokens_per_launch=args.tokens_per_launch,
        server_prompt_lengths=lengths,
        new_tokens=new,
        max_seq_len=window,
        temperature=args.temperature,
        qps=args.qps,
        server_wall_s=round(wall_s, 2),
        server_tok_per_s=round(toks / wall_s, 1),
        server_generated_tokens=toks,
        server_chains=engine.n_chains,
        server_prefills=engine.n_prefills,
        server_p50_latency_s=round(lat_h.quantile(0.50), 3),
        server_p95_latency_s=round(lat_h.quantile(0.95), 3),
        server_ttft_p50_s=round(ttft_h.quantile(0.50), 3),
        server_ttft_p95_s=round(ttft_h.quantile(0.95), 3),
        server_compile_s=round(compile_s, 1),
        prefix_overlap=args.prefix_overlap,
        prefix_cache_mb=cache_mb,
        **engine.stats(),
        backend=jax.default_backend(),
    )
    if args.flight_log:
        # end-of-stream snapshot (fault auto-dumps already appended)
        flight.dump(reason="end_of_stream")
        print(f"flight log -> {args.flight_log}")
    prefix_note = ""
    if engine.prefix is not None:
        st = engine.prefix_stats()
        prefix_note = (
            f", prefix hit rate {st['prefix_hit_rate']:.2f} "
            f"({engine.n_splices} splices, {engine.prefix_hit_tokens} "
            f"tokens reused)"
        )
    if args.spec_k:
        ss = engine.spec_stats()
        prefix_note += (
            f", spec-k {args.spec_k}: mean accepted "
            f"{ss['spec_mean_accepted_len']:.2f}, "
            f"{ss['n_verify_forwards']} verify forwards for {toks} tokens"
        )
    if bank is not None:
        ast = engine.adapter_stats()
        prefix_note += (
            f", adapters: {ast['adapters_registered']}/"
            f"{ast['n_adapters'] - 1} tenants (rank {ast['lora_rank']}), "
            f"{ast['adapter_requests']} tenant requests"
        )
    if args.deadline_s is not None:
        fst = engine.fault_stats()
        prefix_note += (
            f", deadline {args.deadline_s}s: "
            f"{fst['deadline_expired']} expired"
        )
    if args.pipeline_depth > 1 or args.prefill_chunk:
        ps = engine.pipeline_stats()
        prefix_note += (
            f", pipeline depth {ps['pipeline_depth']} "
            f"(chunk {ps['prefill_chunk']}, {ps['n_chunks']} chunks)"
        )
    if args.slo:
        st = engine.slo_stats()
        prefix_note += (
            f", slo: {st['priority_classes']} classes, "
            f"{st['n_preemptions']} preemptions "
            f"({st['n_swaps_out']} out / {st['n_swaps_in']} in)"
        )
    if sentry is not None:
        sentry.uninstall()
        prefix_note += (
            f", sentry: {sentry.n_steady_recompiles} steady recompiles, "
            f"budget {'OK' if not sentry.n_budget_violations else 'OVER'}"
            f", {sentry.reupload_bytes} B re-uploaded"
        )
    print(
        f"server: {args.requests} requests (prompts {lengths}, {new} new "
        f"each) over {args.slots} slots in {wall_s:.2f}s — "
        f"{toks / wall_s:.1f} tok/s, p50 {receipt['server_p50_latency_s']}s "
        f"/ p95 {receipt['server_p95_latency_s']}s per request, ttft p50 "
        f"{receipt['server_ttft_p50_s']}s, "
        f"{engine.n_chains} chains + {engine.n_prefills} prefills"
        f"{prefix_note} (compile {compile_s:.0f}s)"
    )


if __name__ == "__main__":
    main()
