"""DDP allreduce scaling-efficiency sweep: 1 -> N chips.

The BASELINE north-star beyond raw throughput is *scaling*: >=90% ICI
allreduce efficiency from 1 to 32 chips on the ResNet-18 data-parallel
workload (``/root/repo/BASELINE.json:5``; the reference's own comparison is
the 2-GPU-vs-1 wall-clock chart at
``/root/reference/03.model_parallel.ipynb:1014-1037``). This module is the
sweep harness: weak scaling (fixed per-device batch), one mesh width at a
time, slope-timed so async dispatch and host-roundtrip latency cannot lie.

Efficiency definition (weak scaling): with per-device batch ``b`` held
constant, a D-chip run's ``images/s/chip`` divided by the 1-chip run's.
Perfect overlap of the gradient allreduce with the backward gives 1.0;
an exposed allreduce shows up directly as lost efficiency.

Runs unchanged on a CPU mesh (``--xla_force_host_platform_device_count``)
for CI smoke tests and on a real pod slice for the certified number —
device widths come from ``jax.devices()`` either way.
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass

import jax
import numpy as np

from pytorch_distributed_training_tutorials_tpu.bench.harness import slope_time


@dataclass
class ScalePoint:
    """One mesh width's measurement."""

    num_chips: int
    per_device_batch: int
    global_batch: int
    step_time_s: float
    images_per_sec: float
    images_per_sec_per_chip: float
    efficiency: float  # vs the 1-chip (or narrowest) width


def _default_model_and_data(per_device_batch: int, image_px: int):
    import optax

    from pytorch_distributed_training_tutorials_tpu.models import resnet18

    model = resnet18(num_classes=10, stem="cifar")
    tx = optax.sgd(1e-2, momentum=0.9)

    def make_batch(global_batch: int):
        rng = np.random.Generator(np.random.PCG64(0))
        x = rng.standard_normal(
            (global_batch, image_px, image_px, 1)
        ).astype(np.float32)
        y = rng.integers(0, 10, global_batch).astype(np.int32)
        return x, y

    return model, tx, make_batch


def sweep(
    widths=None,
    *,
    per_device_batch: int = 64,
    image_px: int = 28,
    model=None,
    tx=None,
    make_batch=None,
    n1: int = 3,
    n2: int = 10,
) -> list[ScalePoint]:
    """Measure images/s/chip at each data-parallel mesh width.

    ``widths`` defaults to powers of two up to ``len(jax.devices())``.
    Each width gets its own ``{'data': D}`` mesh over a device prefix, a
    fresh replicated train state, and a slope-timed run of the jitted
    train step on a resident batch — the collective cost being measured is
    the gradient allreduce, exactly DDP's (reference ``ddp_gpus.py:38``).
    """
    from pytorch_distributed_training_tutorials_tpu.parallel.data_parallel import (
        DataParallel,
    )
    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        create_train_state,
        make_train_step,
    )

    devices = jax.devices()
    if not widths:  # None or [] (a bare --widths flag): powers of 2
        widths = []
        w = 1
        while w <= len(devices):
            widths.append(w)
            w *= 2
    widths = sorted(set(widths))
    if widths[-1] > len(devices):
        raise ValueError(
            f"width {widths[-1]} exceeds {len(devices)} available devices"
        )

    if model is None:
        model, tx, make_batch = _default_model_and_data(
            per_device_batch, image_px
        )
    elif tx is None or make_batch is None:
        raise ValueError(
            "sweep(model=...) requires tx and make_batch as well"
        )

    points: list[ScalePoint] = []
    base_per_chip: float | None = None
    for width in widths:
        mesh = create_mesh({"data": width}, devices=devices[:width])
        dp = DataParallel(mesh)
        global_batch = per_device_batch * width
        x, y = make_batch(global_batch)
        batch = (dp.shard_batch(x), dp.shard_batch(y))
        state = create_train_state(model, tx, x, strategy=dp)
        has_bn = state.batch_stats is not None
        step = make_train_step(loss="cross_entropy", has_batch_stats=has_bn)

        # state is donated: thread it through the chained steps
        state_box = [state]

        def run(k):
            s = state_box[0]
            for _ in range(k):
                s, metrics = step(s, batch)
            state_box[0] = s
            return float(metrics["loss"])

        dt = slope_time(run, n1=n1, n2=n2, warmup=2)
        per_chip = global_batch / dt / width
        if base_per_chip is None:
            base_per_chip = per_chip
        points.append(
            ScalePoint(
                num_chips=width,
                per_device_batch=per_device_batch,
                global_batch=global_batch,
                step_time_s=dt,
                images_per_sec=global_batch / dt,
                images_per_sec_per_chip=per_chip,
                efficiency=per_chip / base_per_chip,
            )
        )
    return points


def report(points: list[ScalePoint], *, workload: str | None = None) -> dict:
    """JSON-ready sweep summary (the shape committed as scaling JSON)."""
    return {
        "metric": "ddp_weak_scaling_efficiency",
        "workload": workload
        or "resnet18 synthetic images, cross-entropy, sgd+momentum",
        "backend": jax.default_backend(),
        "points": [asdict(p) for p in points],
        "efficiency_at_max_width": points[-1].efficiency if points else None,
    }


def main() -> None:
    import argparse

    from pytorch_distributed_training_tutorials_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--widths", type=int, nargs="*", default=None,
        help="mesh widths to sweep (default: powers of 2 up to all devices)",
    )
    parser.add_argument("--per_device_batch", type=int, default=64)
    parser.add_argument("--image_px", type=int, default=28)
    parser.add_argument("--out", type=str, default=None, help="JSON path")
    parser.add_argument(
        "--hlo_roofline", action="store_true",
        help="also extract per-width collective bytes from compiled HLO "
        "and emit a v4-32 ring-allreduce roofline PREDICTION (no "
        "hardware executed for it)",
    )
    parser.add_argument(
        "--predict_chips", type=int, default=32,
        help="target width for the roofline prediction",
    )
    parser.add_argument(
        "--predict_step_ms", type=float, default=10.23,
        help="measured single-chip step time anchoring the prediction "
        "(default: the ResNet-18 bs512 bf16 v5e trace anchor, "
        "round 4 profile — restate when predicting other workloads)",
    )
    args = parser.parse_args()

    points = sweep(
        args.widths,
        per_device_batch=args.per_device_batch,
        image_px=args.image_px,
    )
    rep = report(
        points,
        workload=(
            f"resnet18 synthetic {args.image_px}x{args.image_px}, "
            "cross-entropy, sgd+momentum"
        ),
    )
    if args.hlo_roofline:
        stats = [
            collective_stats(
                p.num_chips,
                per_device_batch=args.per_device_batch,
                image_px=args.image_px,
            )
            for p in points
            if p.num_chips > 1
        ]
        rep["hlo_collectives"] = stats
        if stats:
            # ring payload is width-independent; use the widest compiled
            payload = (
                stats[-1]["collectives"].get("all-reduce", {}).get("bytes", 0)
            )
            rep["ici_roofline_prediction"] = predict_ici_efficiency(
                payload,
                chips=args.predict_chips,
                step_compute_s=args.predict_step_ms / 1e3,
            )
            pr = rep["ici_roofline_prediction"]
            print(
                f"  roofline @ {args.predict_chips} chips: allreduce "
                f"{payload/1e6:.1f} MB -> efficiency floor "
                f"{pr['efficiency_no_overlap']:.3f}, ceiling "
                f"{pr['efficiency_full_overlap']:.3f} (PREDICTION)"
            )
    for p in points:
        print(
            f"  {p.num_chips:>3} chips: {p.images_per_sec_per_chip:,.0f} "
            f"img/s/chip, efficiency {p.efficiency:.3f}"
        )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=1)
        print(f"wrote {args.out}")
    else:
        print(json.dumps(rep))



# ---------------------------------------------------------------------------
# HLO collective roofline (no hardware required)
#
# The CPU sweep above certifies SPMD correctness, but 8 virtual devices on
# one core cannot say anything about ICI efficiency at real widths. What CAN
# be said without hardware: the compiled program's collective traffic is in
# the HLO — XLA compiles the gradient allreduce into explicit all-reduce ops
# whose operand shapes give exact per-device payload bytes. Combined with a
# measured single-chip step time (the bench anchor) and the ring-allreduce
# cost model, that yields a principled roofline *prediction* for the
# BASELINE >=90%-at-32-chips target, clearly labeled as a prediction.
# ---------------------------------------------------------------------------

_SHAPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "s4": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "u4": 1,
    "c64": 8, "c128": 16, "pred": 1,
}

# Base names plus XLA's async split forms: the TPU latency-hiding
# scheduler rewrites `all-reduce` into `all-reduce-start`/`-done` pairs in
# the optimized HLO. `-start` carries the payload shape; `-done` is
# counted as zero bytes so a pair isn't double-counted.
_COLLECTIVE_BASES = (
    "all-reduce", "reduce-scatter", "all-gather", "collective-permute",
    "all-to-all",
)
_COLLECTIVES = tuple(
    base + suffix for base in _COLLECTIVE_BASES
    for suffix in ("-start", "-done", "")
)


def _shape_nbytes(shape_str: str) -> int:
    """Bytes of one HLO shape literal like ``f32[64,128]{1,0:T(8,128)}``."""
    import re

    m = re.match(r"(\w+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0
    dtype, dims = m.group(1), m.group(2)
    if dtype not in _SHAPE_BYTES:
        raise ValueError(
            f"unknown HLO dtype {dtype!r} in {shape_str!r} — add it to "
            "_SHAPE_BYTES (silently counting 0 would under-report the "
            "collective payload)"
        )
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _SHAPE_BYTES[dtype]


def collective_footprint(hlo_text: str) -> dict:
    """Per-collective op counts and payload bytes from compiled HLO text.

    Sums the OUTPUT shape bytes of every collective instruction (for
    all-reduce the payload each device contributes and receives; tuples —
    XLA's fused gradient buckets — are summed element-wise). Returns
    ``{"all-reduce": {"ops": N, "bytes": B}, ...}`` plus a ``"total"``.
    """
    import re

    out: dict = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(
            r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}:()\s]+?)\s+"
            r"(" + "|".join(_COLLECTIVES) + r")\(",
            line,
        )
        if not m:
            continue
        shapes_str, op = m.group(1), m.group(2)
        done = op.endswith("-done")
        for suffix in ("-start", "-done"):
            if op.endswith(suffix):
                op = op[: -len(suffix)]
        d = out.setdefault(op, {"ops": 0, "bytes": 0})
        if done:
            continue  # payload already counted on the matching -start
        shapes = re.findall(r"\w+\[[\d,]*\](?:\{[^}]*\})?", shapes_str)
        nbytes = sum(_shape_nbytes(sh) for sh in shapes)
        d["ops"] += 1
        d["bytes"] += nbytes
    out["total"] = {
        "ops": sum(v["ops"] for v in out.values()),
        "bytes": sum(v["bytes"] for v in out.values()),
    }
    return out


def collective_stats(width: int, *, per_device_batch: int = 64,
                     image_px: int = 28, model=None, tx=None,
                     make_batch=None) -> dict:
    """Compile the DDP train step for a ``{'data': width}`` mesh and
    extract its collective footprint from the optimized HLO.

    Needs ``width`` (virtual) devices — run under
    ``--xla_force_host_platform_device_count=N`` for widths beyond the
    host's real device count. Nothing executes; this is AOT lowering only.
    (It recompiles the step ``sweep()`` already compiled — accepted so the
    function stays usable WITHOUT running a sweep; the cost is one XLA
    compile per width on the receipt-generation path only.)
    """
    from pytorch_distributed_training_tutorials_tpu.parallel.data_parallel import (
        DataParallel,
    )
    from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
    from pytorch_distributed_training_tutorials_tpu.train.trainer import (
        create_train_state,
        make_train_step,
    )

    if model is None:
        model, tx, make_batch = _default_model_and_data(
            per_device_batch, image_px
        )
    mesh = create_mesh({"data": width}, devices=jax.devices()[:width])
    dp = DataParallel(mesh)
    global_batch = per_device_batch * width
    x, y = make_batch(global_batch)
    batch = (dp.shard_batch(x), dp.shard_batch(y))
    state = create_train_state(model, tx, x, strategy=dp)
    step = make_train_step(
        loss="cross_entropy", has_batch_stats=state.batch_stats is not None
    )
    compiled = step.lower(state, batch).compile()
    stats = collective_footprint(compiled.as_text())
    grad_bytes = 4 * sum(
        l.size for l in jax.tree_util.tree_leaves(state.params)
    )
    return {
        "num_chips": width,
        "collectives": stats,
        "f32_grad_bytes": grad_bytes,
    }


def predict_ici_efficiency(
    allreduce_bytes: int,
    *,
    chips: int = 32,
    step_compute_s: float,
    ici_bytes_per_s: float = 1.0e11,
) -> dict:
    """Ring-allreduce roofline at a target width — a PREDICTION, labeled.

    Model: a D-chip ring all-reduce moves ``2*(D-1)/D * payload`` bytes
    through each chip's ICI links (reduce-scatter + all-gather phases).
    ``ici_bytes_per_s`` defaults to 1e11 (100 GB/s) — a conservative
    per-chip algorithmic bandwidth for a v4 3D-torus ring (each v4 link
    runs ~50 GB/s/direction and a torus ring uses two of them; the
    scaling-book recipe). Two bounds are reported: ``efficiency_no_overlap``
    (the allreduce fully exposed after the backward — the floor) and
    ``efficiency_full_overlap`` (allreduce hidden under the backward's
    ~2/3 of step compute except any residue — the ceiling XLA's latency-
    hiding scheduler approaches when per-bucket allreduces interleave with
    grad computation).
    """
    ring = 2.0 * (chips - 1) / chips
    t_comm = ring * allreduce_bytes / ici_bytes_per_s
    no_overlap = step_compute_s / (step_compute_s + t_comm)
    backward_s = (2.0 / 3.0) * step_compute_s
    exposed = max(0.0, t_comm - backward_s)
    full_overlap = step_compute_s / (step_compute_s + exposed)
    return {
        "prediction": True,
        "chips": chips,
        "allreduce_payload_bytes": int(allreduce_bytes),
        "ici_bytes_per_s_assumed": ici_bytes_per_s,
        "ring_allreduce_s": t_comm,
        "step_compute_s": step_compute_s,
        "efficiency_no_overlap": no_overlap,
        "efficiency_full_overlap": full_overlap,
    }


if __name__ == "__main__":
    main()
