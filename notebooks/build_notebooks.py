"""Generate the three tutorial notebooks (twins of the reference's 01/02/03).

Each notebook reproduces the corresponding reference lesson's *observable*
behavior on TPU (SURVEY.md section 7, build step 8): the per-chip batch
split, the steps-per-epoch sharding proof, and the model-parallel placement
audit + benchmark. Run ``python notebooks/build_notebooks.py`` to regenerate
the ``.ipynb`` files; ``tests/test_notebooks.py`` executes every code cell.
"""

from __future__ import annotations

import os

import nbformat as nbf

HERE = os.path.dirname(os.path.abspath(__file__))


def build(name: str, cells: list[tuple[str, str]]) -> None:
    """Regenerate one notebook, CARRYING OVER captured outputs for code
    cells whose source is unchanged (matched by deterministic cell id).

    The reference's verification mechanism is captured outputs committed
    in the .ipynb — the "Steps 16" vs "Steps 64" sharding proof a reader
    sees without running anything (``02.ddp_toy_example.ipynb:255-318``).
    Carrying unchanged cells' outputs keeps regeneration byte-stable
    (pinned by test_notebooks_regenerate_cleanly) while an edited cell
    drops its stale output until ``--execute`` refreshes it.
    """
    path = os.path.join(HERE, name)
    prior: dict[str, tuple[str, list, object]] = {}
    if os.path.exists(path):
        try:
            old = nbf.read(path, as_version=4)
            for c in old.cells:
                if c.cell_type == "code":
                    prior[c.get("id")] = (
                        c.source,
                        c.get("outputs", []),
                        c.get("execution_count"),
                    )
        except Exception:
            pass
    nb = nbf.v4.new_notebook()
    nb.metadata["kernelspec"] = {
        "display_name": "Python 3", "language": "python", "name": "python3",
    }
    for i, (kind, src) in enumerate(cells):
        src = src.strip("\n")
        if kind == "md":
            cell = nbf.v4.new_markdown_cell(src)
        else:
            cell = nbf.v4.new_code_cell(src)
            old = prior.get(f"cell-{i}")
            if old is not None and old[0] == src:
                cell["outputs"] = old[1]
                cell["execution_count"] = old[2]
        cell["id"] = f"cell-{i}"  # deterministic: output is committed
        nb.cells.append(cell)
    with open(path, "w") as f:
        nbf.write(nb, f)
    print("wrote", path)


def execute(name: str) -> None:
    """Run every code cell in a fresh working dir and store its captured
    stdout as the cell's committed output (the reference's executed-
    notebook verification, SURVEY.md section 4). Subprocess-driving cells
    capture their own children's stdout and print it, so one
    stdout-stream output per cell is the complete observable record."""
    import contextlib
    import io
    import sys
    import tempfile

    # cells import the package the way a notebook user would — make the
    # checkout importable in this fresh interpreter
    repo_root = os.path.dirname(HERE)
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    os.environ["PYTHONPATH"] = (
        repo_root + os.pathsep + os.environ.get("PYTHONPATH", "")
    )
    path = os.path.join(HERE, name)
    nb = nbf.read(path, as_version=4)
    ns: dict = {"__name__": "__main__"}
    count = 0
    cwd = os.getcwd()
    with tempfile.TemporaryDirectory() as tmp:
        os.chdir(tmp)  # figures etc. land in a scratch dir
        try:
            for cell in nb.cells:
                if cell.cell_type != "code":
                    continue
                count += 1
                buf = io.StringIO()
                with contextlib.redirect_stdout(buf):
                    exec(compile(cell.source, f"{name}[{cell['id']}]",
                                 "exec"), ns)
                text = buf.getvalue()
                cell["outputs"] = (
                    [nbf.v4.new_output("stream", name="stdout", text=text)]
                    if text
                    else []
                )
                cell["execution_count"] = count
        finally:
            os.chdir(cwd)
    with open(path, "w") as f:
        nbf.write(nb, f)
    print("executed", path)


SETUP = """
# Hardware-portable setup: on a TPU host this uses the real chips; anywhere
# else it fakes an 8-device CPU mesh (the tutorials' "multi-node without a
# cluster" posture, SURVEY.md section 4).
import os
if not os.environ.get("TPU_DDP_NB_REAL"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
import jax
if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")
print(f"{len(jax.devices())} devices:", jax.devices())
"""


# --------------------------------------------------------------------------
# 01 — data parallelism in one process (twin of 01.data_parallel.ipynb)
# --------------------------------------------------------------------------
NB01 = [
    ("md", """
# 01 — Data parallelism in one process

Twin of the reference's `01.data_parallel.ipynb`: one Python process drives
every local accelerator. In torch this is `nn.DataParallel` — per step it
**replicates** the module, **scatters** the batch, runs 4 GIL-bound threads,
and **gathers** the outputs. On TPU the whole dance collapses into one
compiled SPMD program: params live replicated (no per-step broadcast), the
batch is *sharded* along the `data` mesh axis, and XLA compiles the
scatter/gather away. This notebook reproduces the lesson's observable: **a
global batch of 32 splits into 8 per-chip blocks of 4** (the reference's
`Input shape: [8, 32]` prints, cell 16).
"""),
    ("code", SETUP),
    ("md", """
## Device inventory
The reference checks `torch.cuda.device_count()` (cell 3). The TPU twin is a
named **mesh** over the local devices — the one abstraction all later
parallelism configs reuse.
"""),
    ("code", """
from pytorch_distributed_training_tutorials_tpu import create_mesh
mesh = create_mesh()            # {'data': <all devices>}
print(dict(mesh.shape))
"""),
    ("md", """
## Dataset and the *global-batch* loader
`RandomDataset(32, 1024)` twin: 1,024 samples of `randn(32)`. The reference
feeds `DataLoader(batch_size=32)` and lets DataParallel split each batch;
here `batch_mode="global"` means 32 is the *whole-step* batch that the mesh
divides (the per-device default used everywhere else preserves the
reference's `--batch_size` per-device semantics).
"""),
    ("code", """
from pytorch_distributed_training_tutorials_tpu.data import (
    ShardedLoader, random_dataset,
)
ds = random_dataset(size=32, length=1024)
loader = ShardedLoader(ds, 32, mesh, batch_mode="global", shuffle=False)
batch = next(iter(loader))
print("global batch:", batch.shape)
"""),
    ("md", """
## The observable: the per-chip split
The reference *proves* the scatter with shape prints from inside each
replica's forward. Under SPMD there is no per-replica program to print from —
the proof lives on the array itself: its addressable shards.
"""),
    ("code", """
from pytorch_distributed_training_tutorials_tpu.ops import (
    per_shard_shapes, describe_sharding,
)
print("per-shard shapes:", per_shard_shapes(batch))   # 8 x (4, 32)
print(describe_sharding(batch))
"""),
    ("md", """
## One training step, compiled
`SampleModel` twin (`Linear(32, 2)`), Adam(1e-3), and the reference's
`loss = output.sum()` (cell 16). Params replicated x batch sharded: XLA
inserts the gradient allreduce — the compiled equivalent of DataParallel's
gather + backward reduction, minus the per-step replication cost.
"""),
    ("code", """
import jax, jax.numpy as jnp, optax
from pytorch_distributed_training_tutorials_tpu.models import SampleModel
from pytorch_distributed_training_tutorials_tpu.parallel import DataParallel

model = SampleModel()
dp = DataParallel(mesh)
params = jax.jit(model.init, out_shardings=dp.param_sharding)(
    jax.random.PRNGKey(0), batch
)
opt = optax.adam(1e-3)
opt_state = opt.init(params)

@jax.jit
def step(params, opt_state, x):
    def loss_fn(p):
        out = model.apply(p, x)
        return out.sum()          # the lesson's toy objective
    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = opt.update(grads, opt_state)
    return optax.apply_updates(params, updates), opt_state, loss

for i, x in enumerate(loader):
    params, opt_state, loss = step(params, opt_state, x)
    if i < 3:
        print(f"step {i}: loss {float(loss):+.3f}  "
              f"input split {per_shard_shapes(x)[0]} x {len(jax.devices())}")
print("steps per epoch:", len(loader), "(1024 / 32)")
"""),
    ("md", """
## What replaced what

| torch `nn.DataParallel` (per step) | TPU SPMD (compiled once) |
|---|---|
| replicate module to N GPUs | params placed replicated **once** |
| scatter batch dim 0 | `data`-axis sharding annotation |
| 4 Python threads forward | one XLA program on all chips |
| gather outputs to GPU 0 | outputs stay sharded (or psum'd) |
| grads reduce to master | allreduce compiled into backward |

The GIL-threading bottleneck this lesson warns about does not exist here —
that is the point of the SPMD design.
"""),
]

# --------------------------------------------------------------------------
# 02 — DDP: multi-process data parallelism (twin of 02.ddp_toy_example.ipynb)
# --------------------------------------------------------------------------
NB02 = [
    ("md", """
# 02 — Distributed data parallelism

Twin of the reference's `02.ddp_toy_example.ipynb`. Vocabulary first (the
reference's cell 2): **all-to-one = reduce**, **one-to-all = broadcast**,
every process has a **rank** in `[0, world_size)`. Then the lesson itself:
the same trainer launched two ways — explicit ranks (`mp.spawn`) and
environment-discovered topology (`torchrun`) — proving the data *shards*
(`Steps 64` alone vs `Steps 16` at world size 4).
"""),
    ("code", SETUP),
    ("md", """
## Collectives, hands on
The reference names NCCL; here collectives are XLA ops over ICI. A `psum`
over the mesh *is* the DDP gradient allreduce.
"""),
    ("code", """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from pytorch_distributed_training_tutorials_tpu import create_mesh

mesh = create_mesh()
n = mesh.devices.size

@jax.shard_map(mesh=mesh, in_specs=P("data"), out_specs=P())
def allreduce(x):
    return jax.lax.psum(x, "data")          # all-to-one... then to all

ranks = jnp.arange(n, dtype=jnp.float32)
print("per-device values:", ranks, "-> allreduce:", allreduce(ranks))

@jax.shard_map(mesh=mesh, in_specs=P(), out_specs=P("data"))
def broadcast(x):
    return x                                 # one-to-all: replication
print("broadcast 7.0 ->", broadcast(jnp.asarray([7.0])))
"""),
    ("md", """
## The trainer, in-notebook
The exact `ddp_gpus.py` workload: `Linear(20, 1)` on 2,048 synthetic
samples, SGD(1e-2), batch 32 **per device**. One SPMD process stands in for
the whole process group (multi-host runs use the identical code — see the
launch contracts below).
"""),
    ("code", """
import optax
from pytorch_distributed_training_tutorials_tpu.data import (
    ShardedLoader, synthetic_regression,
)
from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor
from pytorch_distributed_training_tutorials_tpu.train import Trainer

loader = ShardedLoader(synthetic_regression(2048), 32, mesh)
trainer = Trainer(LinearRegressor(), loader, optax.sgd(1e-2), loss="mse")
trainer.train(3)
print("sanity: 2048 / 32 =", 2048 / 32, "steps if unsharded")
print(f"sharded across {mesh.devices.size}: {len(loader)} steps/epoch")
"""),
    ("md", """
## Launch contract 1 — spawn (explicit ranks)
`mp.spawn` twin: the parent forks N OS processes, injects each rank, and
fixes the rendezvous address up front (`ddp_gpus.py:12-17,104-105`). Real
jax.distributed worlds over CPU devices + gloo collectives — multi-process
without a cluster.
"""),
    ("code", """
import subprocess, sys, os
import pytorch_distributed_training_tutorials_tpu as pkg
repo_root = os.path.dirname(os.path.dirname(os.path.abspath(pkg.__file__)))
env = {
    k: v for k, v in os.environ.items() if k != "TPU_WORKER_HOSTNAMES"
}
env["JAX_PLATFORMS"] = "cpu"
env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
out = subprocess.run(
    [sys.executable, "-m",
     "pytorch_distributed_training_tutorials_tpu.launch.train_ddp",
     "--max_epochs", "1", "--batch_size", "32",
     "--nprocs", "4", "--platform", "cpu"],
    capture_output=True, text=True, timeout=600, env=env,
)
print(out.stdout)
assert "Steps 16]" in out.stdout   # 2048 / 32 / 4 — the sharding proof
"""),
    ("md", """
## Launch contract 2 — environment-discovered (the torchrun twin)
The script owns *no* topology: `JAX_COORDINATOR_ADDRESS` /
`JAX_NUM_PROCESSES` / `JAX_PROCESS_ID` come from the launcher (on a real TPU
pod, from the runtime metadata — the pod is the elastic agent). Bare launch =
1 process = no sharding = `Steps 64`, the reference's cell 11 output.
"""),
    ("code", """
out = subprocess.run(
    [sys.executable, "-m",
     "pytorch_distributed_training_tutorials_tpu.launch.train_ddp_env",
     "--max_epochs", "1", "--batch_size", "32"],
    capture_output=True, text=True, timeout=600,
    env={**env, "XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
)
print(out.stdout)
assert "Steps 64]" in out.stdout   # 2048 / 32, unsharded
"""),
    ("md", """
The delta between the two scripts is *only* where topology comes from —
the same seam as `ddp_gpus.py` vs `ddp_gpus_torchrun.py`. Everything after
`init()` is identical SPMD code.
"""),
]

# --------------------------------------------------------------------------
# 03 — model parallelism (twin of 03.model_parallel.ipynb)
# --------------------------------------------------------------------------
NB03 = [
    ("md", """
# 03 — Model parallelism

Twin of the reference's `03.model_parallel.ipynb`, three lessons:

1. **Auto placement + 8-bit load** (`device_map="auto"` +
   `load_in_8bit=True`): a checkpoint restored with matmul weights
   quantized to int8 and placement decided declaratively.
2. **Toy 2-device split**: `Linear(10000,10) -> relu -> Linear(10,5)` with
   the activation hopping devices mid-forward.
3. **Pipeline-split ResNet-50** benchmarked against single-device.
"""),
    ("code", SETUP),
    ("md", """
## Lesson 1 — quantize-on-load + placement audit
The reference streams Llama-7B into int8 (cell 2) and audits every param's
device/dtype (cell 4). Same flow, declarative: orbax restore ->
`load_quantized` -> audit. Int8 matmul weights, float norms — the same
mixed-precision layout the reference's audit shows.
"""),
    ("code", """
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from pytorch_distributed_training_tutorials_tpu.models import (
    TransformerConfig, TransformerLM, model_size,
)
from pytorch_distributed_training_tutorials_tpu.parallel.auto import (
    save_checkpoint, load_quantized, audit_placement,
)

cfg = TransformerConfig(vocab_size=256, d_model=128, n_layers=2, n_heads=4)
lm = TransformerLM(cfg)
variables = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
print(f"params: {model_size(variables['params']):,}")

ckpt = os.path.join(tempfile.mkdtemp(), "lm")
save_checkpoint(ckpt, dict(variables["params"]))
q = load_quantized(ckpt)

from pytorch_distributed_training_tutorials_tpu.ops import Int8Param
flat = jax.tree_util.tree_flatten_with_path(
    q, is_leaf=lambda x: isinstance(x, Int8Param))[0]
for kp, leaf in flat[:6]:
    name = "/".join(str(getattr(k, "key", k)) for k in kp)
    if isinstance(leaf, Int8Param):
        print(f"{name}: int8 {leaf.q.shape} + f32 scales")
    else:
        print(f"{name}: {leaf.dtype} {leaf.shape}")
"""),
    ("md", """
### Lesson 1b — serve it (the step the reference stops short of)
The reference loads Llama-7B 8-bit but never generates (`GenerationConfig`
imported, no `generate` call anywhere). Serving the quantized model exposed
two TPU lessons:

1. **One scanned block body, not L unrolled copies** — serve with
   `scan_layers=True` and `stack_quantized_lm_params` (per-layer int8
   scales are exactly per-layer quantization; generations are
   token-identical). Compile time and program size become O(1) in depth.
2. **Pin loaded checkpoints on device** — leaf-streamed restores land as
   host numpy, and jit re-uploads numpy arguments on *every* call
   (the whole tree's bytes per launch).
   `utils.tree.device_materialize` is one exact-identity launch that
   fixes it; `load_quantized_lm` applies it automatically.
"""),
    ("code", """
import dataclasses
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    quantize_lm_params, stack_quantized_lm_params,
)
from pytorch_distributed_training_tutorials_tpu.models.generate import generate
from pytorch_distributed_training_tutorials_tpu.utils.tree import device_materialize

qparams = quantize_lm_params(dict(variables["params"]))
stacked = device_materialize(stack_quantized_lm_params(qparams))
serve_lm = TransformerLM(
    dataclasses.replace(cfg, quantized=True, scan_layers=True)
)
prompt = (jnp.arange(8, dtype=jnp.int32)[None].repeat(2, 0)) % cfg.vocab_size
out = generate(serve_lm, stacked, prompt, max_new_tokens=8)
print("generated:", np.asarray(out[:, 8:]))
# one (L, ...) leaf per weight instead of L separate copies:
print("stacked q_proj q:",
      stacked["layers"]["block"]["attn"]["q_proj"]["q"].shape, "int8")
"""),
    ("md", """
## Lesson 2 — the toy 2-device split
The reference pins `net1` to `cuda:0`, `net2` to `cuda:1`, and calls
`x.to("cuda:1")` mid-forward (cells 7/12). The twin: each stage is its own
XLA program committed to its device; the hop is an explicit transfer (ICI on
real hardware); backward re-crosses it in reverse.
"""),
    ("code", """
import optax
from pytorch_distributed_training_tutorials_tpu.models import ToyModel
from pytorch_distributed_training_tutorials_tpu.parallel import ManualPipeline

rng = np.random.Generator(np.random.PCG64(0))
pipe = ManualPipeline.from_linen(
    ToyModel(), np.zeros((2, 10000), np.float32),
    devices=jax.devices()[:2], loss="mse", optimizer=optax.sgd(1e-3),
)
for line in pipe.placement_audit():
    print(line)
for step in range(3):
    x = rng.standard_normal((20, 10000)).astype(np.float32)
    y = rng.standard_normal((20, 5)).astype(np.float32)
    print(f"step {step}: loss {float(pipe.train_step(x, y)):.4f}")
"""),
    ("md", """
## Lesson 3 — pipeline-split ResNet-50
conv1..layer2 on device 0, layer3..fc on device 1 (cells 18/26). The
param-count invariance check is the reference's cells 20/22: **25,557,032**
parameters whether split or not.
"""),
    ("code", """
from pytorch_distributed_training_tutorials_tpu.models import resnet50
from pytorch_distributed_training_tutorials_tpu.bench.harness import benchmark

BATCH, IMG = 16, 32   # reference uses 120 @ 3x128x128; scaled to run anywhere
model = resnet50(num_classes=1000)
pipe = ManualPipeline.from_linen(
    model, np.zeros((2, IMG, IMG, 3), np.float32),
    devices=jax.devices()[:2], loss="mse", optimizer=optax.sgd(1e-3),
)
counts = pipe.stage_param_counts()
print("per-stage params:", [f"{c:,}" for c in counts])
print(f"total {sum(counts):,} == unsplit 25,557,032:",
      sum(counts) == 25_557_032)
"""),
    ("code", """
# the reference's timeit.repeat benchmark (cell 28) — async-dispatch-correct
x = rng.standard_normal((BATCH, IMG, IMG, 3)).astype(np.float32)
y = rng.standard_normal((BATCH, 1000)).astype(np.float32)

from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader
from pytorch_distributed_training_tutorials_tpu.data.datasets import ArrayDataset
from pytorch_distributed_training_tutorials_tpu.parallel.mesh import create_mesh
from pytorch_distributed_training_tutorials_tpu.train import Trainer

mesh1 = create_mesh({"data": 1}, devices=jax.devices()[:1])
single = Trainer(
    resnet50(num_classes=1000),
    ShardedLoader(ArrayDataset((x, y)), BATCH, mesh1), optax.sgd(1e-3),
    loss="mse",
)
batch = next(iter(single.loader))

def single_step():
    # train_step donates the state: rebind it every call
    single.state, metrics = single.train_step(single.state, batch)
    return metrics["loss"]

pp = benchmark(lambda: pipe.train_step(x, y), name="2-stage pipeline",
               warmup=1, repeat=5)
sg = benchmark(single_step, name="single device", warmup=1, repeat=5)
print(pp)
print(sg)
"""),
    ("code", """
# the reference's matplotlib bar chart (cells 29-30)
import matplotlib
matplotlib.use("Agg")
import matplotlib.pyplot as plt

fig, ax = plt.subplots(figsize=(5, 3.2))
names = [pp.name, sg.name]
means = [pp.mean_s, sg.mean_s]
stds = [pp.std_s, sg.std_s]
ax.bar(names, means, yerr=stds, color=["#4477aa", "#ee6677"], capsize=6)
ax.set_ylabel("seconds / step")
ax.set_title("ResNet-50: 2-stage pipeline vs single device")
fig.tight_layout()
fig.savefig("resnet50_pipeline_vs_single.png", dpi=120)
print("saved resnet50_pipeline_vs_single.png")
"""),
    ("md", """
Like the reference's chart, the 2-stage *sequential* pipeline is **not**
faster than one device — one batch flows stage0 -> stage1 with no microbatch
interleave, so stages idle (the reference makes the same point, cell 27's
discussion). The split buys *memory headroom* (each device holds ~half the
params), not throughput; adding microbatching is the classic fix and is
where a `stage`-axis `shard_map` schedule would slot in.
"""),
]


# --------------------------------------------------------------------------
# 04 — scaling out (beyond the reference: FSDP, microbatched pipelines,
#      elastic restart, scaling efficiency)
# --------------------------------------------------------------------------
NB04 = [
    ("md", """
# 04 — Scaling out: FSDP, microbatched pipelines, elastic training

The reference *declares* deepspeed and megatron-fsdp in its environment
(`environment.yml:62-63`) and writes its torchrun script against an elastic
agent — but never builds any of it. This lesson makes those capabilities
real, the TPU way: each one is a **sharding recipe over the same named
mesh**, not a wrapper framework.
"""),
    ("code", SETUP),
    ("md", """
## FSDP / ZeRO — shard the *parameters*, not just the batch
DDP keeps every parameter, gradient, and optimizer moment on every chip.
FSDP shards them over the `data` axis; XLA compiles the all-gather-at-use /
reduce-scatter schedule from the annotations. Per-chip HBM for everything
sharded drops to `1/world` — the ZeRO-3 memory curve — while the numerics
are *identical* to DDP (it's an execution schedule, not a new optimizer).
"""),
    ("code", """
import jax, numpy as np, optax
from pytorch_distributed_training_tutorials_tpu import create_mesh
from pytorch_distributed_training_tutorials_tpu.data import ShardedLoader
from pytorch_distributed_training_tutorials_tpu.data.datasets import ArrayDataset
from pytorch_distributed_training_tutorials_tpu.models import MLP
from pytorch_distributed_training_tutorials_tpu.parallel import FSDP
from pytorch_distributed_training_tutorials_tpu.train import Trainer

mesh = create_mesh()
rng = np.random.Generator(np.random.PCG64(0))
labels = rng.integers(0, 4, 512).astype(np.int32)
centers = rng.standard_normal((4, 64)).astype(np.float32) * 3
x = centers[labels] + 0.1 * rng.standard_normal((512, 64)).astype(np.float32)

loader = ShardedLoader(ArrayDataset((x, labels)), 8, mesh)
trainer = Trainer(
    MLP(features=(256, 4)), loader, optax.adam(1e-3),
    strategy=FSDP(mesh, min_size=256), loss="cross_entropy",
)
trainer.train(3)

k = trainer.state.params["Dense_0"]["kernel"]
mu = trainer.state.opt_state[0].mu["Dense_0"]["kernel"]
print("kernel:", k.shape, "spec", k.sharding.spec,
      "-> per-chip shard", k.addressable_shards[0].data.shape)
print("adam mu follows:", mu.sharding.spec)
"""),
    ("md", """
Each chip holds 1/8 of the kernel *and* 1/8 of Adam's moments — the audit
above is the observable. Swap `FSDP(mesh)` for `DataParallel(mesh)` and the
loss curve is bit-for-bit the same (`tests/test_fsdp.py` pins this).

## Pipeline parallelism with microbatching — one compiled program
The reference's 2-stage split runs one batch through stage0 then stage1,
stages idling in turn (lesson 03). The production schedule is **GPipe**:
split the batch into microbatches that fill and drain the pipeline. With a
scanned transformer the whole dp x pp schedule is ONE `shard_map` program:
the layer stack's leading axis is sharded over `stage` (placement = an
annotation), activations hop stages via `ppermute`, and data parallelism
rides the `data` axis of the same mesh.
"""),
    ("code", """
import jax.numpy as jnp
from pytorch_distributed_training_tutorials_tpu.data import synthetic_lm
from pytorch_distributed_training_tutorials_tpu.models.transformer import (
    TransformerConfig, TransformerLM,
)
from pytorch_distributed_training_tutorials_tpu.parallel import (
    PipelinedTransformerLM, PipelineParallel,
)

mesh_pp = create_mesh({"data": 4, "stage": 2})
cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=4, n_heads=2,
                        max_seq_len=32, scan_layers=True)
model = PipelinedTransformerLM(cfg, mesh_pp, num_microbatches=4)

key = jax.random.PRNGKey(0)
tokens = jax.random.randint(key, (16, 8), 0, 64)
variables = model.init(key, tokens)

# the schedule reorders compute, not math: identical logits
ref = TransformerLM(cfg)
diff = jnp.abs(model.apply(variables, tokens) - ref.apply(variables, tokens))
print("max |pipelined - unpipelined| =", float(diff.max()))

loader = ShardedLoader(synthetic_lm(size=256, seq_len=16, vocab_size=64),
                       16, mesh_pp)
t_pp = Trainer(model, loader, optax.adam(3e-3),
               strategy=PipelineParallel(mesh_pp, num_microbatches=4),
               loss="cross_entropy")
t_pp.train(2)
qk = t_pp.state.params["layers"]["block"]["attn"]["q_proj"]["kernel"]
print("4 stacked layers, spec", qk.sharding.spec,
      "-> resident per stage:", qk.addressable_shards[0].data.shape[0])
"""),
    ("md", """
## Heterogeneous stages: GPipe on sub-mesh columns
ResNet-style cuts have no common stacked-layer axis to shard, so each stage
gets one *column* of the `{'data': D, 'stage': S}` grid (its own data-parallel
sub-mesh); microbatches fill/drain across columns, gradients and BatchNorm
statistics accumulate and apply once per step — plain gradient accumulation,
verified against a single-device comparator in `tests/test_gpipe.py`.
"""),
    ("code", """
from pytorch_distributed_training_tutorials_tpu.models import ToyModel
from pytorch_distributed_training_tutorials_tpu.parallel import GPipe

toy_x = rng.standard_normal((32, 10000)).astype(np.float32)
toy_y = rng.standard_normal((32, 5)).astype(np.float32)
pipe = GPipe.from_linen(
    ToyModel(), toy_x, devices=mesh_pp, num_microbatches=4,
    loss="mse", optimizer=optax.sgd(1e-3),
)
first = float(pipe.train_step(toy_x, toy_y))
for _ in range(4):
    last = float(pipe.train_step(toy_x, toy_y))
print(f"loss {first:.4f} -> {last:.4f} over 5 GPipe steps")
for line in pipe.placement_audit():
    print(" ", line)
"""),
    ("md", """
## Elastic restart-and-resume
torchrun's elastic agent restarts a failed world — *from scratch*, because
the reference never checkpoints. Here `spawn(max_restarts=N)` gang-aborts
the world the moment any rank dies, re-forks it with a fresh rendezvous,
and the Trainer resumes from its latest checkpoint. Below, rank 1 hard-kills
itself (`os._exit`) on the first attempt once epochs 0-1 are checkpointed;
the relaunched world resumes at epoch 2 (the printed `resumed at epoch 2`)
and finishes all 3 epochs.
"""),
    ("code", """
import subprocess, sys, tempfile, textwrap, os
import pytorch_distributed_training_tutorials_tpu as pkg
repo_root = os.path.dirname(os.path.dirname(os.path.abspath(pkg.__file__)))

script = textwrap.dedent('''
    import json, os, sys
    import numpy as np

    def worker(rank, workdir):
        from pytorch_distributed_training_tutorials_tpu.parallel import distributed
        distributed.init()
        import optax
        from pytorch_distributed_training_tutorials_tpu import create_mesh
        from pytorch_distributed_training_tutorials_tpu.data import (
            ShardedLoader, synthetic_regression,
        )
        from pytorch_distributed_training_tutorials_tpu.models import LinearRegressor
        from pytorch_distributed_training_tutorials_tpu.train import Trainer

        loader = ShardedLoader(synthetic_regression(256), 32, create_mesh())
        t = Trainer(LinearRegressor(), loader, optax.sgd(1e-2), loss="mse")
        ckpt = os.path.join(workdir, "ckpt")
        sentinel = os.path.join(workdir, "crashed_once")
        if os.path.exists(ckpt):
            t.restore(ckpt)
            print(f"[rank {rank}] resumed at epoch {t.epoch}", flush=True)
        while t.epoch < 3:
            t.train(t.epoch + 1)
            t.save(ckpt)
            if t.epoch == 2 and rank == 1 and not os.path.exists(sentinel):
                open(sentinel, "w").write("1")
                os._exit(17)  # hard crash mid-training

    if __name__ == "__main__":
        from pytorch_distributed_training_tutorials_tpu.launch import spawn
        spawn(worker, 2, args=(sys.argv[1],), env_contract=True,
              platform="cpu", max_restarts=1, join_timeout_s=600)
        print("RESTART-AND-RESUME OK")
''')

workdir = tempfile.mkdtemp()
spath = os.path.join(workdir, "elastic_demo.py")
open(spath, "w").write(script)
env = {k: v for k, v in os.environ.items()
       if k != "TPU_WORKER_HOSTNAMES"}
env["JAX_PLATFORMS"] = "cpu"
env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
out = subprocess.run([sys.executable, spath, workdir],
                     capture_output=True, text=True, timeout=900, env=env)
print(out.stdout[-2000:])
assert "RESTART-AND-RESUME OK" in out.stdout, out.stderr[-2000:]
assert os.path.exists(os.path.join(workdir, "crashed_once"))
"""),
    ("md", """
## Scaling efficiency — the number that matters at pod scale
Weak scaling: hold per-chip batch fixed, widen the `data` axis, and track
images/s/chip vs the 1-chip run. Perfect allreduce/backward overlap = 1.0;
an exposed allreduce shows up directly. (On this CPU mesh the fake devices
share one core, so efficiency drops mechanically — the harness is what
transfers to a pod, where the same command targets >=90% at 32 chips,
`BASELINE.json`.)
"""),
    ("code", """
from pytorch_distributed_training_tutorials_tpu.bench.scaling import sweep
from pytorch_distributed_training_tutorials_tpu.models import MLP as _MLP

def make_batch(global_batch):
    gx = rng.standard_normal((global_batch, 64)).astype(np.float32)
    gy = rng.integers(0, 4, global_batch).astype(np.int32)
    return gx, gy

points = sweep([1, 2, 4], per_device_batch=16,
               model=_MLP(features=(64, 4)), tx=optax.sgd(1e-2),
               make_batch=make_batch, n1=2, n2=6)
for p in points:
    print(f"  {p.num_chips} chips: {p.images_per_sec_per_chip:,.0f} "
          f"img/s/chip, efficiency {p.efficiency:.2f}")
"""),
    ("md", """
## Long context — the same attention contract, three executions

Dense causal attention materializes a `(B, H, S, S)` float32 score tensor
— quadratic HBM that caps single-chip context. Two escapes, both drop-in
`attention_fn`s for the same `TransformerLM`:

- **Pallas flash attention** (`ops.flash_attention`): blockwise online
  softmax — scores only ever exist as VMEM tiles, temp memory flat in S
  (dense needs 2.1 GB of score temps at S=4096); on the chip the
  benchmark's training cell reads `flash_attention_roofline` 31.7
  (ledger, PR 31).
- **Ring attention** (`parallel.ring_attention`): shard the *sequence*
  over a mesh axis; K/V blocks rotate via `ppermute` while each device
  folds them into the same online-softmax state — context length scales
  linearly with the ring size.

They must agree with the dense reference exactly — one contract, three
executions:
"""),
    ("code", """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from pytorch_distributed_training_tutorials_tpu.models import TransformerConfig, TransformerLM
from pytorch_distributed_training_tutorials_tpu.ops import make_flash_attention
from pytorch_distributed_training_tutorials_tpu.parallel.ring_attention import make_ring_attention
from pytorch_distributed_training_tutorials_tpu import create_mesh as _cm

cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                        max_seq_len=64)
toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 32)),
                   jnp.int32)
dense_lm = TransformerLM(cfg)
variables = dense_lm.init(jax.random.PRNGKey(0), toks)

flash_lm = TransformerLM(dataclasses.replace(
    cfg, attention_fn=make_flash_attention(16, 16)))
ring_lm = TransformerLM(dataclasses.replace(
    cfg, attention_fn=make_ring_attention(_cm({"seq": 4}))))

lg_dense = dense_lm.apply(variables, toks)
lg_flash = flash_lm.apply(variables, toks)
lg_ring = ring_lm.apply(variables, toks)
print("flash vs dense:", float(jnp.abs(lg_flash - lg_dense).max()))
print("ring  vs dense:", float(jnp.abs(lg_ring - lg_dense).max()))
assert float(jnp.abs(lg_flash - lg_dense).max()) < 1e-4
assert float(jnp.abs(lg_ring - lg_dense).max()) < 1e-4
"""),
    ("md", """
Serving composes with the same machinery: `models.generate` prefills the
prompt in one forward, decodes through a KV cache sized to the *request*
(not `max_seq_len`), and an SP-configured model falls back to the dense
path only for prompt lengths that don't divide the seq axis.
"""),
    ("md", """
## Configuring an LM train step for the MXU — the knobs that matter

Three configuration choices shape an LM train step on a TPU: flash
attention over dense (the (S, S) scores never exist in HBM), layers
**unrolled** or under `nn.scan` (a scan keeps the program O(1) in depth;
unrolling trades compile time for the scan's stacked activation saves),
and `remat_policy="dots"` (save matmul outputs, recompute only the cheap
elementwise ops; full remat re-runs every matmul in the backward, and
*no* remat cannot fit real batches). What each is worth on today's chip
is for the benchmark to say: its training cell
(`train-internlm2-1.8b-s2048` in `BENCHMARK.json`: flash attention,
scanned layers, `dots`) reads `mfu.train` 41.3 (ledger, PR 31), and
`PERF.md` has the account. The same config
object expresses all three:
"""),
    ("code", """
import optax
from pytorch_distributed_training_tutorials_tpu.train.trainer import TrainState, make_train_step

train_cfg = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=64,
    attention_fn=make_flash_attention(16, 16),  # 1. flash, not dense
    scan_layers=False,                          # 2. unrolled here
    remat=True, remat_policy="dots",            # 3. save the matmuls
)
lm = TransformerLM(train_cfg)
params = lm.init(jax.random.PRNGKey(0), toks)["params"]
state = TrainState.create(
    apply_fn=lm.apply, params=params, tx=optax.adamw(3e-4)
)
step = make_train_step("cross_entropy")  # the jitted donated SPMD step
state, metrics = step(state, (toks[:, :-1], toks[:, 1:]))
print("LM train step (flash x unrolled x dots-remat) loss:",
      float(metrics["loss"]))
# on the chip: python3 benchmark/run.py --workload train-internlm2-1.8b-s2048
"""),
    ("md", """
(Serving flips choice 2: `scan_layers=True` keeps the *program* O(1) in
depth, which is what launch-latency-bound decoding needs.
Training saves activations, serving doesn't; the two paths have different
binding constraints and the config lets each pick.)

Every recipe above — FSDP, both pipeline schedules, elastic restart, the
sweep, the long-context kernels — is the *same code* on a real pod slice;
only the mesh gets wider and the collectives move from shared-memory gloo
to ICI.
"""),
]


NOTEBOOKS = {
    "01_data_parallel.ipynb": NB01,
    "02_ddp.ipynb": NB02,
    "03_model_parallel.ipynb": NB03,
    "04_scaling_out.ipynb": NB04,
}


if __name__ == "__main__":
    import sys

    for nb_name, nb_cells in NOTEBOOKS.items():
        build(nb_name, nb_cells)
    if "--execute" in sys.argv:
        # each notebook re-execs the builder in a FRESH interpreter: the
        # SETUP cell must set XLA_FLAGS/JAX_PLATFORMS before jax
        # initializes, which a shared process could only do once
        import subprocess

        selected = [a for a in sys.argv[1:] if a != "--execute"]
        unknown = [a for a in selected if a not in NOTEBOOKS]
        if unknown:
            raise SystemExit(
                f"unknown notebook(s) {unknown}; choose from "
                f"{sorted(NOTEBOOKS)}"
            )
        for nb_name in selected or NOTEBOOKS:
            subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--_execute_one", nb_name],
                check=True,
            )
    elif "--_execute_one" in sys.argv:
        execute(sys.argv[sys.argv.index("--_execute_one") + 1])
