"""Traffic kind ``train_steps``: ``Trainer.train`` over a ``ShardedLoader``
under the configuration's strategy, whole epochs until ``--seconds`` has
passed.

Set-up builds one ``Trainer``, gives it the benchmark's weights, and drives
it through its first epoch by the window's own call; the same object then
runs the window. From that first epoch come the numbers ``correct``
compares with the block's plain reference
(``benchmark/blocks/<block>/reference.py``): the loss of steps 1 to 3, the
norm of each leaf's first gradient (Adam's first moment after one step,
over ``1 - b1``) and the norm of each leaf's change after three steps.
"""

from __future__ import annotations

import gc
import math
import statistics
import time

import numpy as np

from . import adamw, harness, program, traffic as traffic_lib, weights

PROOF_STEPS = 3


class TimedLoader:
    """The loader the Trainer iterates, with ``next()`` on the clock and
    the first batches kept for the reference."""

    def __init__(self, inner, counters: dict, keep: int):
        self._inner, self._counters, self._keep = inner, counters, keep
        self.first_batches: list = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __len__(self):
        return len(self._inner)

    def __iter__(self):
        it = iter(self._inner)
        while True:
            t0 = time.perf_counter()
            try:
                with harness.span("loader_next"):
                    batch = next(it)
            except StopIteration:
                return
            self._counters["input_wait_s"] += time.perf_counter() - t0
            self._counters["batches"] += 1
            if len(self.first_batches) < self._keep:
                self.first_batches.append(batch)
            yield batch


def _moment(opt_state):
    """Adam's first moment, wherever the optimizer keeps it."""
    import jax

    found = [
        s for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")
        ) if hasattr(s, "mu")
    ]
    if not found:
        raise SystemExit("the optimizer state holds no first moment 'mu'")
    return found[0].mu


def leaf_norms(tree: dict, prefix: str = "") -> dict:
    """Norm of every leaf of a reference-layout tree; the leaves of a
    group (a dict inside the tree: layers stacked on the leading axis) an
    index at a time: ``{"embed": (), "layers/wq": (L,), ...}``."""
    import jax.numpy as jnp

    out = {}
    for name, leaf in tree.items():
        if isinstance(leaf, dict):
            out.update(leaf_norms(leaf, prefix + name + "/"))
            continue
        x = leaf.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if prefix else None
        out[prefix + name] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


def flatten_norms(norms: dict) -> dict[str, float]:
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
    return out


def worst_gap(ours: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The worst leaf's gap between two norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(ours[k] - r) / max(r, med, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def spread(spec: dict):
    """Where the reference's leaves lie when one chip cannot hold them
    (None on one device): each leaf split over all devices along its
    longest axis that divides evenly."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    n = len(jax.devices())
    if n == 1:
        return None
    mesh = Mesh(jax.devices(), ("chips",))

    def place(leaf):
        dims = leaf[0]
        fit = [i for i, d in enumerate(dims) if d % n == 0 and d >= n]
        spec = [None] * len(dims)
        if fit:
            spec[max(fit, key=lambda i: dims[i])] = "chips"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree_util.tree_map(
        place, spec, is_leaf=lambda x: isinstance(x, tuple))


def reference_steps(block, shape, seed, std, batches, hyper,
                    precision="float32", rows=None, skip_update=False):
    """The block's reference through the first steps: (losses, first
    gradient's norms, norms of the change after the last step)."""
    import jax
    import jax.numpy as jnp

    spec = block.reference.leaf_shapes(shape)
    placement = spread(spec)
    params = weights.make(spec, seed, "float32", std, out_shardings=placement)
    kw = {} if placement is None else {"out_shardings": placement}
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t), **kw)
    mu, nu = zeros(params), zeros(params)
    losses, grad_norms = [], None
    norms = jax.jit(leaf_norms)
    hyp = tuple(hyper[k] for k in ("learning_rate", "b1", "b2", "eps", "weight_decay"))
    grad = block.reference.grad_fn(shape, precision, placement)
    for i, (tokens, targets) in enumerate(batches):
        loss, grads = grad(params, jnp.asarray(tokens), jnp.asarray(targets), rows)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = flatten_norms(jax.device_get(norms(grads)))
        if not skip_update:
            params, mu, nu = adamw.adamw(
                params, grads, mu, nu, jnp.asarray(i + 1, jnp.int32), hyp
            )
        del grads
    change = jax.jit(
        lambda p, key: leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, p, weights.build(spec, key, "float32", std)))
    )(params, weights.seed_key(seed))
    return losses, grad_norms, flatten_norms(jax.device_get(change))


def compare(checks, limits: dict, ours: dict, ref: tuple) -> dict:
    """Put every number compared beside its limit; returns the detail."""
    ref_losses, ref_grads, ref_change = ref
    detail = {"ref_losses": ref_losses, "losses": ours["losses"]}
    for i, (a, b) in enumerate(zip(ours["losses"], ref_losses), 1):
        name = f"loss_step{i}_rel"
        if name in limits:
            checks.at_most(name, abs(a - b) / abs(b), limits[name])
    g, g_at = worst_gap(ours["grad_norms"], ref_grads)
    detail["grad_worst_leaf"] = g_at
    if "grad_norm_gap" in limits:
        checks.at_most("grad_norm_gap", g, limits["grad_norm_gap"])
    # a leaf whose gradient is nought to rounding moves by round-off alone
    med = statistics.median(ref_grads.values())
    still = {k for k, v in ref_grads.items() if v < 1e-3 * med}
    c, c_at = worst_gap(ours["change_norms"], ref_change, skip=still)
    detail["change_worst_leaf"] = c_at
    detail["leaves_left_out"] = sorted(still)
    if "change_norm_gap" in limits:
        checks.at_most("change_norm_gap", c, limits["change_norm_gap"])
    detail["gaps"] = {"grad_norm_gap": g, "change_norm_gap": c}
    return detail


def run(cell, args, log, tracer, fault=None) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, mix, block = cell.config, cell.traffic, cell.block
    block.needs("train")
    shape = block.reference.Shape.from_config(cfg)
    spec = block.reference.leaf_shapes(shape)
    std = cfg["initializer_range"]
    hyper = mix["adamw"]
    counters = {"input_wait_s": 0.0, "batches": 0}

    strat = program.strategy(cfg["train"]["strategy"])
    model = block.program.model(cfg, "train", mix["seq_len"])
    arrays = traffic_lib.train_tokens(mix, shape.vocab_size, args.seed)
    loader = TimedLoader(
        program.sharded_loader(arrays, mix["batch"], strat.mesh, args.seed),
        counters, PROOF_STEPS,
    )

    proof: dict = {"losses": []}
    window_losses: list = []
    state = {"phase": "proof", "steps": 0}

    @jax.jit
    def grad_norms_of(opt_state):
        mu = block.program.from_program(_moment(opt_state))
        return leaf_norms(jax.tree_util.tree_map(
            lambda m: m / (1 - hyper["b1"]), mu))

    @jax.jit
    def change_norms_of(params, key):
        start = weights.build(spec, key, "float32", std)
        return leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, block.program.from_program(params), start))

    def on_step(step, loss):
        state["steps"] += 1
        if state["phase"] == "window":
            window_losses.append(loss)
            return
        if step <= PROOF_STEPS:
            proof["losses"].append(loss)
        if step == 1:
            proof["grad_norms"] = grad_norms_of(trainer.state.opt_state)
        if step == PROOF_STEPS:
            proof["change_norms"] = change_norms_of(
                trainer.state.params, weights.seed_key(args.seed))

    trainer = program.trainer(model, loader, cfg, mix, strat, args.seed, on_step)
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, trainer.state.params)
    params = weights.make(
        spec, args.seed, "float32", std,
        convert=lambda t: block.program.to_program(t, shape),
        out_shardings=shardings,
    )
    program.check_same_structure(params, trainer.state.params)
    trainer.state = trainer.state.replace(params=params)
    del params
    if fault is not None:
        fault(trainer)

    # the first epoch, by the window's own call: warms the step, and its
    # first three steps are what the reference follows
    trainer.train(1)
    jax.block_until_ready(trainer.state.params)
    ours = {
        "losses": [float(x) for x in proof["losses"]],
        "grad_norms": flatten_norms(jax.device_get(proof["grad_norms"])),
        "change_norms": flatten_norms(jax.device_get(proof["change_norms"])),
    }
    batches = [jax.device_get(b) for b in loader.first_batches]
    loader.first_batches.clear()
    counters.update(input_wait_s=0.0, batches=0)

    gc.collect()
    gc.freeze()  # what set-up left behind is never scanned inside the window
    setup = log.snapshot()
    state["phase"] = "window"
    steps_before = state["steps"]
    traced = {}
    epoch_s = []
    t0 = time.perf_counter()
    setup_s = t0 - args.t_process_start
    while True:
        was = tracer.state
        tracer.poll(time.perf_counter() - t0)
        if tracer.state != was:
            traced[tracer.state] = state["steps"]
        t_epoch = time.perf_counter()
        trainer.train(trainer.epoch + 1)
        epoch_s.append(time.perf_counter() - t_epoch)
        if time.perf_counter() - t0 >= args.seconds:
            break
    jax.block_until_ready(trainer.state.params)
    window_s = time.perf_counter() - t0
    if tracer.state == "tracing":
        traced["done"] = state["steps"]
    tracer.stop()
    after = log.snapshot()
    steps = state["steps"] - steps_before
    losses = np.asarray(jax.device_get(window_losses), np.float64)
    memory_peak = harness.peak_bytes()
    n_devices = strat.mesh.size

    # free the program's state before the reference takes the chip
    gc.unfreeze()
    trainer.on_step = None
    del trainer, loader, window_losses, proof, grad_norms_of, change_norms_of
    gc.collect()

    tokens_per_step = mix["batch"] * mix["seq_len"]
    return {
        "kind": "train_steps", "block": block, "shape": shape,
        "setup_s": setup_s,
        "window_s": window_s, "attempted": int(steps),
        "failed": int(np.sum(~np.isfinite(losses))),
        "end_to_end": {"train_tokens_per_s": steps * tokens_per_step / window_s},
        "counters": {
            **counters, "steps": int(steps), "tokens_per_step": tokens_per_step,
            "window_builds": after["programs"] - setup["programs"],
            "setup_compile": setup, "n_devices": n_devices,
            "traced_steps": traced.get("done", 0) - traced.get("tracing", 0),
            "last_loss": float(losses[-1]) if len(losses) else None,
            # where a run reads far off: one long stall, or every epoch slow
            "epoch_s": [min(epoch_s), statistics.median(epoch_s), max(epoch_s)],
        },
        "memory_peak_bytes": memory_peak,
        "proof": {"ours": ours, "batches": batches, "hyper": hyper, "std": std},
    }


def decide(cell, args, bundle: dict, checks) -> dict:
    """Run the reference over the first steps and put each number beside
    its limit."""
    proof = bundle["proof"]
    t0 = time.perf_counter()
    ref = reference_steps(
        bundle["block"], bundle["shape"], args.seed, proof["std"], proof["batches"],
        proof["hyper"],
    )
    detail = compare(checks, cell.limits["limits"], proof["ours"], ref)
    detail["reference_s"] = time.perf_counter() - t0
    return detail
