"""Where the benchmark touches the program, the half that is the same for
every block: the strategy, the ``Trainer``, the loader, the ``ServeEngine``
and its requests from a configuration's file. The other half is a block's
own ``benchmark/blocks/<block>/program.py``: the model and the mapping of
the benchmark's weights to its parameter tree. No other file imports the
program (``tests/test_blocks.py`` greps for it).

From the program come the system under test and nothing else: no weights,
no data, no metric arithmetic.
"""

from __future__ import annotations

import jax

PKG = "pytorch_distributed_training_tutorials_tpu"


def module(name: str = ""):
    """A module of the program, imported when first needed."""
    import importlib

    return importlib.import_module(PKG + ("." + name if name else ""))


def put(tree: dict, path: str, value) -> None:
    """``value`` at ``a/b/c`` of a tree of nested dicts."""
    *parents, last = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[last] = value


def check_same_structure(ours, theirs) -> None:
    """Fail loudly where the program's tree has another leaf, shape or
    type than the benchmark made."""
    a = {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(ours)}
    b = {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(theirs)}
    if a != b:
        diff = {k: (a.get(k), b.get(k)) for k in set(a) | set(b)
                if a.get(k) != b.get(k)}
        raise SystemExit(f"parameter trees differ (ours, program's): {diff}")


def strategy(name: str):
    return getattr(module("parallel"), name)(module().create_mesh())


def trainer(model, loader, config: dict, traffic: dict, strat, seed: int,
            on_step):
    import optax

    opts = config["train"]
    hyper = traffic["adamw"]
    adamw = module("ops.fused_optim").fused_adamw if opts["kernels"] else optax.adamw
    tx = adamw(
        hyper["learning_rate"], b1=hyper["b1"], b2=hyper["b2"],
        eps=hyper["eps"], weight_decay=hyper["weight_decay"],
    )
    return module("train").Trainer(
        model, loader, tx, strategy=strat,
        loss="fused_cross_entropy" if opts["kernels"] else "cross_entropy",
        seed=seed & 0x7FFFFFFF, quiet=True, on_step=on_step,
    )


def sharded_loader(arrays: tuple, batch: int, mesh, seed: int):
    data = module("data")
    return data.ShardedLoader(
        data.ArrayDataset(arrays), batch, mesh, batch_mode="global",
        seed=seed & 0x7FFFFFFF,
    )


def serve_engine(model, params, options: dict):
    return module("serve").ServeEngine(model, params, **options)


def request(prompt, max_new_tokens: int):
    return module("serve").Request(prompt=prompt, max_new_tokens=max_new_tokens)


def enable_compile_cache(path: str) -> None:
    """The benchmark's own fixed directory inside the checkout, whatever
    the machine's environment says (PR 25: the machine's directory gave
    back 11 of 17 programs)."""
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
