"""graftcheck: the rule engine that machine-checks CLAUDE.md's hard rules.

Each rule gets a known-bad fixture asserting it fires at the right
location and a clean twin asserting silence — including the
default-argument import-purity case the runtime subprocess guard
(test_import_purity.py) structurally cannot catch. Plus: suppression
comments (reason mandatory), the CLI contract, and the tier-1 repo sweep
— ``pytest tests/ -q`` fails on any new unsuppressed finding anywhere in
the package, scripts, or examples.

No jax needed anywhere here: the analysis package is pure stdlib, and
``test_analysis_cli_imports_no_jax`` pins that property in a subprocess.
"""

import json
import subprocess
import sys
import textwrap
import time
from pathlib import Path

from pytorch_distributed_training_tutorials_tpu.analysis import analyze_file, analyze_paths, all_rules
from pytorch_distributed_training_tutorials_tpu.analysis.cli import main as cli_main
from pytorch_distributed_training_tutorials_tpu.analysis.engine import Config

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "pytorch_distributed_training_tutorials_tpu"
SWEEP_PATHS = [PKG, REPO / "scripts", REPO / "examples"]


def check(src: str, path: str = "fixture/mod.py", config: Config | None = None):
    """Run all rules over a source string under a synthetic path."""
    return analyze_file(Path(path), config=config, source=textwrap.dedent(src))


def hits(findings, rule: str):
    return [f for f in findings if f.rule == rule and not f.suppressed]


# ---------------------------------------------------------------- import-purity

BAD_PURITY = """
    import jax
    import jax.numpy as jnp

    NEG_INF = jnp.float32(-1e30)

    def f(x, pad=jnp.zeros((3,))):
        return x + pad

    class C:
        scale = jnp.ones(())
"""


def test_import_purity_fires_on_module_constant():
    found = hits(check(BAD_PURITY), "import-purity")
    assert any(f.line == 5 and "module-level" in f.message for f in found)


def test_import_purity_fires_on_default_argument():
    # THE case the runtime subprocess guard cannot catch: the default
    # evaluates at `def` time, long before anything calls f.
    found = hits(check(BAD_PURITY), "import-purity")
    assert any(f.line == 7 and "default-argument" in f.message for f in found)


def test_import_purity_fires_on_class_attribute():
    found = hits(check(BAD_PURITY), "import-purity")
    assert any(f.line == 11 and "class-attribute" in f.message for f in found)


def test_import_purity_clean_twin_is_silent():
    clean = """
        import functools
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        SPEC = PartitionSpec("data")          # metadata: no backend touch

        @jax.jit
        def f(x, dtype=jnp.float32):          # attribute ref, not a call
            return jnp.zeros_like(x, dtype)   # call-time: fine

        g = jax.jit(lambda x: x * 2)          # transform constructor: fine

        if __name__ == "__main__":
            print(f(jnp.ones((2,))))          # entry point: fine
    """
    assert not hits(check(clean), "import-purity")


def test_import_purity_fires_on_backend_probe():
    found = hits(check("import jax\nN = jax.device_count()\n"),
                 "import-purity")
    assert len(found) == 1 and found[0].line == 2


# ---------------------------------------------------------- traced-control-flow

def test_traced_control_flow_fires_per_construct():
    src = """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                pass
            while x:
                pass
            for v in x:
                pass
            y = float(x)
            z = x.item()
            return x
    """
    found = hits(check(src), "traced-control-flow")
    assert [f.line for f in found] == [6, 8, 10, 12, 13]


def test_traced_control_flow_honors_static_argnums_and_argnames():
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,),
                           static_argnames=("mode",))
        def f(x, flag, *, mode="a"):
            if flag:
                pass
            if mode == "a":
                pass
            return x
    """
    assert not hits(check(src), "traced-control-flow")


def test_traced_control_flow_sees_call_site_wrapping():
    src = """
        import jax

        def step(state, batch):
            if batch:
                pass
            return state

        step_jit = jax.jit(step, donate_argnums=0)
    """
    found = hits(check(src), "traced-control-flow")
    assert len(found) == 1 and found[0].line == 5


def test_traced_control_flow_sees_nested_scan_body():
    src = """
        import jax

        @jax.jit
        def f(xs):
            def body(carry, x):
                if x > 0:
                    pass
                return carry, x
            return jax.lax.scan(body, 0.0, xs)
    """
    found = hits(check(src), "traced-control-flow")
    assert len(found) == 1 and found[0].line == 7


def test_traced_control_flow_clean_twin_is_silent():
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, mask=None):
            if mask is None:                  # identity: trace-time python
                mask = jnp.ones_like(x)
            if x.shape[0] > 1:                # shapes are static
                pass
            if len(x) > 1:                    # len is static
                pass
            return jax.lax.cond(x.sum() > 0, lambda v: v, lambda v: -v, x)
    """
    assert not hits(check(src), "traced-control-flow")


def test_traced_control_flow_skips_unresolvable_statics():
    # A non-literal static spec: skipping beats guessing wrong.
    src = """
        import functools
        import jax

        STATICS = (1,)

        @functools.partial(jax.jit, static_argnums=STATICS)
        def f(x, flag):
            if flag:
                pass
            return x
    """
    assert not hits(check(src), "traced-control-flow")


def test_traced_control_flow_sees_nn_remat_class_with_statics():
    # The models/transformer.py idiom: argnums count self as 0.
    src = """
        import flax.linen as nn

        class Block(nn.Module):
            def __call__(self, x, decode, prefill):
                if decode:
                    pass
                if prefill:
                    pass
                if x.sum() > 0:
                    pass
                return x

        Wrapped = nn.remat(Block, static_argnums=(2, 3))
    """
    found = hits(check(src), "traced-control-flow")
    assert [f.line for f in found] == [10]  # only the `if x.sum() > 0`


def test_traced_control_flow_catches_python_branch_on_accepted_length():
    """The speculative-decoding foot-gun (ISSUE 7): the accepted length
    coming out of the verify step is DATA; branching on it in Python
    inside the jitted chain is exactly the bug class traced-control-flow
    exists for — and its jnp.where/cumprod twin (the shape the engine's
    _spec_chain_fn actually uses) must stay silent."""
    src = """
        import jax

        @jax.jit
        def chain(state, n_accept):
            if n_accept > 0:            # accepted length is data!
                state = state + n_accept
            return state
    """
    found = hits(check(src), "traced-control-flow")
    assert len(found) == 1 and found[0].line == 6

    clean = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def chain(state, draft, out):
            ok = draft == out           # verify comparison stays on device
            acc = jnp.cumprod(ok.astype(jnp.int32), axis=-1)
            n_accept = acc.sum(-1)      # accepted length as DATA
            return jnp.where(n_accept > 0, state + n_accept, state)
    """
    assert not hits(check(clean), "traced-control-flow")


def test_traced_control_flow_catches_python_branch_on_adapter_id():
    """The multi-tenant foot-gun (ISSUE 8): a slot's LoRA adapter id is
    DATA inside the compiled decode chain — a Python branch selecting
    per-tenant factors would force one compile per tenant mix (or just
    crash on the tracer). The jnp.take gather twin (what
    adapters.bank.apply_lora actually does) must stay silent."""
    src = """
        import jax

        @jax.jit
        def forward(x, factors, adapter_id):
            if adapter_id > 0:          # per-slot adapter id is data!
                x = x @ factors[1]
            return x
    """
    found = hits(check(src), "traced-control-flow")
    assert len(found) == 1 and found[0].line == 6

    clean = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def forward(x, a, b, adapter_ids):
            ai = jnp.take(a, adapter_ids, axis=0)   # gather, not branch
            bi = jnp.take(b, adapter_ids, axis=0)
            return x + jnp.einsum("bsr,bro->bso",
                                  jnp.einsum("bsd,bdr->bsr", x, ai), bi)
    """
    assert not hits(check(clean), "traced-control-flow")


def test_traced_control_flow_catches_python_branch_on_finite_flag():
    """The robustness foot-gun (ISSUE 9): the per-slot finite-logits flag
    and the skip-step ok flag are DATA computed inside compiled code — a
    Python branch on either (quarantine decision, update-vs-skip) would
    crash on the tracer or force a recompile per outcome. The jnp.where
    twins (what serve/engine.py's guard and trainer.py's _apply_update
    actually do) must stay silent."""
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def update(state, grads, loss):
            if jnp.isfinite(loss):      # the finite flag is data!
                state = state + grads
            return state
    """
    found = hits(check(src), "traced-control-flow")
    assert len(found) == 1 and found[0].line == 7

    clean = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def update(state, grads, loss):
            ok = jnp.isfinite(loss)
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(grads)))
            new = state + grads
            return jnp.where(ok, new, state)   # select, not branch

        @jax.jit
        def chain_guard(logits):
            # the quarantine flag rides the scan output, never a branch
            return jnp.all(jnp.isfinite(logits), axis=-1)
    """
    assert not hits(check(clean), "traced-control-flow")


def test_traced_control_flow_catches_python_branch_on_page_table():
    """The paged-KV foot-gun (ISSUE 13): a slot's page-table entries are
    DATA inside the compiled decode chain (they select which pool pages
    the slot reads) — a Python branch on one would crash on the tracer
    or compile per table content. The jnp.take gather twin (what
    models/transformer.py's paged decode read actually does) must stay
    silent."""
    src = """
        import jax

        @jax.jit
        def read_cache(pool, page_table, step):
            if page_table[step] >= 0:   # the page id is data!
                return pool[page_table[step]]
            return pool[0]
    """
    found = hits(check(src), "traced-control-flow")
    assert len(found) == 1 and found[0].line == 6

    clean = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def read_cache(pool, page_table):
            # gather pages by traced table entry; sentinel ids fall in
            # mode="fill" zeros, masked by the validity row downstream
            pages = jnp.take(pool, page_table, axis=0, mode="fill",
                             fill_value=0)
            return pages.reshape((-1,) + pool.shape[2:])
    """
    assert not hits(check(clean), "traced-control-flow")


def test_traced_control_flow_catches_branch_on_kernel_selector():
    """The fused-kernel foot-gun (ISSUE 17): kernel-vs-gather dispatch
    must be ENGINE-static — a Python branch on a traced value (e.g. the
    slot's cache_index deciding "deep enough for the kernel") fires,
    while the sanctioned idiom (branching on a config bool, trace-time
    structure like models/transformer.py's ``cfg.paged_kernel``) stays
    silent."""
    src = """
        import jax

        @jax.jit
        def attend(q, pool, table, cache_index):
            if cache_index.max() > 128:   # depth is data!
                return paged_attention(q, pool, table, cache_index)
            return gather_attention(q, pool, table, cache_index)
    """
    found = hits(check(src), "traced-control-flow")
    assert len(found) == 1 and found[0].line == 6

    clean = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def attend(q, pool, table, cache_index, cfg=None):
            # engine-static dispatch: the flag is trace-time structure
            # (a static config bool), so each config compiles ONE read
            # path — selection between prebuilt programs stays legal
            if cfg.paged_kernel:
                return paged_attention(q, pool, table, cache_index)
            return gather_attention(q, pool, table, cache_index)
    """
    assert not hits(check(clean), "traced-control-flow")


# -------------------------------------------------------------- host-sync-hazard

def test_host_sync_fires_inside_jit():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            a = np.asarray(x)
            b = jax.device_get(x)
            x.block_until_ready()
            return x
    """
    found = hits(check(src), "host-sync-hazard")
    assert [f.line for f in found] == [7, 8, 9]


def test_host_sync_pipelined_chain_fetch_contract():
    """The ISSUE 11 foot-gun pair: fetching a chain result INSIDE the
    compiled chain (peeking at logits mid-trace) fires host-sync-hazard
    — it would force a device sync per launch and defeat the pipeline —
    while the double-buffered engine idiom (dispatch chain i+1, THEN
    ``jax.device_get`` chain i's retained output, both at host level)
    stays silent."""
    bad = """
        import jax

        @jax.jit
        def chain(state):
            out = state + 1
            peek = jax.device_get(out)      # fetch inside the chain!
            return out, peek
    """
    found = hits(check(bad), "host-sync-hazard")
    assert [f.line for f in found] == [7]

    clean = """
        import jax

        @jax.jit
        def chain(state):
            return state + 1, state * 2

        def pump(state, inflight, depth):
            # dispatch chain i+1 BEFORE fetching chain i — the fetch of
            # an in-flight result happens outside any traced body
            state, out = chain(state)
            inflight.append(out)
            if len(inflight) > depth - 1:
                return state, jax.device_get(inflight.pop(0))
            return state, None
    """
    assert not hits(check(clean), "host-sync-hazard")


def test_host_sync_per_shard_fetch_loop():
    """The ISSUE 15 foot-gun pair: collecting a sharded chain result by
    looping ``jax.device_get`` over shards inside the traced body fires
    host-sync-hazard (one sync per shard per launch — the per-LAUNCH
    floor sharded serving must not multiply by tp), while the engine's
    idiom — ONE batched ``jax.device_get`` of the replicated token
    block at host level, sharded cache leaves never fetched — stays
    silent."""
    bad = """
        import jax

        @jax.jit
        def collect(state, shards):
            outs = []
            for s in shards:             # one host sync PER SHARD
                outs.append(jax.device_get(s))
            return state, outs
    """
    found = hits(check(bad), "host-sync-hazard")
    assert [f.line for f in found] == [8]

    clean = """
        import jax

        @jax.jit
        def chain(state):
            return state, state * 2

        def collect(state):
            # the sharded engine fetches ONCE, at host level, and only
            # the replicated token block — never the head-sharded cache
            state, out = chain(state)
            return state, jax.device_get(out)
    """
    assert not hits(check(clean), "host-sync-hazard")


def test_host_sync_silent_outside_jit():
    src = """
        import time
        import jax
        import numpy as np

        def timed_leg(fn, x):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))      # the harness idiom: deliberate
            host = np.asarray(jax.device_get(x))
            return time.perf_counter() - t0, host
    """
    assert not hits(check(src), "host-sync-hazard")


# ------------------------------------------------------------ strategy-interface

def test_strategy_interface_fires_on_partial_contract():
    src = """
        class HalfStrategy:
            def shard_batch(self, b):
                return b

            def shard_state(self, s):
                return s
    """
    found = hits(check(src, path="pkg/parallel/bad.py"), "strategy-interface")
    assert len(found) == 1
    f = found[0]
    assert "HalfStrategy" in f.message
    assert "variable_shardings" in f.message and "num_devices" in f.message


def test_strategy_interface_full_contract_and_inheritance_silent():
    src = """
        class Full:
            @property
            def num_devices(self):
                return 1

            def variable_shardings(self, v):
                return v

            def shard_state(self, s):
                return s

            def shard_batch(self, b):
                return b

        class Hybrid(Full):                   # inherits the rest
            def shard_batch(self, b):
                return b

        class NotAStrategy:                   # none of the contract: out of scope
            def helper(self):
                pass
    """
    assert not hits(check(src, path="pkg/parallel/ok.py"), "strategy-interface")


def test_strategy_interface_scoped_to_parallel_dirs():
    src = """
        class Partial:
            def shard_batch(self, b):
                return b
    """
    assert not hits(check(src, path="pkg/models/whatever.py"),
                    "strategy-interface")


# ------------------------------------------------------------ reference-citation

def _ref_config(tmp_path: Path) -> Config:
    root = tmp_path / "reference"
    root.mkdir(exist_ok=True)
    (root / "ddp_gpus.py").write_text("\n".join(f"l{i}" for i in range(1, 51)))
    return Config(reference_root=root, repo_root=tmp_path / "norepo")


def test_reference_citation_fires_past_eof(tmp_path):
    src = '''
        """Twin of ddp_gpus.py:400 (past the end)."""
    '''
    found = hits(check(src, config=_ref_config(tmp_path)), "reference-citation")
    assert len(found) == 1 and "past the end" in found[0].message


def test_reference_citation_resolving_citation_silent(tmp_path):
    src = '''
        """Twin of ddp_gpus.py:50 (the last line) and ddp_gpus.py:1."""
    '''
    assert not hits(check(src, config=_ref_config(tmp_path)),
                    "reference-citation")


def test_reference_citation_fires_on_missing_file(tmp_path):
    src = '''
        """Twin of nonexistent_lesson.py:3."""
    '''
    found = hits(check(src, config=_ref_config(tmp_path)), "reference-citation")
    assert len(found) == 1 and "not found" in found[0].message


def test_reference_citation_malformed_fires_without_reference_tree(tmp_path):
    src = '''
        """See ddp_gpus.py:somewhere for details."""
    '''
    cfg = Config(reference_root=tmp_path / "absent", repo_root=tmp_path)
    found = hits(check(src, config=cfg), "reference-citation")
    assert len(found) == 1 and "malformed" in found[0].message


def test_reference_citation_absent_tree_skips_resolution(tmp_path):
    src = '''
        """Twin of ddp_gpus.py:400 — unresolvable without the tree."""
    '''
    cfg = Config(reference_root=tmp_path / "absent", repo_root=tmp_path)
    assert not hits(check(src, config=cfg), "reference-citation")


def test_reference_citation_pytest_node_ids_are_not_citations(tmp_path):
    src = '''
        """Pinned by tests/test_gpipe.py::test_dispatch_count."""
    '''
    cfg = Config(reference_root=tmp_path / "absent", repo_root=tmp_path)
    assert not hits(check(src, config=cfg), "reference-citation")


# ------------------------------------------------------------------ naive-timing

def test_naive_timing_fires_on_unfetched_region():
    # the async mirage: times the enqueue, not the work
    src = """
        import time
        import jax

        def leg(fn, x):
            t0 = time.perf_counter()
            fn(x)
            dt = time.perf_counter() - t0
            return dt
    """
    found = hits(check(src), "naive-timing")
    assert len(found) == 1 and found[0].line == 8
    assert "no device fetch" in found[0].message


def test_naive_timing_clean_when_region_closes_with_a_fetch():
    src = """
        import time
        import jax

        def leg_blocked(fn, x):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            return time.perf_counter() - t0

        def leg_float(fn, x):
            t0 = time.time()
            out = fn(x)
            loss = float(out[-1])
            return time.time() - t0, loss
    """
    assert not hits(check(src), "naive-timing")


def test_naive_timing_resolves_same_file_fetching_helpers():
    # the bench.py idiom: the fetch lives in a local helper the timed
    # region calls
    src = """
        import time
        import jax

        def run_and_fetch(fn, x):
            out = fn(x)
            return float(out)

        def leg(fn, x):
            t0 = time.perf_counter()
            run_and_fetch(fn, x)
            return time.perf_counter() - t0
    """
    assert not hits(check(src), "naive-timing")


def test_naive_timing_skips_files_without_jax():
    # no jax import, no async dispatch: plain wall-clock code is fine
    src = """
        import time

        def leg(fn, x):
            t0 = time.perf_counter()
            fn(x)
            return time.perf_counter() - t0
    """
    assert not hits(check(src), "naive-timing")


def test_naive_timing_exempts_the_jax_free_flight_recorder():
    """The flight recorder (ISSUE 10) timestamps every event with
    perf_counter and never fetches — correct, because it is jax-free by
    contract (host bookkeeping, not measurement of device work). The
    rule's jax-import gate is what makes that legal: the REAL module
    source must sweep clean under its real path."""
    flight_py = PKG / "obs" / "flight.py"
    findings = analyze_file(flight_py)
    assert not hits(findings, "naive-timing")
    assert "import jax" not in flight_py.read_text()


def test_naive_timing_fires_if_recorder_style_timing_moves_into_jax_code():
    # the counter-fixture: the same timestamping idiom inside an
    # engine-like jax-importing file IS the async mirage and must fire
    src = """
        import time
        import jax

        class Recorder:
            def chain_end(self, dt):
                self.samples.append(dt)

        def run_chain(chain, state, rec):
            t0 = time.perf_counter()
            chain(state)
            rec.chain_end(time.perf_counter() - t0)
    """
    found = hits(check(src), "naive-timing")
    assert len(found) == 1
    assert "no device fetch" in found[0].message


def test_naive_timing_skips_callless_calibration_regions():
    src = """
        import time
        import jax

        def timer_overhead():
            t0 = time.perf_counter()
            return time.perf_counter() - t0
    """
    assert not hits(check(src), "naive-timing")


# ----------------------------------------------------------------- suppressions

SUPPRESSED = """
    import jax.numpy as jnp

    A = jnp.zeros((2,))  # graftcheck: disable=import-purity -- fixture constant, module never imported by workers
"""


def test_suppression_with_reason_suppresses():
    findings = check(SUPPRESSED)
    assert not hits(findings, "import-purity")
    sup = [f for f in findings if f.suppressed]
    assert len(sup) == 1
    assert "never imported by workers" in sup[0].suppress_reason


def test_suppression_without_reason_is_itself_a_finding():
    src = """
        import jax.numpy as jnp

        A = jnp.zeros((2,))  # graftcheck: disable=import-purity
    """
    findings = check(src)
    assert hits(findings, "import-purity"), "reasonless must not suppress"
    assert hits(findings, "bad-suppression")


def test_suppression_unknown_rule_is_flagged_and_inert():
    src = """
        import jax.numpy as jnp

        A = jnp.zeros((2,))  # graftcheck: disable=not-a-rule -- whatever
    """
    findings = check(src)
    assert hits(findings, "import-purity")
    assert hits(findings, "bad-suppression")


def test_standalone_suppression_covers_next_code_line():
    src = """
        import jax.numpy as jnp

        # graftcheck: disable=import-purity -- fixture constant for the test below
        A = jnp.zeros((2,))
    """
    assert not hits(check(src), "import-purity")


def test_suppression_marker_inside_string_is_inert():
    src = """
        import jax.numpy as jnp

        MSG = "# graftcheck: disable=import-purity -- not a comment"
        A = jnp.zeros((2,))
    """
    assert hits(check(src), "import-purity")


def test_suppression_only_silences_named_rule():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            if x > 0:  # graftcheck: disable=host-sync-hazard -- wrong rule named
                pass
            return x
    """
    assert hits(check(src), "traced-control-flow")


# ----------------------------------------------------------------- engine / CLI

def test_parse_error_is_reported_not_raised(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = analyze_file(bad)
    assert [f.rule for f in findings] == ["parse-error"]


def test_cli_exit_codes_and_json(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\nA = jnp.zeros((2,))\n")
    clean = tmp_path / "clean.py"
    clean.write_text("import jax.numpy as jnp\n\ndef f(x):\n    return jnp.sum(x)\n")

    assert cli_main([str(clean)]) == 0
    assert cli_main([str(bad)]) == 1
    assert cli_main([str(bad), "--select", "traced-control-flow"]) == 0
    assert cli_main(["--select", "no-such-rule", str(bad)]) == 2
    assert cli_main([str(tmp_path / "missing_dir_or_file.py")]) == 2
    capsys.readouterr()

    assert cli_main([str(bad), "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["unsuppressed"] == 1
    assert report["findings"][0]["rule"] == "import-purity"
    assert report["findings"][0]["line"] == 2

    assert cli_main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    for rid in all_rules():
        assert rid in listing


# ----------------------------------------------------------------- jax-free-host

def _host_pkg(tmp_path, helper_src: str):
    """A tmp package: pkg/sub/hostmod.py -> pkg/sub/helper.py -> ???"""
    root = tmp_path / "pkg"
    (root / "sub").mkdir(parents=True)
    (root / "__init__.py").write_text("import importlib\n")
    (root / "sub" / "__init__.py").write_text("import importlib\n")
    (root / "sub" / "hostmod.py").write_text("from pkg.sub import helper\n")
    (root / "sub" / "helper.py").write_text(helper_src)
    return root


HOST_CFG = Config(host_only_modules=("pkg.sub.hostmod",),
                  forbidden_import_roots=("jax", "flax"))


def test_jax_free_host_fires_on_transitive_import(tmp_path):
    """THE case no single-file rule can see: hostmod.py itself never
    mentions jax — the violation is two hops down the import graph."""
    root = _host_pkg(tmp_path, "from pkg.sub import deep\n")
    (root / "sub" / "deep.py").write_text("import os\nimport jax\n")
    findings, _ = analyze_paths([root], config=HOST_CFG)
    found = hits(findings, "jax-free-host")
    assert len(found) == 1
    f = found[0]
    assert f.path.endswith("hostmod.py") and f.line == 1
    assert "pkg.sub.hostmod -> pkg.sub.helper -> pkg.sub.deep -> jax" \
        in f.message


def test_jax_free_host_clean_chain_is_silent(tmp_path):
    root = _host_pkg(tmp_path, "import os\nimport collections\n")
    findings, _ = analyze_paths([root], config=HOST_CFG)
    assert not hits(findings, "jax-free-host")


def test_jax_free_host_function_local_import_is_the_sanctioned_pattern(
        tmp_path):
    # lazy import inside a function never runs at import time — the
    # runtime subprocess pin agrees (it only observes import-time effects)
    root = _host_pkg(
        tmp_path,
        "def heavy():\n    import jax\n    return jax\n",
    )
    findings, _ = analyze_paths([root], config=HOST_CFG)
    assert not hits(findings, "jax-free-host")


def test_jax_free_host_undeclared_module_may_import_jax(tmp_path):
    root = _host_pkg(tmp_path, "import jax\n")
    cfg = Config(host_only_modules=("pkg.sub.other",),
                 forbidden_import_roots=("jax",))
    findings, _ = analyze_paths([root], config=cfg)
    assert not hits(findings, "jax-free-host")


def test_jax_free_host_direct_import_fires_in_single_file_analysis():
    # the degenerate one-file sweep still catches a DIRECT violation
    cfg = Config(host_only_modules=("hostmod",),
                 forbidden_import_roots=("jax",))
    found = hits(check("import os\nimport jax\n", path="fixture/hostmod.py",
                       config=cfg), "jax-free-host")
    assert len(found) == 1 and found[0].line == 2


def test_jax_free_host_suppressible_with_reason(tmp_path):
    root = _host_pkg(tmp_path, "import jax\n")
    (root / "sub" / "hostmod.py").write_text(
        "# graftcheck: disable=jax-free-host -- fixture: deliberately dirty\n"
        "from pkg.sub import helper\n"
    )
    findings, _ = analyze_paths([root], config=HOST_CFG)
    assert not hits(findings, "jax-free-host")
    assert any(f.rule == "jax-free-host" and f.suppressed for f in findings)


def test_host_only_declaration_matches_the_swept_tree():
    """Single-source assertion: every declared host-only module exists in
    the repo sweep's import graph, and the static rule + the runtime
    subprocess pin (test_prefix.py) read the SAME constant — the
    declaration cannot rot silently in either direction."""
    from pytorch_distributed_training_tutorials_tpu.analysis.engine import (
        SweepContext, _parse,
    )
    from pytorch_distributed_training_tutorials_tpu.analysis.hostonly import (
        FORBIDDEN_IMPORT_ROOTS, HOST_ONLY_MODULES,
    )

    assert Config().host_only_modules == HOST_ONLY_MODULES
    assert Config().forbidden_import_roots == FORBIDDEN_IMPORT_ROOTS

    cfg = Config()
    contexts = []
    for p in sorted(PKG.rglob("*.py")):
        got = _parse(p, p.read_text(encoding="utf-8"), cfg)
        if hasattr(got, "tree"):  # FileContext, not a parse-error Finding
            contexts.append(got)
    graph = SweepContext(contexts=contexts, config=cfg).modgraph
    known = {graph.module_of(c.path) for c in contexts}
    missing = set(HOST_ONLY_MODULES) - known
    assert not missing, f"declared host-only but not in tree: {missing}"


# ------------------------------------------------------------------ fetch-budget

def test_fetch_budget_fires_on_stray_sync_in_serve():
    src = """
        import jax
        import numpy as np

        def _sweep(self):
            flags = jax.device_get(self.flags)
            arr = np.asarray(self.block)
            n = self.count.item()
            jax.block_until_ready(self.state)
            return flags, arr, n
    """
    found = hits(check(src, path="serve/engine.py"), "fetch-budget")
    assert [f.line for f in found] == [6, 7, 8, 9]
    assert "chains + prefills + splices" in found[0].message


def test_fetch_budget_budgeted_sites_are_clean():
    # the budgeted-vs-stray pair: the SAME calls inside the budget's
    # enclosing functions (incl. nested helpers) are the contract itself
    src = """
        import jax

        def _collect_chain(self):
            block = jax.device_get(self.block)
            def distribute(rows):
                return jax.device_get(rows)
            return distribute(block)

        def _refill(self, slot):
            return int(jax.device_get(self.first))

        def _refill_paged(self, slot):
            return int(jax.device_get(self.first))

        def _advance_one(self):
            return int(jax.device_get(self.tok))
    """
    assert not hits(check(src, path="serve/engine.py"), "fetch-budget")


def test_fetch_budget_only_applies_to_serve():
    src = """
        import jax

        def flush(self):
            return jax.device_get(self.losses)
    """
    assert not hits(check(src, path="obs/metrics.py"), "fetch-budget")


def test_fetch_budget_exempts_the_selftest_harness():
    # serve/__main__.py IS the measuring instrument: its reference
    # decodes and fetch-counting spies fetch deliberately
    src = """
        import jax

        def selftest():
            return jax.device_get(make_ref())
    """
    assert not hits(check(src, path="serve/__main__.py"), "fetch-budget")


def test_fetch_budget_sentry_wrapper_is_a_measuring_instrument():
    # ISSUE 19 fixture pair: `_sentry_fetch` is HOW every budgeted site
    # fetches (count + delegate — the production twin of the selftest
    # spies), so its body is exempt; the SAME sync in any other serve/
    # function still fires — the exemption never grows the budget.
    clean = """
        import jax

        def _sentry_fetch(self, x):
            if self._sentry is not None:
                self._sentry.budgeted_fetch()
            return jax.device_get(x)
    """
    assert not hits(check(clean, path="serve/engine.py"), "fetch-budget")
    stray = """
        import jax

        def _sentry_stats(self):
            return jax.device_get(self.counters)
    """
    found = hits(check(stray, path="serve/engine.py"), "fetch-budget")
    assert [f.line for f in found] == [5]


def test_fetch_budget_item_with_args_is_not_a_sync():
    # dict.item-style calls with arguments are not the jax .item() sync
    src = """
        import jax

        def lookup(self, k):
            return self.table.item(k)
    """
    assert not hits(check(src, path="serve/engine.py"), "fetch-budget")


def test_fetch_budget_suppressible_with_reason():
    src = """
        import jax

        def _probe(self):
            return jax.device_get(self.x)  # graftcheck: disable=fetch-budget -- debug probe, never in the request loop
    """
    findings = check(src, path="serve/engine.py")
    assert not hits(findings, "fetch-budget")
    assert any(f.rule == "fetch-budget" and f.suppressed for f in findings)


# ----------------------------------------------------------------- engine-static

def test_engine_static_fires_on_request_shape():
    src = """
        import jax.numpy as jnp

        def _refill(self, req):
            return jnp.zeros((req.max_new_tokens,))
    """
    found = hits(check(src, path="serve/engine.py"), "engine-static")
    assert len(found) == 1 and found[0].line == 5
    assert "shape" in found[0].message


def test_engine_static_fires_on_request_static_arg():
    src = """
        import jax

        class Engine:
            def __init__(self):
                self._splice = jax.jit(
                    self._splice_fn, static_argnames=("seg_len", "grow"))

            def _refill(self, req):
                return self._splice(req.prompt, seg_len=req.p_len)
    """
    found = hits(check(src, path="serve/engine.py"), "engine-static")
    assert len(found) == 1
    assert "'seg_len'" in found[0].message


def test_engine_static_fires_on_conditional_program_construction():
    src = """
        import jax

        def _handle(self, req):
            if req.p_len > 512:
                fn = jax.jit(lambda x: x * 2)
            else:
                fn = self._default
            return fn
    """
    found = hits(check(src, path="serve/engine.py"), "engine-static")
    assert len(found) == 1
    assert "built once at engine init" in found[0].message


def test_engine_static_fires_on_scheduler_popped_values():
    src = """
        import jax.numpy as jnp

        def _refill_slot(self, slot):
            item = self.scheduler.pop(self.free)
            return jnp.zeros((item.p_len,))
    """
    assert hits(check(src, path="serve/engine.py"), "engine-static")


def test_engine_static_bucketed_values_are_the_sanctioned_idiom():
    # the REAL engine's shape: bucket_len() quantizes the per-request
    # length into the bounded pow2 family (a call sanitizes), and a
    # comparison yields a two-valued bool (bounded compile family) —
    # both must stay silent, or the rule flags serve/engine.py itself
    src = """
        import jax
        import jax.numpy as jnp

        class Engine:
            def __init__(self):
                self._splice = jax.jit(
                    self._splice_fn, static_argnames=("seg_len", "grow"))

            def _refill(self, req):
                p_len = len(req.prompt)
                bucket = bucket_len(p_len, self.window)
                grow = self.prefix is not None and req.key not in self.prefix
                buf = jnp.zeros((bucket,))
                return self._splice(buf, seg_len=bucket, grow=grow)
    """
    assert not hits(check(src, path="serve/engine.py"), "engine-static")


def test_engine_static_host_branch_selecting_prebuilt_programs_is_fine():
    # branching ON request data to SELECT among prebuilt programs is the
    # sanctioned design (prefill-vs-splice dispatch); only construction
    # under the branch fires
    src = """
        import jax

        def _refill(self, req):
            if req.cached:
                out = self._splice(req.prompt)
            else:
                out = self._prefill(req.prompt)
            return out
    """
    assert not hits(check(src, path="serve/engine.py"), "engine-static")


def test_engine_static_only_applies_to_serve():
    src = """
        import jax.numpy as jnp

        def pad(req):
            return jnp.zeros((req.n,))
    """
    assert not hits(check(src, path="data/loader.py"), "engine-static")


def test_engine_static_suppressible_with_reason():
    src = """
        import jax.numpy as jnp

        def _refill(self, req):
            return jnp.zeros((req.n,))  # graftcheck: disable=engine-static -- fixture: bounded by admission check
    """
    findings = check(src, path="serve/engine.py")
    assert not hits(findings, "engine-static")
    assert any(f.rule == "engine-static" and f.suppressed for f in findings)


def test_engine_static_real_engine_is_clean():
    """The real serve/engine.py — with its seg_len=bucket static, grow
    BoolOp, and prefill-vs-splice dispatch — must sweep clean; any false
    positive here means the heuristic's sanitizers regressed."""
    findings = analyze_file(PKG / "serve" / "engine.py")
    assert not hits(findings, "engine-static")
    assert not hits(findings, "fetch-budget")


# ----------------------------------------------------------- unused-suppression

def test_unused_suppression_fires_on_stale_disable():
    src = """
        import time

        # graftcheck: disable=import-purity -- was needed before the fix
        x = 1
    """
    found = hits(check(src), "unused-suppression")
    assert len(found) == 1 and found[0].line == 4
    assert "matched no finding" in found[0].message


def test_unused_suppression_silent_when_the_disable_works():
    findings = check(SUPPRESSED)
    assert not hits(findings, "unused-suppression")


def test_unused_suppression_not_judged_under_rule_filtering():
    # a --rules-filtered run cannot tell stale from unexercised
    from pytorch_distributed_training_tutorials_tpu.analysis.registry import select_rules

    src = """
        import time

        # graftcheck: disable=import-purity -- judged only on full sweeps
        x = 1
    """
    rules = list(select_rules(["naive-timing"]))
    findings = analyze_file(Path("fixture/mod.py"), rules=rules,
                            source=textwrap.dedent(src))
    assert not hits(findings, "unused-suppression")


def test_unused_suppression_skips_engine_pseudo_rule_targets():
    # disable=parse-error etc. guard conditions no Rule ever "runs"
    src = """
        # graftcheck: disable=parse-error -- checked-in fixture marker
        x = 1
    """
    assert not hits(check(src), "unused-suppression")


def test_unused_suppression_reasonless_disable_is_bad_not_stale():
    src = """
        # graftcheck: disable=import-purity
        x = 1
    """
    findings = check(src)
    assert hits(findings, "bad-suppression")
    assert not hits(findings, "unused-suppression")


def test_unused_suppression_is_itself_suppressible():
    # the escape hatch: a disable kept deliberately (platform-specific
    # path the sweep machine never exercises)
    src = """
        import time

        # graftcheck: disable=import-purity,unused-suppression -- fires only on the TPU host
        x = 1
    """
    findings = check(src)
    assert not hits(findings, "unused-suppression")


# ----------------------------------------------------- CLI v2: envelope + --rules

def test_cli_rules_flag_and_versioned_envelope(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\nA = jnp.zeros((2,))\n")

    # --rules is the v2 spelling; --select keeps working (tested above)
    assert cli_main([str(bad), "--rules", "traced-control-flow"]) == 0
    capsys.readouterr()

    assert cli_main([str(bad), "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["schema"] == "graftcheck-report/v1"
    assert report["files"] == 1
    assert report["rule_counts"] == {"import-purity": 1}
    assert isinstance(report["elapsed_s"], float)
    assert set(report["rules"]) == set(all_rules())


def test_cli_rules_filter_reflected_in_envelope(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\nA = jnp.zeros((2,))\n")
    assert cli_main([str(bad), "--json", "--rules",
                     "import-purity,naive-timing"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["rules"] == ["import-purity", "naive-timing"]
    assert report["rule_counts"] == {"import-purity": 1}


# ------------------------------------------------------------- the tier-1 sweep

def test_repo_sweep_has_zero_unsuppressed_findings():
    """THE enforcement hook: any new hard-rule violation anywhere in the
    package, scripts, or examples fails the suite."""
    findings, n_files = analyze_paths(SWEEP_PATHS)
    bad = [f for f in findings if not f.suppressed]
    assert n_files > 60, f"sweep saw only {n_files} files — wrong cwd?"
    assert not bad, "unsuppressed graftcheck findings:\n" + "\n".join(
        f.render() for f in bad
    )


def test_every_suppression_in_tree_carries_a_reason():
    findings, _ = analyze_paths(SWEEP_PATHS)
    assert not [f for f in findings if f.rule == "bad-suppression"]


def test_analysis_cli_imports_no_jax_and_is_fast():
    """Acceptance pin: the CLI sweep imports no jax (nor numpy/flax) and
    finishes well under the 10 s budget."""
    code = (
        "import sys\n"
        "from pytorch_distributed_training_tutorials_tpu.analysis.cli import main\n"
        "rc = main([%r, %r, %r])\n"
        "heavy = [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib', 'numpy', 'flax', 'optax'))]\n"
        "assert rc == 0, 'sweep not clean: rc=%%d' %% rc\n"
        "assert not heavy, 'analysis imported: %%s' %% heavy\n"
        "print('NO_JAX_OK')\n"
    ) % tuple(str(p) for p in SWEEP_PATHS)
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
    )
    elapsed = time.monotonic() - t0
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NO_JAX_OK" in out.stdout
    assert elapsed < 10, f"sweep took {elapsed:.1f}s (budget: 10s)"
