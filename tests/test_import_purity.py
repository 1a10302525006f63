"""Importing the package must NOT initialize the XLA backend.

Multi-process workers call jax.distributed.initialize() AFTER importing the
framework; any module-level jax computation (even `jnp.float32(-inf)`)
initializes the backend first and breaks every spawn/torchrun world with
"initialize() must be called before any JAX calls". Regression guard for
the round-2 ring-attention NEG_INF incident.

Two complementary guards:

- the runtime subprocess check (below): imports the package in a child and
  asserts no backend came up — ground truth for what import actually does;
- the static graftcheck `import-purity` rule over every file in the
  package: strictly stronger on coverage — it also sees default argument
  values, class attributes, and modules the import graph doesn't reach
  from the top-level import (anything the child process never executes).
"""

import os
import subprocess
import sys
from pathlib import Path

CHILD = """
import jax
jax.config.update("jax_platforms", "cpu")
from jax._src import xla_bridge
import pytorch_distributed_training_tutorials_tpu
import pytorch_distributed_training_tutorials_tpu.parallel
import pytorch_distributed_training_tutorials_tpu.models
import pytorch_distributed_training_tutorials_tpu.data
import pytorch_distributed_training_tutorials_tpu.train
import pytorch_distributed_training_tutorials_tpu.launch
import pytorch_distributed_training_tutorials_tpu.bench.harness
import pytorch_distributed_training_tutorials_tpu.utils.profiling
assert not xla_bridge._backends, (
    "package import initialized the XLA backend: %s" % xla_bridge._backends
)
print("IMPORT_PURE")
"""


def test_package_import_does_not_initialize_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", CHILD],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "IMPORT_PURE" in out.stdout


def test_static_import_purity_over_whole_package():
    """The static twin: every module (reached by the runtime import graph
    or not) is free of import-time jax computation — including default
    argument values and class attributes, which the subprocess guard only
    catches if the module is imported AND the def/class executes."""
    from pytorch_distributed_training_tutorials_tpu.analysis import all_rules, analyze_paths

    pkg = Path(__file__).resolve().parents[1] / "pytorch_distributed_training_tutorials_tpu"
    rule = all_rules()["import-purity"]
    findings, n_files = analyze_paths([pkg], rules=[rule])
    assert n_files > 50, f"only {n_files} files scanned — wrong path?"
    bad = [f for f in findings if not f.suppressed]
    assert not bad, "import-time jax computation:\n" + "\n".join(
        f.render() for f in bad
    )
