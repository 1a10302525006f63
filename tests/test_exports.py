"""Every name a lazily exporting package promises resolves.

Six packages re-export through a PEP 562 ``_LAZY_EXPORTS`` table (name ->
module, resolved on first ``getattr``), so a table can go on naming a
module that is gone and only a user finds out. The packages that import
eagerly (``models``, ``ops``, ``parallel``, ``data``, ``train``,
``launch``) fail at import and need no case.
"""

import importlib

import pytest

ROOT = "pytorch_distributed_training_tutorials_tpu"
LAZY_PACKAGES = ["", "adapters", "bench", "obs", "serve", "utils"]


@pytest.mark.parametrize("package", LAZY_PACKAGES, ids=lambda p: p or ROOT)
def test_every_exported_name_resolves(package):
    module = importlib.import_module(f"{ROOT}.{package}".rstrip("."))
    assert set(module._LAZY_EXPORTS) <= set(module.__all__)
    for name in module.__all__:
        assert getattr(module, name) is not None, name
