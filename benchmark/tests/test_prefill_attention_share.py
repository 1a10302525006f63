"""``prefill_attention_share.*`` on the hand-made trace of
``test_program_trace.py`` with the prefill's ``dynamic_update_slice`` moved
under the scope ``prefill_attn`` (10 % of the busy time), and where there is
nothing to read: the recorded traces of the programs before ISSUE 37, which
have no such scope, as the parent commit's traced runs have none."""

import os

import pytest

from benchmark.tests import test_program_trace
from benchmark.tests.test_program_trace import SPEC, T, bundle_for, read

METRICS = ["prefill_attention_share.latency", "prefill_attention_share.serve"]


@pytest.fixture
def trace_with_the_scope(tmp_path, monkeypatch):
    """The hand-made trace with its prefill's ``kv_cache/dynamic_update_slice``
    renamed to the kernel's call under ``prefill_attn``."""
    with open(os.path.join(test_program_trace.HERE, "program_trace.txt")) as f:
        text = f.read()
    assert text.count("attn/kv_cache/dynamic_update_slice:") == 1
    (tmp_path / "texts").mkdir()
    (tmp_path / "texts" / "program_trace.txt").write_text(text.replace(
        "attn/kv_cache/dynamic_update_slice:",
        "attn/prefill_attn/flash_attention_fwd:"))
    monkeypatch.setattr(test_program_trace, "HERE", str(tmp_path / "texts"))


@pytest.mark.parametrize("metric", METRICS)
def test_share_under_the_scope(metric, tmp_path, monkeypatch, trace_with_the_scope):
    b = bundle_for(tmp_path, monkeypatch, "program_trace.txt", (T(100), T(1400)))
    assert read(metric, b) == pytest.approx(10.0)
    assert read(metric.replace("prefill_attention", "kv_cache"), b) == pytest.approx(10.0)
    assert read(metric.replace("prefill_attention", "decode_attention"), b) is None


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_gives_none(metric, tmp_path, monkeypatch):
    """No trace, a trace with no operation under the scope (the parent's
    program), and PR 26's recorded trace: the line leaves the metric out."""
    assert read(metric, {"trace": None}) is None
    for name in ("program_trace.txt", "recorded_trace.txt"):
        (tmp_path / name).mkdir()
        assert read(metric, bundle_for(tmp_path / name, monkeypatch, name)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_declared_beside_the_prefill_share(metric):
    """Same layer and end-to-end metric as the ``prefill_share`` of its
    suffix, lower is better, in the cells whose prefill runs ``Attention``
    (the openPangu cell's is ``LatentAttention``, the Phi cell's
    ``banded_attention``: no such scope there)."""
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    mine, twin = declared[metric], declared[metric.replace("_attention", "")]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert mine[key] == twin[key]
    assert set(mine["workloads"]) <= set(twin["workloads"])
    assert not any("openpangu" in w or "phi" in w for w in mine["workloads"])
