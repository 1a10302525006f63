"""``decode_attention_share.*`` on the hand-made trace of
``test_program_trace.py`` with the chain's ``scatter`` moved under the scope
``decode_attn`` (10 % of the busy time), and where there is nothing to read:
the recorded traces of the programs before ISSUE 31, which have no such
scope, as the parent commit's traced runs have none."""

import os

import pytest

from benchmark.tests import test_program_trace
from benchmark.tests.test_program_trace import SPEC, T, bundle_for, read

METRICS = ["decode_attention_share.latency", "decode_attention_share.serve"]


@pytest.fixture
def trace_with_the_scope(tmp_path, monkeypatch):
    """The hand-made trace with its chain's ``kv_cache/scatter`` renamed to a
    kernel call under ``decode_attn``, where ``bundle_for`` looks for it."""
    with open(os.path.join(test_program_trace.HERE, "program_trace.txt")) as f:
        text = f.read()
    assert text.count("attn/kv_cache/scatter:") == 1
    (tmp_path / "texts").mkdir()
    (tmp_path / "texts" / "program_trace.txt").write_text(text.replace(
        "attn/kv_cache/scatter:", "attn/decode_attn/pallas_call:"))
    monkeypatch.setattr(test_program_trace, "HERE", str(tmp_path / "texts"))


@pytest.mark.parametrize("metric", METRICS)
def test_share_under_the_scope(metric, tmp_path, monkeypatch, trace_with_the_scope):
    b = bundle_for(tmp_path, monkeypatch, "program_trace.txt", (T(100), T(1400)))
    assert read(metric, b) == pytest.approx(10.0)
    assert read(metric.replace("decode_attention", "kv_cache"), b) == pytest.approx(10.0)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_gives_none(metric, tmp_path, monkeypatch):
    """No trace, a trace with no operation under the scope (the parent's
    program), and PR 26's recorded trace: the line leaves the metric out."""
    assert read(metric, {"trace": None}) is None
    for name in ("program_trace.txt", "recorded_trace.txt"):
        (tmp_path / name).mkdir()
        assert read(metric, bundle_for(tmp_path / name, monkeypatch, name)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_declared_beside_the_cache_share(metric):
    """Same layer, same cells and same end-to-end metric as the
    ``kv_cache_share`` of its suffix: the kernel took over what that scope's
    read copy cost."""
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    twin = declared[metric.replace("decode_attention", "kv_cache")]
    assert {k: v for k, v in declared[metric].items() if k != "name"} == \
        {k: v for k, v in twin.items() if k != "name"}
