"""``kv_cache_share.*`` on the hand-made trace of ``test_program_trace.py``
(a prefill's ``dynamic_update_slice`` and a chain's ``scatter`` under the
scope ``kv_cache``: 20 % of the busy time), and where there is nothing to
read."""

import pytest

from benchmark.tests.test_program_trace import SPEC, T, bundle_for, read

METRICS = ["kv_cache_share.latency", "kv_cache_share.serve"]


@pytest.mark.parametrize("metric", METRICS)
def test_share_under_the_scope(metric, tmp_path, monkeypatch):
    b = bundle_for(tmp_path, monkeypatch, "program_trace.txt", (T(100), T(1400)))
    assert read(metric, b) == pytest.approx(20.0)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_gives_none(metric, tmp_path, monkeypatch):
    """No trace, and PR 26's recorded trace (no scope path on any
    operation): the line leaves the metric out."""
    assert read(metric, {"trace": None}) is None
    b = bundle_for(tmp_path, monkeypatch, "recorded_trace.txt")
    assert read(metric, b) is None


@pytest.mark.parametrize("metric", METRICS)
def test_declared_beside_the_scan_share(metric):
    """Same layer, same cells and same end-to-end metric as the
    ``layer_scan_share`` of its suffix: the two split the copies round a
    layer between ``lax.scan``'s own and the program's."""
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    twin = declared[metric.replace("kv_cache", "layer_scan")]
    assert {k: v for k, v in declared[metric].items() if k != "name"} == \
        {k: v for k, v in twin.items() if k != "name"}
