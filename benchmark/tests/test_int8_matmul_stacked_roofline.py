"""``int8_matmul_stacked_roofline.*`` on event lines recorded on the chip
(PR 33, the chat cell's traced run, seed 3300000101): the call under the
layer scan, whose first operand is the scalar-prefetched layer index and
whose weight is the stack viewed (L*k, n), at a decode step and at a
prefill bucket; and the calls it must leave to others: the head's (k, n)
call of the same run, the parent's (k, n) call under the scan (same seed,
the parent's program), ``decode_attention`` (an ``s32[1]`` first operand
too) and a grouped product (made up from PR 30's shapes)."""

import os

import pytest

from benchmark.lib import harness, xplane
from benchmark.tests.test_program_trace import SPEC

METRICS = ["int8_matmul_stacked_roofline.serve", "int8_matmul_stacked_roofline.latency"]
TAIL = ', custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
STACKED_DECODE = (
    "%int8_matmul.54 = f32[32,8192]{1,0:T(8,128)S(1)} custom-call(s32[1]{0:T(128)} "
    "%dynamic_slice.27, f32[32,2048]{1,0:T(8,128)S(1)} %multiply_bitcast_fusion.8, "
    "s8[49152,8192]{1,0:T(8,128)(4,1)} %bitcast.135, f32[24,1,8192]{2,1,0:T(1,128)} "
    "%get-tuple-element.1229)" + TAIL)
STACKED_PREFILL = (
    "%int8_matmul.27 = f32[1024,8192]{1,0:T(8,128)S(1)} custom-call(s32[1]{0:T(128)} "
    "%dynamic_slice.10, f32[1024,2048]{1,0:T(8,128)S(1)} %multiply_bitcast_fusion.5, "
    "s8[49152,8192]{1,0:T(8,128)(4,1)} %bitcast.129, f32[24,1,8192]{2,1,0:T(1,128)S(1)} "
    "%copy-done.4)" + TAIL)
HEAD = (
    "%int8_matmul.48 = f32[32,92544]{1,0:T(8,128)S(1)} custom-call(f32[32,2048]"
    "{1,0:T(8,128)S(1)} %multiply_bitcast_fusion.6, s8[2048,92544]{1,0:T(8,128)(4,1)} "
    "%get-tuple-element.1341, f32[1,92544]{1,0:T(1,128)} %get-tuple-element.1342)" + TAIL)
PARENT_LAYER = (
    "%int8_matmul.54 = f32[32,8192]{1,0:T(8,128)S(1)} custom-call(f32[32,2048]"
    "{1,0:T(8,128)S(1)} %multiply_bitcast_fusion.8, s8[2048,8192]{1,0:T(8,128)(4,1)S(1)} "
    "%dynamic-slice_bitcast_fusion.32, f32[1,8192]{1,0:T(1,128)S(1)} "
    "%dynamic-slice_bitcast_fusion.33)" + TAIL)
DECODE_ATTENTION = (
    "%decode_attention.6 = f32[32,16,128]{2,1,0:T(8,128)S(1)} custom-call(s32[1]{0:T(128)} "
    "%dynamic_slice.27, s32[32]{0:T(128)S(1)} %bitcast.147, s32[32]{0:T(128)S(1)} %gte.1146, "
    "s32[32]{0:T(128)S(1)} %gte.1147, f32[32,16,128]{2,1,0:T(8,128)S(1)} %fusion.5, "
    "bf16[24,32,2048,8,128]{4,3,2,1,0:T(8,128)(2,1)} %fusion.30, "
    "bf16[24,32,2048,8,128]{4,3,2,1,0:T(8,128)(2,1)} %fusion.31)" + TAIL)
GROUPED = (
    "%grouped_int8_matmul.3 = bf16[768,2048]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0:T(128)} "
    "%n_tiles, bf16[768,7680]{1,0:T(8,128)(2,1)} %rows, s8[16,7680,2048]{2,1,0:T(8,128)(4,1)} "
    "%q, f32[16,1,2048]{2,1,0:T(1,128)} %scale)" + TAIL)


def bundle(*lines_and_seconds):
    events, t = [], 1000
    for line, seconds in lines_and_seconds:
        events.append(xplane.Event(line, t, t + int(seconds * 1e9)))
        t = events[-1].end + 10
    cell = harness.Cell("serve-internlm2-1.8b-chat")
    return {
        "trace": xplane.Trace({"/device:TPU:0": events}, [], {}),
        "trace_window": (0, t), "busiest": "/device:TPU:0", "root": harness.ROOT,
        "peaks": cell.peaks["devices"]["TPU v5 lite"], "cell": cell,
    }


def read(metric, b):
    return harness.load_module(
        os.path.join(harness.BENCH, "layer_metrics", metric + ".py")).read(b)


@pytest.mark.parametrize("metric", METRICS)
def test_a_decode_call_is_bound_by_one_layers_bytes(metric):
    """61.1 us a call on the chip. One layer's 2048 x 8192 int8 bytes, its
    scales, x and the result over 819 GB/s, never the stack's 24 layers:
    33.9 %, and with the rest of the run's calls beside it the same."""
    byts = 32 * 2048 * 4 + 2048 * 8192 + 4 * 8192 + 32 * 8192 * 4
    want = 100 * byts / 819e9 / 61.1e-6
    assert 30 < want < 40
    assert read(metric, bundle((STACKED_DECODE, 61.1e-6))) == pytest.approx(want, rel=1e-3)
    others = [(HEAD, 713e-6), (PARENT_LAYER, 42.8e-6), (DECODE_ATTENTION, 30e-6),
              (GROUPED, 400e-6)]
    assert read(metric, bundle((STACKED_DECODE, 61.1e-6), *others)) == pytest.approx(
        want, rel=1e-3)


@pytest.mark.parametrize("metric", METRICS)
def test_a_prefill_call_is_bound_by_its_operations(metric):
    """A bucket of 1,024 rows, 342 us a call: 2 x 1024 x 2048 x 8192 int8
    operations over the chip's int8 peak; mixed with decode calls by time."""
    cell = harness.Cell("serve-internlm2-1.8b-chat")
    peak = cell.peaks["devices"]["TPU v5 lite"]["flops_per_s"]["int8"]
    ops_bound = 2 * 1024 * 2048 * 8192 / peak
    assert read(metric, bundle((STACKED_PREFILL, 342e-6))) == pytest.approx(
        100 * ops_bound / 342e-6, rel=1e-3)
    byts = 32 * 2048 * 4 + 2048 * 8192 + 4 * 8192 + 32 * 8192 * 4
    both = read(metric, bundle((STACKED_PREFILL, 342e-6), (STACKED_DECODE, 61.1e-6)))
    assert both == pytest.approx(
        100 * (ops_bound + byts / 819e9) / (342e-6 + 61.1e-6), rel=1e-3)
    assert both < 100


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_gives_none(metric):
    """No trace, and a trace with no call of the new form (the parent's
    program, the head alone): the line leaves the metric out. The old
    reader takes the (k, n) calls and none of the new form."""
    assert read(metric, {"trace": None}) is None
    b = bundle((HEAD, 713e-6), (PARENT_LAYER, 42.8e-6), (DECODE_ATTENTION, 30e-6),
               (GROUPED, 400e-6))
    assert read(metric, b) is None
    old = metric.replace("_stacked", "").replace(".serve", "")
    assert read(old, b) is not None
    assert read(old, bundle((STACKED_DECODE, 61.1e-6), (STACKED_PREFILL, 342e-6))) is None


@pytest.mark.parametrize("metric", METRICS)
def test_declared_beside_the_old_roofline(metric):
    """Same layer, source, unit and end-to-end metric as the
    ``int8_matmul_roofline*`` of its suffix, in the one cell that scans
    int8 layers and reports that end-to-end metric."""
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    twin = declared[metric.replace("_stacked", "").replace(".serve", "")]
    mine = declared[metric]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert mine[key] == twin[key], key
    assert set(mine["workloads"]) <= set(twin["workloads"])
    assert mine["workloads"] == [
        {"tpot_mean_ms": "serve-mistral-7b-long",
         "tpot_p95_ms": "serve-internlm2-1.8b-chat"}[mine["moves"]]]
