"""The reduction from a trace to busy time, idle gaps and sums."""

import os

import pytest

from benchmark.lib import xplane
from benchmark.lib.xplane import Event

HERE = os.path.dirname(__file__)


def ev(name, start, end):
    return Event(name, start, end)


OPS = [ev("fusion.1", 0, 10), ev("while.2", 5, 40), ev("custom-call.3", 8, 20),
       ev("fusion.1", 30, 40), ev("fusion.4", 60, 70)]


def test_busy_union_merges_overlaps_and_clips():
    assert xplane.busy_union(OPS, 0, 100) == [(0, 40), (60, 70)]
    assert xplane.busy_union(OPS, 35, 65) == [(35, 40), (60, 65)]
    assert xplane.busy_seconds(OPS, 0, 100) == 50 / 1e9


def test_gaps_longest_first():
    assert xplane.gaps(OPS, 0, 100) == [(70, 100), (40, 60)]


def test_gap_attribution_splits_over_spans():
    spans = [ev("bench:step", 35, 50), ev("bench:wait_arrival", 50, 58),
             ev("bench:step", 75, 90)]
    got = xplane.attribute_gaps(xplane.gaps(OPS, 0, 100), spans)
    assert got == pytest.approx({"step": 25e-9, "wait_arrival": 8e-9,
                                 "untracked": 17e-9})


def test_sums_leave_wrappers_out():
    got = xplane.sums_by(OPS, 0, 100, lambda e: e.name)
    assert "while.2" not in got
    assert got == pytest.approx({"fusion.1": 20e-9, "custom-call.3": 12e-9,
                                 "fusion.4": 10e-9})
    assert xplane.top(got, 1) == [["fusion.1", pytest.approx(20e-9)]]


UP = ('%up_proj.4 = f32[32,8192]{1,0:T(8,128)S(1)} custom-call(f32[32,2048]{1,0:T(8,128)S(1)} '
      '%convert_bitcast_fusion.20, s8[2048,8192]{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_bitcast_fusion.36, '
      'f32[1,8192]{1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.37), '
      'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[32,2048]{1,0}}')
FUSION = ('%fusion.286 = bf16[4,2048,2048]{1,2,0:T(8,128)(2,1)S(1)} fusion(bf16[4,2048,2048]{1,2,0} '
          '%copy-done.85, bf16[4,8192,2048]{2,1,0} %x), kind=kOutput, calls=%fused_computation.179')


def test_custom_calls_carry_their_operand_shapes():
    calls = xplane.custom_calls([ev(UP, 0, 10), ev(FUSION, 10, 20)], 0, 100)
    assert len(calls) == 1
    call = calls[0]
    assert call.instruction == "%up_proj.4"
    assert call.results == [("f32", (32, 8192))]
    assert call.operands == [("f32", (32, 2048)), ("s8", (2048, 8192)), ("f32", (1, 8192))]
    assert xplane.custom_calls([ev(UP, 0, 10)], 20, 30) == []


def test_short_names_for_the_breakdown():
    assert xplane.short_name(FUSION) == "%fusion.286 fusion bf16[4,2048,2048]"
    assert xplane.short_name(UP) == (
        "%up_proj.4 custom-call f32[32,8192] (f32[32,2048],s8[2048,8192],f32[1,8192])")
    assert xplane.short_name("bench:step") == "bench:step"


def test_roofline_share_is_bound_over_time():
    assert xplane.roofline_share([(2e-3, 1e-3), (2e-3, 0.0)]) == 25.0
    assert xplane.roofline_share([]) is None


RECORDED = os.path.join(HERE, "recorded_trace.txt")


@pytest.fixture(scope="module")
def recorded():
    """0.33 s of a ``--trace 1`` run of train-internlm2-1.8b-s2048 on one
    TPU v5 lite chip (PR 26), cut by ``cut_trace.py``."""
    from jax.profiler import ProfileData

    with open(RECORDED) as f:
        return xplane.load(ProfileData.from_text_proto(f.read()))


def test_recorded_trace_reduces(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    evs = recorded.devices["/device:TPU:0"]
    lo, hi = xplane.window_of(recorded)
    busy = xplane.busy_seconds(evs, lo, hi)
    window = (hi - lo) / 1e9
    assert 0.25 < window < 0.45 and 0.9 * window < busy <= window
    # a scanned layer stack is a while that spans its body: in the union,
    # in no sum
    assert any(xplane.is_wrapper(e.name) for e in evs)
    sums = xplane.sums_by(evs, lo, hi, lambda e: xplane.short_name(e.name))
    assert not any(k.split()[1] == "while" for k in sums)
    assert sum(sums.values()) >= 0.9 * busy
    # the benchmark's own spans are on the device's clock
    assert recorded.spans and all(s.name == "bench:loader_next" for s in recorded.spans)
    assert lo <= recorded.spans[0].start < hi
    gaps = xplane.gaps(evs, lo, hi)
    idle = sum(t - s for s, t in gaps) / 1e9
    assert idle == pytest.approx(window - busy, abs=1e-9)
    by = xplane.attribute_gaps(gaps, recorded.spans)
    assert sum(by.values()) == pytest.approx(idle, rel=1e-6)


def test_recorded_trace_tells_the_kernels_apart(recorded):
    evs = recorded.devices["/device:TPU:0"]
    lo, hi = xplane.window_of(recorded)
    calls = xplane.custom_calls(evs, lo, hi)
    flash = [c for c in calls if c.operands[:3] == [("bf16", (64, 2048, 128))] * 3]
    loss = [c for c in calls if c.operands[0] == ("bf16", (8192, 2048))
            and c.operands[1][1][0] == 2048 and c.operands[1][1][1] >= 92544]
    adamw = [c for c in calls if c.operands[0][0] == "f32" and c.operands[0][1][-1] == 128
             and len(c.results) == 3]
    assert flash and loss and adamw
    assert not {id(c) for c in flash} & {id(c) for c in loss}
    # one optimizer step: a forward and the dh / dW pair of the fused loss
    assert {len(c.operands) for c in loss} == {3, 5}
    # per layer a forward, its recomputation, dq and dkv
    assert {len(c.operands) for c in flash} >= {3, 6}
