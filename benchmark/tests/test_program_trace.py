"""The program's spans, scopes and programs out of a trace
(``lib/program_trace.py``), on a small hand-made trace in the layout the
TPU's profiler writes (``program_trace.txt``: two engine steps of nested
``prog:`` spans inside ``bench:step``, a prefill and two chain programs,
scoped and unscoped operations, three idle gaps), and the new per-layer
readers on it, on PR 26's recorded trace (a program without spans) and on
no trace at all."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import harness, program_trace, xplane

HERE = os.path.dirname(__file__)
US = 1000  # the file's times are microseconds after 1000 ns
T = lambda us: 1000 + us * US  # noqa: E731
SPEC = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NEW = sorted(
    f[:-3] for f in os.listdir(os.path.join(harness.BENCH, "layer_metrics"))
    if "program_trace" in open(os.path.join(harness.BENCH, "layer_metrics", f)).read()
)


def serialized(name):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, name)) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def pt():
    return program_trace.load(serialized("program_trace.txt"))


def bundle_for(tmp_path, monkeypatch, name, window=None):
    """What ``run.py`` hands a reader after a traced run whose newest
    trace is ``name``."""
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "t.xplane.pb").write_bytes(serialized(name))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    program_trace._CACHE.clear()
    trace = xplane.load(str(out / "t.xplane.pb"))
    lo, hi = window or xplane.window_of(trace)
    return {"trace": trace, "trace_window": (lo, hi), "busiest": "/device:TPU:0"}


def read(metric, bundle):
    path = os.path.join(harness.BENCH, "layer_metrics", metric + ".py")
    return harness.load_module(path).read(bundle)


def test_spans_nest_by_thread_and_carry_their_fields(pt):
    assert pt.main_thread == "/host:CPU/python3"
    assert len(pt.named("step")) == 2 and len(pt.named("loader_next")) == 2
    refill, = pt.named("refill")
    assert refill.fields == {"rid": 7, "slot": 1}
    assert refill.children[0].fields == {"rid": 7, "bucket": 16}
    assert refill.parent.name == "prog:step"
    assert refill.parent.parent.name == "bench:step"
    assert [c.name for c in refill.children] == ["prog:prefill_fetch"]
    complete, = pt.named("complete")
    assert complete.parent.name == "prog:distribute"
    assert [s.fields["rid"] for s in pt.named("queue_pop")] == [7, -1]  # signed
    # a span on another thread has no parent on this one; the profiler's
    # own python frames are no spans of ours
    assert all(s.parent is None for s in pt.named("loader_next"))
    assert pt.named("submit")[0].parent is None  # between two steps
    assert not any("python.frame" in s.name for s in pt.spans)
    assert (refill.start, refill.end) == (T(110), T(470))


def test_self_time_is_duration_less_children(pt):
    refill, = pt.named("refill")
    first, second = pt.named("step")
    assert refill.self_seconds == pytest.approx(200e-6)
    assert first.self_seconds == pytest.approx(30e-6)
    assert second.self_seconds == pytest.approx(28e-6)
    assert pt.named("distribute")[0].self_seconds == pytest.approx(20e-6)


def test_ops_carry_their_scope_path_and_program(pt):
    ops = pt.devices["/device:TPU:0"]
    kernel = next(o for o in ops if "tpu_custom_call" in o.name)
    assert kernel.path is None and kernel.program is None  # no stat, no guess
    assert [o.program for o in ops if o is not kernel] == (
        ["jit__prefill_fn"] * 2 + ["jit__chain_fn"] * 5)
    assert program_trace.scope_of(ops[0].path) == "kv_cache"
    assert program_trace.scope_of(ops[1].path) == "mlp"
    squeeze = next(o for o in ops if o.name.startswith("%squeeze"))
    assert program_trace.scope_of(squeeze.path) == "layer_scan"
    assert program_trace.scopes_on("jit(step_fn)/transpose(jvp(loss))/mul") == (
        "step_fn", "loss", "mul")


def test_gaps_go_to_the_innermost_span_once(pt):
    ops = pt.devices["/device:TPU:0"]
    gaps = xplane.gaps(ops, T(100), T(1400))
    assert sorted(gaps) == [(T(400), T(500)), (T(900), T(1000)), (T(1300), T(1400))]
    got = program_trace.attribute_gaps(gaps, program_trace.innermost_segments(pt))
    want = {"prog:prefill_fetch": 60, "prog:refill": 10, "prog:step": 53,
            "prog:chain_dispatch": 20, "prog:chain_fetch": 35,
            "prog:distribute": 80, "prog:complete": 5, "prog:queue_pop": 2,
            "bench:step": 25, "prog:submit": 4, "untracked": 6}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(300e-6)  # every idle second once
    # the benchmark's own attribution sums a span with its parents
    old = xplane.attribute_gaps(gaps, xplane.load(_profile()).spans)
    assert old == pytest.approx({"step": 290e-6, "untracked": 10e-6})
    inside = sum(v for k, v in got.items() if k.startswith("prog:") and k != "prog:submit")
    assert inside / old["step"] > 0.9


def _profile():
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(serialized("program_trace.txt"))


def test_readers_on_the_hand_made_trace(tmp_path, monkeypatch):
    b = bundle_for(tmp_path, monkeypatch, "program_trace.txt", (T(100), T(1400)))
    assert read("chain_period.latency", b) == pytest.approx(0.405)
    # idle under the two steps: 100 + 40 and 35 + 90 of the three gaps' 300
    assert read("step_host.serve", b) == pytest.approx(0.1325)
    assert read("loader_wait.train", b) == pytest.approx(100 * 150 / 1300)
    assert read("layer_scan_share.serve", b) == pytest.approx(10.0)
    assert read("prefill_share.serve", b) == pytest.approx(30.0)
    assert program_trace.program_share(b, "_chain_fn") == pytest.approx(60.0)
    under = lambda scope: program_trace.share_of_busy(  # noqa: E731
        b, lambda o: scope in program_trace.scopes_on(o.path))
    assert under("kv_cache") == pytest.approx(20.0)
    assert under("mlp") == pytest.approx(35.0)  # a union
    # no share can pass 100 %: everything picked is the whole busy time
    assert program_trace.share_of_busy(b, lambda o: True) == pytest.approx(100.0)
    assert under("optimizer") is None  # nothing under it


def test_fields_join_the_spans_of_a_request_and_of_a_chain(pt):
    """What the fields are for: ``rid`` makes a request's row, ``chain`` a
    chain's, ``step`` counts optimizer steps where a dispatch covers four."""
    rows = {r["rid"]: r for r in program_trace.request_rows(pt)}
    assert sorted(rows) == [5, 7, 8]  # the pop that found nothing (-1) is no request
    assert rows[7] == {
        "rid": 7, "p_len": None, "max_new": None, "slot": 1, "bucket": 16,
        "queued_ms": None, "refill_ms": pytest.approx(0.36), "first_token_ms": None,
        "tokens": None, "served_ms": None}  # submitted before the trace began
    assert (rows[8]["p_len"], rows[8]["max_new"], rows[8]["slot"]) == (40, 12, None)
    assert rows[5]["tokens"] == 9 and rows[5]["bucket"] is None
    first, second = program_trace.chain_rows(pt)
    assert first == {
        "chain": 0, "occupancy": 3, "dispatch_ms": pytest.approx(0.01),
        "fetch_ms": pytest.approx(0.41), "launch_to_tokens_ms": pytest.approx(0.425),
        "tokens": 11}
    assert (second["chain"], second["occupancy"], second["tokens"]) == (1, 2, 4)
    assert program_trace.period_ms(pt.named("chain_fetch"), "chain") == pytest.approx(0.405)
    # two dispatches 550 us apart, the second's ``step`` four on
    assert program_trace.period_ms(pt.named("dispatch"), "step") == pytest.approx(0.1375)
    assert program_trace.period_ms(pt.named("chain_fetch")[:1], "chain") is None


def test_tables_for_perf_md(pt, capsys, tmp_path, monkeypatch):
    t = program_trace.tables(pt)
    assert t["window_s"] == pytest.approx(1320e-6) and t["busy_s"] == pytest.approx(1000e-6)
    assert t["spans"]["prog:step"]["n"] == 2
    assert t["device_by_program"] == pytest.approx(
        {"jit__prefill_fn": 300e-6, "jit__chain_fn": 650e-6, "(no program)": 100e-6})
    by = t["device_by_scope"]
    assert by["kv_cache"] == pytest.approx(200e-6) and by["(no path)"] == pytest.approx(100e-6)
    assert sum(t["idle_by_span"].values()) == pytest.approx(320e-6)
    bundle_for(tmp_path, monkeypatch, "program_trace.txt")
    program_trace.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert "| `prog:chain_fetch` | 2 |" in out and "| jit__prefill_fn |" in out
    assert "| 7 |  |  | 1 | 16 |" in out and "| 0 | 3 | 0.01 | 0.41 |" in out
    assert "chain_period_ms 0.405" in out and "dispatch_period_ms 0.138" in out


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_none_without_a_trace(metric):
    assert read(metric, {"trace": None}) is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_none_on_a_program_without_spans(metric, tmp_path, monkeypatch):
    """PR 26's recorded trace: ``bench:`` spans only, no scope path, no
    module line. What the parent commit's traced runs look like."""
    b = bundle_for(tmp_path, monkeypatch, "recorded_trace.txt")
    assert read(metric, b) is None


def test_new_metrics_are_declared_and_small():
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    assert len(NEW) == 9 and set(NEW) <= set(declared)
    for name in NEW:
        src = open(os.path.join(harness.BENCH, "layer_metrics", name + ".py")).read()
        assert len(src.strip().splitlines()) <= 12, name
        assert declared[name]["source"] in ("program_counter", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_rehearsal_reports_the_host_span_metrics(cell):
    """``--rehearse --trace 1``: the metrics read from the program's own
    spans are in the line by name (a CPU run gives no value)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 27), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    mine = {m["name"] for m in SPEC["per_layer"] if cell in m.get("workloads", ())
            and m["name"] in NEW and m["source"] == "program_counter"}
    assert mine and mine <= set(line["metrics"])
    assert all(line["metrics"][m]["value"] is None for m in mine)
