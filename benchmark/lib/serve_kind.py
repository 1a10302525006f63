"""Traffic kinds ``open_poisson`` and ``closed_clients``: requests through
``ServeEngine.submit`` / ``step`` from one thread.

The window drives the engine that set-up built and warmed (one request for
each prompt bucket the mix reaches). Once the window has closed and every
request due in it has been waited for, a sample of the finished requests,
drawn from the seed with the longest in it, goes to the block's plain
reference (``benchmark/blocks/<block>/reference.py``): one pass over each prompt with its served
tokens, and the number compared is the widest gap by which a served
token's logit lies below the reference's best, in units of the deviation
of that position's logits.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import harness, program, traffic as traffic_lib, weights

DRAIN_SECONDS = 60.0


class Record:
    def __init__(self, index, due, prompt_len, out_len):
        self.index, self.due = index, due
        self.prompt_len, self.out_len = prompt_len, out_len
        self.request = None
        self.completion = None
        self.done_at = None


def build_engine(cell, seed: int):
    import jax
    import jax.numpy as jnp

    cfg, block = cell.config, cell.block
    block.needs("serve")
    shape = block.reference.Shape.from_config(cfg)
    serve = cfg["serve"]
    model = block.program.model(cfg, "serve", serve["window"])
    ref_params = weights.make(
        block.reference.leaf_shapes(shape), seed, serve["weights_dtype"],
        cfg["initializer_range"]
    )
    params = block.program.to_program(ref_params, shape)
    theirs = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    program.check_same_structure(params, theirs)
    engine = program.serve_engine(model, params, serve["engine"])
    return shape, ref_params, engine


def warm_up(engine, cell, seed: int) -> int:
    """One short request for each prompt bucket the mix reaches: every
    prefill program and the decode chain."""
    vocab = cell.config["vocab_size"]
    new = cell.config["serve"]["engine"].get("tokens_per_launch", 8) + 2
    lens = traffic_lib.prompt_buckets(cell.traffic, cell.config["serve"]["window"])
    for i, n in enumerate(lens):
        engine.submit(program.request(
            traffic_lib.prompt_tokens(seed, 1_000_000 + i, n, vocab), new))
    engine.run_until_idle()
    return len(lens)


class Driver:
    """The load loop. ``plan`` is the list of Records in submission order;
    open loop: each is due at its own time; closed loop: ``clients`` are
    outstanding at any time and a completion releases the next."""

    def __init__(self, engine, plan, prompts, closed_clients: int = 0):
        self.engine, self.plan, self.prompts = engine, plan, prompts
        self.closed = closed_clients
        self.n_planned = len(plan)
        self.next = 0
        self.by_id: dict = {}
        self.outstanding = 0
        self.occupancy: list[int] = []
        self.late: list[float] = []
        self.steps = 0
        self.longest_step = (0.0, 0.0)  # seconds it took, seconds into the run

    def _submit(self, rec: Record, now: float) -> None:
        rec.request = program.request(
            self.prompts[rec.index % self.n_planned], rec.out_len)
        rid = self.engine.submit(rec.request)
        self.by_id[rid] = rec
        self.outstanding += 1
        if rec.due is None:
            rec.due = now
        self.late.append(now - rec.due)

    def _submit_due(self, now: float, accepting: bool) -> None:
        while accepting and (self.closed or self.next < len(self.plan)):
            if self.closed:
                if self.outstanding >= self.closed:
                    break
                if self.next == len(self.plan):  # the clients go round again
                    again = self.plan[self.next - self.n_planned]
                    self.plan.append(Record(
                        self.next, None, again.prompt_len, again.out_len))
            rec = self.plan[self.next]
            if not self.closed and rec.due > now:
                break
            self._submit(rec, now)
            self.next += 1

    def run(self, t0: float, until: float, tracer=None, drain: bool = False):
        """Drive from now to ``until`` seconds after ``t0``; with ``drain``
        go on, submitting nothing new, until nothing is outstanding."""
        engine = self.engine
        while True:
            now = time.perf_counter() - t0
            if tracer is not None:
                tracer.poll(now)
            if drain:
                if not self.outstanding or now >= until or engine.idle:
                    return
            elif now >= until:
                self._submit_due(until, accepting=True)
                return
            with harness.span("submit"):
                self._submit_due(now, accepting=not drain)
            if engine.idle:
                if self.closed or self.next >= len(self.plan):
                    if not self.outstanding:
                        return
                else:
                    wait = min(self.plan[self.next].due, until) - now
                    with harness.span("wait_arrival"):
                        time.sleep(max(0.0, min(wait, 0.005)))
                    continue
            with harness.span("step"):
                done = engine.step()
            self.steps += 1
            self.occupancy.append(engine.active_slots)
            at = time.perf_counter() - t0
            self.longest_step = max(self.longest_step, (at - now, now))
            for c in done:
                rec = self.by_id.pop(c.request_id, None)
                if rec is not None:
                    rec.completion, rec.done_at = c, at
                    self.outstanding -= 1


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def tpot_s(completion) -> float:
    """Seconds per output token after the first."""
    return (completion.latency_s - completion.ttft_s) / (len(completion.tokens) - 1)


def run(cell, args, log, tracer, fault=None) -> dict:
    cfg, mix = cell.config, cell.traffic
    vocab = cfg["vocab_size"]
    shape, ref_params, engine = build_engine(cell, args.seed)
    n_warm = warm_up(engine, cell, args.seed)
    if fault is not None:
        fault(engine)

    closed = mix["kind"] == "closed_clients"
    if closed:
        pairs = traffic_lib.closed_clients(mix, args.seed)
        plan = [Record(i, None, p, o) for i, (p, o) in enumerate(pairs)]
    else:
        reqs = traffic_lib.open_poisson(mix, args.seconds, args.seed)
        plan = [Record(i, t, p, o) for i, (t, p, o) in enumerate(reqs)]
    prompts = [
        traffic_lib.prompt_tokens(args.seed, r.index, r.prompt_len, vocab)
        for r in plan
    ]
    driver = Driver(engine, plan, prompts, mix["clients"] if closed else 0)
    ramp = float(mix.get("ramp_seconds", 0.0)) if closed else 0.0
    if ramp:
        driver.run(time.perf_counter(), ramp)  # the clients reach steady state
    first_in_window = driver.next
    driver.occupancy.clear()
    driver.late.clear()
    driver.steps = 0
    driver.longest_step = (0.0, 0.0)

    gc.collect()
    gc.freeze()  # what set-up left behind is never scanned inside the window
    setup = log.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - args.t_process_start
    if ramp:  # completions stamped before t0 belong to the ramp
        for r in plan[:first_in_window]:
            if r.done_at is not None:
                r.done_at = -1.0
    driver.run(t0, args.seconds, tracer)
    window_s = time.perf_counter() - t0
    tracer.stop()
    occupancy = list(driver.occupancy)
    steps_in_window = driver.steps
    late = list(driver.late)
    longest_step = driver.longest_step
    driver.run(t0, window_s + DRAIN_SECONDS, drain=True)
    drain_s = time.perf_counter() - t0 - window_s
    after = log.snapshot()
    memory_peak = harness.peak_bytes()
    errors = engine.fault_stats().get("prefill_errors", 0)
    n_slots = engine.n_slots

    due = plan[first_in_window:driver.next]
    ok = lambda r: (  # noqa: E731
        r.completion is not None
        and r.completion.finish_reason in ("length", "eos")
    )
    in_window = [
        r for r in plan[:driver.next]
        if ok(r) and r.done_at is not None and 0 <= r.done_at <= window_s
    ]
    tokens_done = sum(len(r.completion.tokens) for r in in_window)
    prompt_done = sum(r.prompt_len for r in in_window)
    ttft, tpot = [], []
    for r in due:
        if not ok(r):
            ttft.append(DRAIN_SECONDS + window_s)
            tpot.append(DRAIN_SECONDS + window_s)
            continue
        c = r.completion
        ttft.append(r.request.submitted_s - (t0 + r.due) + c.ttft_s)
        if len(c.tokens) > 1:
            tpot.append(tpot_s(c))
    end_to_end = {"serve_tokens_per_s": tokens_done / window_s}
    done_tpot = [
        tpot_s(r.completion) for r in in_window if len(r.completion.tokens) > 1
    ]
    if done_tpot:
        end_to_end["tpot_mean_ms"] = 1e3 * float(np.mean(done_tpot))
    if ttft:
        end_to_end["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    if tpot:
        end_to_end["tpot_p95_ms"] = 1e3 * percentile(tpot, 95)

    # the sample the reference sees: drawn from the seed, the longest in it
    finished = [r for r in plan[:driver.next] if ok(r)]
    n_check = min(mix["check_requests"], len(finished))
    sample = []
    if finished:
        longest = max(finished, key=lambda r: r.prompt_len + len(r.completion.tokens))
        rest = [r for r in finished if r is not longest]
        order = traffic_lib.rng(args.seed, 5).permutation(len(rest))
        sample = [longest] + [rest[i] for i in order[: n_check - 1]]
    served = [(list(r.completion.prompt), list(r.completion.tokens)) for r in sample]

    done_lengths = [(r.prompt_len, len(r.completion.tokens)) for r in in_window]
    gc.unfreeze()  # the engine can be freed before the reference runs
    del driver, engine, plan, due, in_window, finished, sample
    gc.collect()
    return {
        "kind": mix["kind"], "block": cell.block, "shape": shape,
        "setup_s": setup_s,
        "window_s": window_s, "attempted": len(ttft),
        "failed": sum(1 for x in ttft if x >= DRAIN_SECONDS),
        "end_to_end": end_to_end,
        "counters": {
            "steps": steps_in_window, "n_slots": n_slots,
            "occupancy_sum": int(np.sum(occupancy)), "occupancy_n": len(occupancy),
            "tokens_done": tokens_done, "prompt_tokens_done": prompt_done,
            "requests_done": len(ttft),
            "done_lengths": done_lengths,
            "late_max_s": max(late, default=0.0),
            "late_mean_s": float(np.mean(late)) if late else 0.0,
            # where a run reads far off: one long stall, and when
            "longest_step_s": longest_step[0], "longest_step_at_s": longest_step[1],
            "drain_s": drain_s, "prefill_errors": errors,
            "warm_requests": n_warm,
            "window_builds": after["programs"] - setup["programs"],
            "setup_compile": setup,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
            "completed_in_window": len(done_lengths),
        },
        "memory_peak_bytes": memory_peak,
        "proof": {"served": served, "ref_params": ref_params},
    }


def token_gaps(block, shape, ref_params, served, window: int,
               weight_bits: int = 8, max_out: int = 0):
    """For each sampled request the widest gap of its tokens, and the count
    compared. With ``weight_bits=4`` the tokens judged are not the served
    ones but those the int4 control puts first at the same positions."""
    import jax
    import jax.numpy as jnp

    reference = block.reference

    # one shape whatever the sample: one program to compile
    max_out = max(max_out, max(len(t) for _, t in served))

    @jax.jit
    def gaps(params, tokens, positions, chosen, n):
        lg = reference.logits(params, tokens, shape, positions)
        if weight_bits != 8:
            low = reference.logits(
                params, tokens, shape, positions, weight_bits=weight_bits)
            chosen = jnp.argmax(low, -1)
        best = jnp.max(lg, -1)
        mine = jnp.take_along_axis(lg, chosen[:, None], 1)[:, 0]
        gap = (best - mine) / jnp.std(lg, -1)
        return jnp.max(jnp.where(jnp.arange(gap.shape[0]) < n, gap, 0.0))

    worst, compared = [], 0
    vocab = shape.vocab_size
    for prompt, out in served:
        if not all(0 <= t < vocab for t in out):
            worst.append(float("inf"))  # a token outside the vocabulary
            compared += len(out)
            continue
        seq = (prompt + out[:-1])[:window]
        tokens = np.zeros((window,), np.int32)
        tokens[: len(seq)] = seq
        n = len(out)
        positions = np.minimum(len(prompt) - 1 + np.arange(max_out), window - 1)
        chosen = np.zeros((max_out,), np.int32)
        chosen[:n] = out
        worst.append(float(gaps(
            ref_params, jnp.asarray(tokens), jnp.asarray(positions, jnp.int32),
            jnp.asarray(chosen), n)))
        compared += n
    return worst, compared


def decide(cell, args, bundle: dict, checks) -> dict:
    proof = bundle["proof"]
    t0 = time.perf_counter()
    detail = {}
    if proof["served"]:
        worst, compared = token_gaps(
            bundle["block"], bundle["shape"], proof["ref_params"], proof["served"],
            cell.config["serve"]["window"],
            max_out=cell.traffic["output"]["max"],
        )
        detail.update(gaps=worst, tokens_compared=compared,
                      first_served=[t[:6] for _, t in proof["served"]])
        checks.at_most("served_token_gap", max(worst), cell.limit("served_token_gap"))
        checks.at_least("tokens_compared", compared, cell.limit("tokens_compared_min"))
    else:
        checks.at_least("tokens_compared", 0, cell.limit("tokens_compared_min"))
    detail["reference_s"] = time.perf_counter() - t0
    return detail
