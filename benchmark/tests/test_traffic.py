"""The arrival processes: reproducible from a seed, the same work for
every seed, and a generator that says how late it ran."""

import numpy as np

from benchmark.lib import serve_kind, traffic

CHAT = {
    "kind": "open_poisson", "rate_per_s": 8.0, "pairing_seed": 7,
    "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1024},
    "output": {"dist": "lognormal", "median": 96, "sigma": 0.6, "min": 16, "max": 256},
}


def test_open_poisson_repeats_from_its_seed():
    assert traffic.open_poisson(CHAT, 40.0, 5) == traffic.open_poisson(CHAT, 40.0, 5)
    assert traffic.open_poisson(CHAT, 40.0, 5) != traffic.open_poisson(CHAT, 40.0, 6)


def test_every_seed_offers_the_same_set():
    a = traffic.request_set(CHAT, 300, 1)
    b = traffic.request_set(CHAT, 300, 2**31 + 9)
    assert a != b and sorted(a) == sorted(b)
    ga = np.diff(traffic.poisson_due_times(8.0, 40.0, 1), prepend=0)
    gb = np.diff(traffic.poisson_due_times(8.0, 40.0, 2), prepend=0)
    assert len(ga) == len(gb) == 320  # the rate as stated, for every seed
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert 39.8 < ga.sum() < 40.0 and ga.std() > 0.8 * ga.mean()  # exponential
    a = traffic.open_poisson(CHAT, 40.0, 1)
    b = traffic.open_poisson(CHAT, 40.0, 2)
    assert sorted(x[1:] for x in a) == sorted(x[1:] for x in b)


def test_lengths_follow_the_distribution_and_its_clip():
    p = traffic.length_set(CHAT["prompt"], 1001)
    assert p.min() == 32 and p.max() == 1024 and int(np.median(p)) == 256
    u = traffic.length_set({"dist": "uniform", "min": 1024, "max": 3072}, 1001)
    assert u.min() >= 1024 and u.max() <= 3072 and abs(u.mean() - 2048) < 2


def test_prompt_buckets_cover_what_the_mix_reaches():
    assert traffic.prompt_buckets(CHAT, 2048) == [32, 64, 128, 256, 512, 1024]
    long = {"prompt": {"dist": "uniform", "min": 1024, "max": 3072}}
    assert traffic.prompt_buckets(long, 4096) == [1024, 2048, 3072]


def test_train_rows_all_differ_and_skew_low():
    mix = {"batch": 4, "seq_len": 64, "steps_per_epoch": 8, "token_skew": 4.0}
    x, y = traffic.train_tokens(mix, 1000, 3)
    assert x.shape == y.shape == (32, 64) and (x[:, 1:] == y[:, :-1]).all()
    assert len({r.tobytes() for r in x}) == 32
    assert np.median(x) < 1000 / 8 and x.max() < 1000


class _Done:
    def __init__(self, rid):
        self.request_id, self.finish_reason = rid, "length"
        self.tokens, self.prompt, self.ttft_s, self.latency_s = [1, 2], [1], 0.0, 0.0


class _SlowEngine:
    """Finishes each request in the step after its submission; every step
    takes 20 ms, so a request due mid-step is submitted late."""

    n_slots = 1

    def __init__(self):
        self.queue, self.n = [], 0

    def submit(self, request):
        self.n += 1
        self.queue.append(self.n)
        return self.n

    @property
    def idle(self):
        return not self.queue

    @property
    def active_slots(self):
        return len(self.queue)

    def step(self):
        import time

        time.sleep(0.02)
        done, self.queue = [_Done(r) for r in self.queue], []
        return done


def test_driver_reports_how_late_it_ran(monkeypatch):
    import time

    monkeypatch.setattr(serve_kind.program, "request", lambda p, n: object())
    plan = [serve_kind.Record(i, 0.01 * i, 4, 2) for i in range(10)]
    driver = serve_kind.Driver(_SlowEngine(), plan, [[1]] * 10)
    driver.run(time.perf_counter(), 0.5)
    assert driver.next == 10 and len(driver.late) == 10
    assert 0.005 < max(driver.late) < 0.05  # a step's length, not zero
    assert all(r.completion is not None for r in plan)


def test_closed_loop_blocks_hold_the_same_work():
    mix = {"block": 16, "pairing_seed": 11,
           "prompt": {"dist": "uniform", "min": 1024, "max": 3072},
           "output": {"dist": "uniform", "min": 64, "max": 256}}
    a, b = traffic.closed_clients(mix, 1), traffic.closed_clients(mix, 2)
    assert a != b and len(a) == len(b) == 512
    for k in range(0, 512, 16):
        assert sorted(a[k:k + 16]) == sorted(b[k:k + 16]) == sorted(a[:16])
