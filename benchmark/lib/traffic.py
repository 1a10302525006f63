"""The one general traffic generator. A mix is a data file under
``benchmark/traffic/`` naming one of three kinds and its parameters:

``train_steps``
    ``batch``, ``seq_len``, ``steps_per_epoch``, ``token_skew`` and the
    ``adamw`` hyper-parameters: epochs of ``steps_per_epoch`` optimizer
    steps over seeded token ids, skewed to the low ids by ``u**skew``.
``open_poisson``
    ``rate_per_s``, ``prompt`` and ``output`` length distributions: an
    open loop, requests due at seeded exponential gaps whether or not
    earlier ones have finished.
``closed_clients``
    ``clients``, ``ramp_seconds``, ``block``, ``prompt`` and ``output``: each
    client sends its next request when its last completes; the requests
    come in blocks of ``block``, each block the same set of lengths.

A length distribution is ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}``.

Every seed gets the same number of requests, the same set of lengths and the
same set of gaps, in another order: the set is the distribution's quantiles
at evenly spaced probabilities (lengths paired by the mix's own
``pairing_seed``), and the run's seed only permutes it. So the work offered
in a window does not move with the seed, only its order does.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([seed, stream]))


def length_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles at (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def request_set(mix: dict, n: int, seed: int) -> list[tuple[int, int]]:
    """``n`` (prompt length, output length) pairs: the same set for every
    seed, in the seed's order."""
    prompts = length_set(mix["prompt"], n)
    outputs = length_set(mix["output"], n)
    outputs = outputs[rng(mix.get("pairing_seed", 0), 1).permutation(n)]
    order = rng(seed, 2).permutation(n)
    return [(int(prompts[i]), int(outputs[i])) for i in order]


def poisson_due_times(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times, seconds from the window's opening, of exactly
    ``round(rate * seconds)`` requests: the exponential distribution's
    quantile gaps in the seed's order, stretched so that the last request
    is due half a mean gap before the window closes. Every seed offers the
    same number of requests at the same set of gaps."""
    n = max(1, round(rate_per_s * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)[rng(seed, 3).permutation(n)]
    due = np.cumsum(gaps)
    return due * (seconds * (n - 0.5) / n / due[-1])


def open_poisson(mix: dict, seconds: float, seed: int):
    """Requests due inside ``seconds``: (due_s, prompt_len, out_len)."""
    due = poisson_due_times(mix["rate_per_s"], seconds, seed)
    pairs = request_set(mix, len(due), seed)
    return [(float(t), p, o) for t, (p, o) in zip(due, pairs)]


def closed_clients(mix: dict, seed: int, blocks: int = 32):
    """The shared list the clients draw their next request from: blocks of
    ``mix["block"]`` requests, each block the same set of lengths in an
    order of its own, so that any stretch of the stream holds nearly the
    same work whatever the seed."""
    out = []
    for b in range(blocks):
        out += request_set(mix, mix["block"], seed * 1009 + b)
    return out


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list[int]:
    return rng(seed, 16 + index).integers(0, vocab, length).tolist()


def train_tokens(mix: dict, vocab: int, seed: int):
    """(inputs, targets) for one epoch's rows: ``steps_per_epoch * batch``
    rows of ``seq_len`` next-token pairs, all different."""
    rows = mix["steps_per_epoch"] * mix["batch"]
    u = rng(seed, 4).random((rows, mix["seq_len"] + 1))
    toks = np.minimum((vocab * u ** mix["token_skew"]), vocab - 1).astype(np.int32)
    return toks[:, :-1], np.ascontiguousarray(toks[:, 1:])


def prompt_buckets(mix: dict, window: int, floor: int = 8) -> list[int]:
    """One prompt length for each power-of-two bucket the mix can reach
    (what a warm-up has to touch), longest within the bucket."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out, b = [], floor
    while True:
        top = min(b, window)
        if top >= lo and (b // 2 < hi or b == floor):
            out.append(min(top, hi))
        if b >= hi or b >= window:
            return sorted(set(out))
        b *= 2
