"""One schema'd receipt writer for every performance claim.

Every number this repo has ever quoted (BENCH_*, SERVING_*, TRAIN_LLM_*,
PROFILE_*) was produced by a script writing its own ad-hoc JSON; nothing
stamped WHICH code, WHICH jax, WHICH mesh, or how stable the measurement
window was. This module is the single envelope all of them now write
through:

    receipt = make_receipt("bench_headline", payload, mesh=mesh, drift=...)
    write_receipt(path, receipt)

The envelope is FLAT-MERGED with the payload (payload keys stay top-level)
so existing consumers that read ``metric`` / ``value`` / ``tok_s`` keep
working; the envelope adds ``schema`` / ``kind`` / ``env`` / optional
``drift``. :func:`validate_receipt` checks both the schema'd form and (in
legacy mode) the payloads of receipts checked in before the schema existed.

Import purity: this module imports jax only inside :func:`environment_stamp`
— receipt validation (tests, tooling) must not initialize a backend.
"""

from __future__ import annotations

import json
import os
import subprocess

SCHEMA = "graft-receipt/v1"

# Known receipt kinds — one per number-producing entry point.
KINDS = frozenset({
    "bench_headline",    # bench.py
    "serving",           # examples/serve_llm_int8.py
    "launch_probe",      # scripts/launch_overhead_probe.py
    "obs_selftest",      # python -m ...obs --selftest
    "serve_selftest",    # python -m ...serve --selftest
})

_ENVELOPE_KEYS = ("schema", "kind", "env", "drift")


def _git_sha() -> str | None:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=root, capture_output=True, text=True, timeout=10,
        )
    except (OSError, subprocess.SubprocessError):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def environment_stamp(mesh=None) -> dict:
    """git sha + jax version + backend + device/mesh shape, best-effort.

    ``mesh``: an optional ``jax.sharding.Mesh`` — its axis dict is the
    honest answer to "what parallelism produced this number".
    """
    import jax  # deferred: stamping implies a backend already exists

    stamp = {
        "git_sha": _git_sha(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }
    if mesh is not None:
        stamp["mesh"] = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    return stamp


def make_receipt(kind: str, payload: dict, *, mesh=None,
                 drift: dict | None = None) -> dict:
    """Envelope ``payload`` (flat merge) with schema + environment stamp."""
    if kind not in KINDS:
        raise ValueError(f"unknown receipt kind {kind!r}; known: "
                         f"{', '.join(sorted(KINDS))}")
    clash = set(payload) & set(_ENVELOPE_KEYS)
    if clash:
        raise ValueError(f"payload keys collide with envelope: {clash}")
    receipt = dict(payload)
    receipt["schema"] = SCHEMA
    receipt["kind"] = kind
    receipt["env"] = environment_stamp(mesh=mesh)
    if drift is not None:
        receipt["drift"] = drift
    return receipt


def write_receipt(path: str | None, receipt: dict) -> dict:
    """Validate and write a receipt (no-op write when ``path`` is None)."""
    problems = validate_receipt(receipt)
    if problems:
        raise ValueError("invalid receipt: " + "; ".join(problems))
    if path:
        with open(path, "w") as f:
            json.dump(receipt, f, indent=2)
            f.write("\n")
    return receipt


def validate_receipt(obj, kind: str | None = None) -> list[str]:
    """Problems with a receipt (empty list == valid).

    Two modes:

    - schema'd (``schema`` key present): envelope keys are checked in
      full — known kind, env stamp with jax_version/backend/device_count;
    - legacy (no ``schema`` key): the pre-schema payloads of rounds 1-5
      (``MULTICHIP_r0*.json``, ``ACCURACY_r04.json``). Those are still
      required to be non-empty dicts carrying at least one numeric
      measurement — retroactive validation, not a rubber stamp.
    """
    problems: list[str] = []
    if not isinstance(obj, dict):
        return ["receipt is not a dict"]
    if "schema" not in obj:
        return _validate_legacy(obj, kind)
    if obj["schema"] != SCHEMA:
        problems.append(f"unknown schema {obj['schema']!r}")
    k = obj.get("kind")
    if k not in KINDS:
        problems.append(f"unknown kind {k!r}")
    if kind is not None and k != kind:
        problems.append(f"kind {k!r} != expected {kind!r}")
    env = obj.get("env")
    if not isinstance(env, dict):
        problems.append("missing env stamp")
    else:
        for key in ("jax_version", "backend", "device_count"):
            if key not in env:
                problems.append(f"env stamp missing {key!r}")
    drift = obj.get("drift")
    if drift is not None and not isinstance(drift, dict):
        problems.append("drift must be a dict (DriftBracket.to_dict())")
    payload_keys = [key for key in obj if key not in _ENVELOPE_KEYS]
    if not payload_keys:
        problems.append("empty payload (envelope only)")
    return problems


def _validate_legacy(obj: dict, kind: str | None) -> list[str]:
    if not obj:
        return ["legacy receipt is empty"]

    def numbers(o):
        if isinstance(o, bool):
            return
        if isinstance(o, (int, float)):
            yield o
        elif isinstance(o, dict):
            for v in o.values():
                yield from numbers(v)
        elif isinstance(o, (list, tuple)):
            for v in o:
                yield from numbers(v)

    if not any(True for _ in numbers(obj)):
        return ["legacy receipt carries no numeric measurement"]
    if kind == "bench_headline":
        # the bench line itself, or the min-of-N wrapper that nests it
        # under "parsed" (the checked-in BENCH_r0*.json shape)
        line = obj.get("parsed") if isinstance(obj.get("parsed"), dict) \
            else obj
        missing = [k for k in ("metric", "value", "unit") if k not in line]
        if missing:
            return [f"legacy bench payload missing {missing}"]
    return []


def load_receipt(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
