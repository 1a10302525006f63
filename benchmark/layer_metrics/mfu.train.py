"""The whole step's share of the chips' bf16 peak: analytic operations a
token (``benchmark/lib/flops.py``, recomputation not counted) times the
window's tokens a second, over chips times peak."""

from benchmark.lib import flops


def read(bundle):
    rate = bundle["values"].get("train_tokens_per_s")
    if not rate or bundle["peaks"] is None:
        return None
    per_token = flops.train_flops_per_token(
        bundle["shape"], bundle["cell"].traffic["seq_len"])
    peak = bundle["peaks"]["flops_per_s"]["bfloat16"] * bundle["device"]["count"]
    return 100.0 * per_token * rate / peak
