"""The whole step's share of the chips' bf16 peak: analytic operations a
token (the block's reference's ``train_flops_per_token``, recomputation not
counted) times the window's tokens a second, over chips times peak."""


def read(bundle):
    rate = bundle["values"].get("train_tokens_per_s")
    if not rate or bundle["peaks"] is None:
        return None
    per_token = bundle["block"].reference.train_flops_per_token(
        bundle["shape"], bundle["cell"].traffic["seq_len"])
    peak = bundle["peaks"]["flops_per_s"]["bfloat16"] * bundle["device"]["count"]
    return 100.0 * per_token * rate / peak
