"""Causal attention over (batch, heads, seq, head_dim): the operations and
bytes the algorithm needs for one call, from shapes alone.

Forward: the scores and their weighted sum over the causal triangle, two
products of 2*S*S/2*hd operations a head. Backward: five such products
(the scores again, dV, dP, dQ, dK) as the FlashAttention papers count it;
a second recomputation by a split dq/dkv pair is the implementation's and
is not counted. Bytes: q, k, v read and o written once (forward); q, k, v,
o, do read and dq, dk, dv written once (backward). ``kv_heads`` < ``heads``
reads only the grouped K/V.
"""


def cost(batch, heads, kv_heads, seq, head_dim, itemsize, backward=False):
    product = 2.0 * batch * heads * seq * seq / 2 * head_dim
    q_bytes = batch * heads * seq * head_dim * itemsize
    kv_bytes = batch * kv_heads * seq * head_dim * itemsize
    if backward:
        return 5 * product, 4 * q_bytes + 4 * kv_bytes  # q o do dq / k v dk dv
    return 2 * product, 2 * q_bytes + 2 * kv_bytes
