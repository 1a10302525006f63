"""One decode step of one layer's attention over the K and V rows the live
slots hold (``ops/decode_attention.py``), as the equations count it:
``rows`` is the rows the call's slots attend together (a slot of depth
``d`` attends ``d + 1``, a ring at most its length), each query head
scores each row of its KV head over ``head_dim`` numbers and weighs its
value, ``4 * heads * rows * head_dim`` operations; a row is K and V of
every KV head, ``2 * kv_heads * head_dim`` numbers of ``itemsize`` bytes,
read once for all the heads that share it; each slot's query comes in and
its result goes out, ``heads * head_dim`` numbers each.

Charged to the kernel, since the equations do not need them: the rows past
a slot's depth inside a block it reads whole, the grid steps over slots
that hold nothing (a call's slots are all its grid walks), and the
products of every query head with every KV head's rows of a block (PR 31).
"""


def cost(rows, slots, heads, kv_heads, head_dim, itemsize=2, q_itemsize=4,
         out_itemsize=4):
    ops = 4.0 * heads * rows * head_dim
    kv = rows * 2 * kv_heads * head_dim * itemsize
    return ops, kv + slots * heads * head_dim * (q_itemsize + out_itemsize)
