"""Rows sorted by expert against the int8 weights (experts, k, n) of the
experts a chip holds, a scale a column: ``pairs`` rows really routed here
(2 * pairs * k * n int8 operations; each read in ``x_itemsize`` bytes and
written in ``out_itemsize``) and every held expert's weights read once, in
one byte, with its float32 scales. At decode (a few pairs an expert) the
weight bytes bound it.

``expected_pairs`` gives the pairs a call computes from its shapes alone:
the kernel's row buffer is the static worst case ``ceil(tokens * top_k /
block_m) * block_m + experts * block_m`` rows, ``block_m`` the rows of one
entry of its ``tile_expert`` operand, and of the ``tokens * top_k`` choices
the share ``experts / all_experts`` falls on the experts held.
``expected_experts`` gives the held experts that get a pair at all, whose
weights alone the kernel reads: a choice misses one expert with probability
``1 - 1 / all_experts``, so at 64 tokens x 8 choices 13.8 of 16 are read
(every one at 128 tokens and more, to a percent); pass it as ``experts``.
"""


def expected_pairs(rows: int, tiles: int, experts: int, all_experts: int) -> float:
    block_m = rows // tiles
    return (rows - experts * block_m) * experts / all_experts


def expected_experts(rows: int, tiles: int, experts: int, all_experts: int) -> float:
    choices = rows - experts * (rows // tiles)
    return experts * (1.0 - (1.0 - 1.0 / all_experts) ** choices)


def cost(pairs, experts, k, n, x_itemsize=2, out_itemsize=2):
    ops = 2.0 * pairs * k * n
    return ops, experts * (k * n + 4 * n) + pairs * (k * x_itemsize + n * out_itemsize)
