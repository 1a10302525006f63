"""One decode step of one Mamba-2 layer for ``slots`` sequences
(``ops/ssd.py:ssd_update``), ``heads`` (H) of ``head_dim`` (P) channels by
``states`` (N), ``groups`` (G) sharing ``B`` and ``C``, as the equations
count it (``falcon_h1/reference.serve_flops``): ``6 H P N`` operations a
sequence (the state's decay, its rank-one update and the read-out against
``C``: a multiply-add each) of float32. Its bytes are the state read once
and written once, ``2 H N P`` numbers a sequence, and beside it ``x`` in and
``y`` out (``H P`` each), ``dt`` (``H``), ``B`` and ``C`` (``G N`` each).

Charged to the kernel, since the equations do not need them: the decay a
head comes in spread over its ``P`` channels, ``B`` and ``C`` as columns
padded to a whole lane tile, and a slot that holds no live sequence is
counted as if it did (a call's shapes hold the slots, not which are live).
The bound is the bytes': 8.4 MB a sequence at 32 x 128 x 256, 537 MB a
layer a step at 64 sequences.
"""


def cost(slots, heads, head_dim, states, groups=1, itemsize=4):
    ops = 6.0 * slots * heads * head_dim * states
    state = 2 * heads * states * head_dim
    beside = 2 * heads * head_dim + heads + 2 * groups * states
    return ops, slots * (state + beside) * itemsize
