"""``rows`` sequences of ``positions`` through one Mamba-1 layer's selective
scan of ``channels`` (E) by ``states`` (N), as the equations count it
(``reference.serve_flops``): a position is ``6 E N`` operations (the state's
decay and its update, two multiply-adds; the read-out, one) of float32; its
bytes are ``u`` and ``delta`` in and ``y`` out, ``E`` numbers each, and ``B``
and ``C``, ``N`` each; ``A`` comes in and the last state goes out once a
row. The exponential a state element a position is not counted (the
operation counts of this benchmark are multiply-adds).

Charged to the kernel, since the equations do not need them: ``B`` and
``C`` are read again for every block of channels, in rows padded to a whole
lane tile. The bound is nearly always the bytes': what holds the kernel is
the recurrence itself, one position after the other, which no peak of the
table expresses; the share says how far from streaming its operands it is.
"""


def cost(rows, positions, channels, states, itemsize=4):
    ops = 6.0 * rows * positions * channels * states
    a_position = (3 * channels + 2 * states) * itemsize
    once = 2 * states * channels * itemsize  # A in, the last state out
    return ops, rows * (positions * a_position + once)
