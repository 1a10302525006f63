"""x (m, k) against int8 weights (k, n) with a scale a column: 2*m*k*n
int8 operations; x read in ``x_itemsize`` bytes, the weights in one byte,
the result written in ``out_itemsize``. At decode (m = slots) the weight
bytes bound it; at prefill (m = bucket) the operations do.
"""


def cost(m, k, n, x_itemsize=4, out_itemsize=4):
    return 2.0 * m * k * n, m * k * x_itemsize + k * n + 4 * n + m * n * out_itemsize
