"""Output head and cross entropy in one pass, never holding the logits:
hidden (rows, d) against head (d, vocab), one target a row.

Forward: one rows x d x vocab product. Backward: the logits again to form
softmax minus one-hot, then dh and dW: three products. Bytes: hidden and
head read, a loss a row written (forward); hidden and head read, dh and dW
written (backward).
"""


def cost(rows, d, vocab, itemsize, backward=False, grad_itemsize=4):
    product = 2.0 * rows * d * vocab
    if backward:
        byts = (rows * d + d * vocab) * itemsize + (rows * d + d * vocab) * grad_itemsize
        return 3 * product, byts
    return product, (rows * d + d * vocab) * itemsize + rows * 4
