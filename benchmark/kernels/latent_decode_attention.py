"""``slots`` queries of ``heads`` rows each against the ``context`` cached
rows they attend, as the equations count them (``reference.serve_flops``): a
head-row scores a cached token over ``rank + rope`` numbers and weighs the
``rank`` of its latent, 2 * (rank + rope) + 2 * rank operations; a cached
token is ``rank + rope`` numbers of ``itemsize`` bytes, read once for all
heads; the queries come in and the float32 result goes out a head-row each.
``context`` is the mean number of rows a slot attends.

Charged to the kernel, since the equations do not need them: the columns
that pad a stored row to whole lane tiles (576 -> 640, which it reads and
multiplies) and the rows past a slot's depth in the blocks of rows it reads
whole.
"""


def cost(slots, heads, context, rank, rope, itemsize=2):
    ops = slots * heads * context * (2.0 * (rank + rope) + 2.0 * rank)
    row = (rank + rope) * itemsize
    return ops, slots * (context * row + heads * (row + 4 * rank))
