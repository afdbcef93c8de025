"""Host milliseconds in ``convert.encode`` per level-0 megapixel: the
Huffman coding of every level's coefficients on the host."""
from spans import per_mpx_ms


def read(ctx):
    return per_mpx_ms(ctx, "convert.encode")
