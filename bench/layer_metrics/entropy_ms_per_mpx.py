"""Host milliseconds in ``convert.entropy`` per level-0 megapixel: the
Huffman coding and Part-10 wrap of every level, and also the wait for the
level's coefficients to come back from the device, which the span covers
too."""
from spans import per_mpx_ms


def read(ctx):
    return per_mpx_ms(ctx, "convert.entropy")
