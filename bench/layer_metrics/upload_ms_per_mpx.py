"""Host milliseconds in ``convert.upload`` (reading level 0 out of the
container and copying it to the device) per level-0 megapixel."""
from spans import per_mpx_ms


def read(ctx):
    return per_mpx_ms(ctx, "convert.upload")
