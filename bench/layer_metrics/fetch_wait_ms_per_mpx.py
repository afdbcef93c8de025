"""Host milliseconds in ``convert.fetch`` per level-0 megapixel: the
converter blocked on the device, from the start of a level to the return
of its coefficients (the rest of the pyramid program up to that level,
then the device-to-host copy)."""
from spans import per_mpx_ms


def read(ctx):
    return per_mpx_ms(ctx, "convert.fetch")
