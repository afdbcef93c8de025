"""Host milliseconds of ``convert.decode`` (level 0 of a JPEG SVS decoded on
the device: parse and pack the scans, upload them, the entropy decoder's
loop up to its error flags, the inverse dispatch) inside the traced part
of the window, each span clipped to it, per level-0 megapixel the client
credits to that part."""
from span_time import traced_ms_per_mpx


def read(ctx):
    return traced_ms_per_mpx(ctx, ("convert.decode",))
