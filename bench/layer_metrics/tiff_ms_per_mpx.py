"""Host milliseconds in ``export.tiff`` (the tiled-TIFF writer and its
Deflate) inside the traced part of the window, per megapixel exported
there."""
from span_time import traced_ms_per_mpx


def read(ctx):
    return traced_ms_per_mpx(ctx, ("export.tiff",))
