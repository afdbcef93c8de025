"""Host milliseconds in ``export.wado`` (the level's frame index and its
frame-by-frame WADO retrieval) inside the traced part of the window, per
megapixel exported there."""
from span_time import traced_ms_per_mpx


def read(ctx):
    return traced_ms_per_mpx(ctx, ("export.wado",))
