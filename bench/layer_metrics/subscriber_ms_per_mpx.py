"""Host milliseconds of the store's subscribers per level-0 megapixel of
the slides they worked for: ``validate.verify`` (the blob read and the
Part-10 deep check) and ``inference.score`` (frame fetch, decode and
statistics of the ML subscriber)."""
from span_time import per_mpx_ms_of


def read(ctx):
    return per_mpx_ms_of(ctx, ("validate.verify", "inference.score"))
