"""Host milliseconds of the batched decoder's host stages under
``export.level`` inside the traced part of the window, per megapixel
exported there: ``decode.parse`` (JFIF parse, unstuffing, packing the
decoder's buffer) and ``decode.scatter`` (DC integration and the zigzag
scatter of the fetched coefficients)."""
from span_time import traced_ms_per_mpx


def read(ctx):
    return traced_ms_per_mpx(ctx, ("decode.parse", "decode.scatter"),
                             ancestor="export.level")
