"""Device milliseconds of the jitted entropy decoder's ``while_loop``
program (``jit__lockstep``) per megapixel exported in the traced part of
the window (each level credited with the share of its export inside)."""
import devtrace


def read(ctx):
    if ctx.trace is None:
        return None
    times = devtrace.program_time(ctx.trace, "jit__lockstep")
    mpx = ctx.client.mpx_in(ctx.tw0, ctx.tw1)
    if not times or not mpx:
        return None
    return sum(sum(v) for v in times.values()) * 1e3 / mpx
