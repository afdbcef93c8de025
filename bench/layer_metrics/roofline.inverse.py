"""Percent of its roofline that the inverse JPEG transform program
(``jit__jpeg_inverse_core``: dequantise, inverse DCT, YCbCr -> RGB) reaches
in the traced window: the least time the algorithm's work needs on this
chip over the program's device time.

Each compiled variant is one level size; variants ordered by mean device
time pair with the level sizes of the mix's studies ordered by work."""
import devtrace
import reference
import work


def read(ctx):
    if ctx.trace is None:
        return None
    groups = devtrace.program_time(ctx.trace, "jit__jpeg_inverse_core")
    sizes = {d * d for side, _ in ctx.mix["sizes"]
             for d in reference.level_dims(int(side),
                                           ctx.cfg["min_level_size"])}
    least = [work.least_time(*work.inverse(px), ctx.peaks)[0] for px in sizes]
    match = devtrace.assign_by_duration(groups, least)
    if not match:
        return None
    need = sum(match[g] * len(d) for g, d in groups.items())
    took = sum(sum(d) for d in groups.values())
    return 100.0 * need / took
