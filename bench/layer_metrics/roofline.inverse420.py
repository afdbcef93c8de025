"""Percent of its roofline that the inverse transform of 4:2:0 tiles
(``jit__jpeg_inverse420_core``: dequantise, inverse DCT, chroma upsample,
YCbCr -> RGB) reaches on level 0 in the traced window: the least time the
algorithm's work needs on this chip over the program's device time.

The program also runs on the few frames the ML subscriber decodes; only
the variant whose work the reader can name counts: the level-0 batch, the
compiled variant with the longest mean device time."""
import devtrace
import work
import work_svs


def read(ctx):
    if ctx.trace is None:
        return None
    groups = devtrace.program_time(ctx.trace, "jit__jpeg_inverse420_core")
    if not groups:
        return None
    times = max(groups.values(), key=lambda d: sum(d) / len(d))
    side = max(int(side) for side, _ in ctx.mix["sizes"])
    least = work.least_time(*work_svs.inverse420(side * side), ctx.peaks)[0]
    return 100.0 * least * len(times) / sum(times)
