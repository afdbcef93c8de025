"""Percent of its roofline that the fused pyramid program (``jit_chain``:
every level's forward transform and the downsample chain) reaches: the
least time the algorithm's work needs on this chip, over the device time
of the program's executions in the traced window.

Each compiled variant of the program is one slide side; variants ordered
by mean device time pair with the mix's sides ordered by work."""
import devtrace
import reference
import work


def read(ctx):
    if ctx.trace is None:
        return None
    groups = devtrace.program_time(ctx.trace, "jit_chain")
    sides = {int(side) for side, _ in ctx.mix["sizes"]}
    least = {}
    for side in sides:
        dims = reference.level_dims(side, ctx.cfg["min_level_size"])
        least[side] = work.least_time(*work.pyramid(dims), ctx.peaks)[0]
    match = devtrace.assign_by_duration(groups, list(least.values()))
    if not match:
        return None
    need = sum(match[g] * len(d) for g, d in groups.items())
    took = sum(sum(d) for d in groups.values())
    return 100.0 * need / took
