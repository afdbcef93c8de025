"""Level-0 megapixels converted per second of the window: each slide whose
study answered QIDO with every level is credited with its level 0 in the
share of its landing-to-QIDO interval that lies inside the window (slides
still in flight at the window's end are followed to the drain limit)."""


def read(ctx):
    return ctx.client.mpx_in(ctx.t0, ctx.t1) / (ctx.t1 - ctx.t0)
