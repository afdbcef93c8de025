"""Megapixels of pyramid levels exported per second of the window: each
level whose TIFF landed in the derived bucket is credited with its pixels
in the share of its start-to-put interval that lies inside the window."""


def read(ctx):
    return ctx.client.mpx_in(ctx.t0, ctx.t1) / (ctx.t1 - ctx.t0)
