"""Span helpers of the layer metrics that read several spans at once, or
only the traced part of the window; ``spans.py`` holds the first readers'
helpers, whose ``per_mpx_ms`` is ``per_mpx_ms_of`` with one name."""
from __future__ import annotations

from spans import by_id, key_of


def per_mpx_ms_of(ctx, names: tuple[str, ...]) -> float | None:
    """Milliseconds of the finished spans named any of ``names`` per
    level-0 megapixel of the slides those spans worked for."""
    index = by_id(ctx.spans)
    slides = {s.key: s for s in ctx.client.slides}
    total_ms = 0.0
    mpx: dict[str, float] = {}
    for sp in ctx.spans:
        if sp["name"] not in names or sp["end"] is None:
            continue
        s = slides.get(key_of(sp, index))
        if s is None:
            continue
        total_ms += (sp["end"] - sp["start"]) * 1e3
        mpx[s.key] = s.mpx
    return total_ms / sum(mpx.values()) if mpx else None


def under(span: dict, index: dict[str, dict], name: str) -> bool:
    """Whether an ancestor of ``span`` is named ``name``."""
    parent = index.get(span["parent_id"])
    while parent is not None:
        if parent["name"] == name:
            return True
        parent = index.get(parent["parent_id"])
    return False


def traced_ms_per_mpx(ctx, names: tuple[str, ...],
                      ancestor: str | None = None) -> float | None:
    """Milliseconds of the finished spans named any of ``names`` inside
    the traced part of the window, each clipped to it, per megapixel the
    client credits to that part (``ctx.client.mpx_in(tw0, tw1)``). With
    ``ancestor``, only the spans under a span of that name count."""
    if ctx.tw0 is None:
        return None
    index = by_id(ctx.spans)
    total, found = 0.0, False
    for sp in ctx.spans:
        if sp["name"] not in names or sp["end"] is None:
            continue
        if ancestor is not None and not under(sp, index, ancestor):
            continue
        inside = min(sp["end"], ctx.tw1) - max(sp["start"], ctx.tw0)
        if inside > 0:
            total += inside
            found = True
    mpx = ctx.client.mpx_in(ctx.tw0, ctx.tw1)
    return total * 1e3 / mpx if found and mpx else None
