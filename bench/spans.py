"""Helpers for metric readers: the program's spans and the client's
records, joined."""
from __future__ import annotations


def by_id(spans: list[dict]) -> dict[str, dict]:
    return {s["span_id"]: s for s in spans}


def key_of(span: dict, index: dict[str, dict]) -> str | None:
    """The landing key a span works for: the ``key`` attribute of the span
    or of its nearest ancestor that has one."""
    while span is not None:
        if "key" in span["attrs"]:
            return span["attrs"]["key"]
        span = index.get(span["parent_id"])
    return None


def finished(spans: list[dict], name: str) -> list[dict]:
    return [s for s in spans if s["name"] == name and s["end"] is not None]


def per_mpx_ms(ctx, name: str) -> float | None:
    """Milliseconds of span ``name`` per level-0 megapixel of the slides
    those spans worked for."""
    index = by_id(ctx.spans)
    slides = {s.key: s for s in ctx.client.slides}
    total_ms = 0.0
    mpx: dict[str, float] = {}
    for sp in finished(ctx.spans, name):
        s = slides.get(key_of(sp, index))
        if s is None:
            continue
        total_ms += (sp["end"] - sp["start"]) * 1e3
        mpx[s.key] = s.mpx
    return total_ms / sum(mpx.values()) if mpx else None


def idle_share(ctx) -> float | None:
    """Percent of the traced window in which no program ran on the device."""
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
