"""Reduction of a profiler trace to device busy time, per-program device
time and the host's activity in the device's idle gaps.

The benchmark traces one window with ``tpu_trace_mode=TRACE_ONLY_XLA``:
each device plane's ``XLA Modules`` line then holds one event per program
execution (``jit_<name>(<fingerprint>)``), which is all the reduction
needs, and a long ``while_loop`` stays one event instead of millions. The
window itself is a host annotation (``WINDOW``) in the same trace, so the
device timeline and the host clock line up through it.
"""
from __future__ import annotations

import re
from collections import defaultdict

#: host annotation that spans the traced window
WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # user annotations only
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    return opts


def load(path: str) -> dict:
    """Module executions per device plane, and the window annotation.

    Returns ``{"devices": {plane: [(name, start_ns, dur_ns)]},
    "window": (start_ns, dur_ns) or None}``.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    window = None
    for plane in pd.planes:
        if _DEVICE_PLANE.fullmatch(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    evs += [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.duration_ns)
    return {"devices": devices, "window": window}


def program(name: str) -> str:
    """``jit_chain(1216…)`` -> ``jit_chain``."""
    return _FINGERPRINT.sub("", name)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(raw: dict) -> dict | None:
    """Clip every device plane's executions to the window.

    Returns ``None`` where the trace holds no window or no device
    execution; otherwise ``window_s``, ``busy_s`` (union of executions,
    averaged over the device planes), per plane ``events`` (full name,
    start, end in seconds from the window's start) and ``gaps`` of the
    first plane.
    """
    if raw["window"] is None:
        return None
    w0, wd = raw["window"]
    w1 = w0 + wd
    planes = {}
    for plane, evs in sorted(raw["devices"].items()):
        clipped = [(n, (max(s, w0) - w0) * 1e-9, (min(s + d, w1) - w0) * 1e-9)
                   for n, s, d in evs if s < w1 and s + d > w0]
        if clipped:
            planes[plane] = sorted(clipped, key=lambda e: e[1])
    if not planes:
        return None
    busy = []
    for evs in planes.values():
        busy.append(sum(b - a for a, b in _union([(a, b) for _, a, b in evs])))
    first = next(iter(planes.values()))
    union = _union([(a, b) for _, a, b in first])
    edges = [0.0] + [x for ab in union for x in ab] + [wd * 1e-9]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return {"window_s": wd * 1e-9, "busy_s": sum(busy) / len(busy),
            "events": planes, "gaps": gaps}


def program_time(red: dict, name: str) -> dict[str, list[float]]:
    """Durations in the window of each compiled variant (full name) of the
    program ``name``, on every plane."""
    out: dict[str, list[float]] = defaultdict(list)
    for evs in red["events"].values():
        for n, a, b in evs:
            if program(n) == name:
                out[n].append(b - a)
    return dict(out)


def breakdown(red: dict, spans: list[dict], t0: float,
              top: int = 10) -> dict:
    """The device programs that took most time, and the longest idle gaps
    labelled with the host span that covers most of each.

    ``spans`` are the program's span dicts on the host clock; ``t0`` is the
    host time of the window's start.
    """
    per: dict[str, float] = defaultdict(float)
    for evs in red["events"].values():
        for n, a, b in evs:
            per[program(n)] += b - a
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    leaf = [(s["name"], s["start"] - t0, s["end"] - t0) for s in spans
            if s.get("end") is not None and s["name"] in LEAF_SPANS]
    labelled = []
    for a, b in sorted(red["gaps"], key=lambda g: g[0] - g[1])[:top]:
        cover: dict[str, float] = defaultdict(float)
        for name, sa, sb in leaf:
            ov = min(b, sb) - max(a, sa)
            if ov > 0:
                cover[name] += ov
        label = max(cover, key=cover.get) if cover else "no span"
        labelled.append([label, b - a])
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": labelled}


#: the program's spans that name one layer's own work
LEAF_SPANS = ("pipeline.fetch", "convert.upload", "convert.dispatch",
              "convert.entropy", "convert.pack", "pipeline.store",
              "stow.archive", "export.study")


def assign_by_duration(groups: dict[str, list[float]],
                       works: list[float]) -> dict[str, float] | None:
    """Match compiled variants of one program to the shapes it ran on.

    Each compiled variant (one fingerprint) is one shape. Variants sorted
    by their mean device time pair with the shapes' work sorted the same
    way. Returns ``{variant: work}``, or ``None`` where the counts differ.
    """
    works = sorted(set(works))
    if len(groups) != len(works):
        return None
    order = sorted(groups, key=lambda g: sum(groups[g]) / len(groups[g]))
    return dict(zip(order, works))
