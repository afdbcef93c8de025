"""The program's spans and its named kernels, read from a profiler trace.

    python3 bench/hostplane.py <trace.xplane.pb>

``devtrace`` reduces a traced window to the device's programs and idle
gaps. This module reads two more things from the same file:

- the program's spans, where the tracer was armed with
  ``annotate=jax.profiler.TraceAnnotation`` (``repro.core.tracing``):
  each then lies in the trace's host plane, on the thread that ran it and
  on the device events' clock, so no second clock has to be joined;
- each Pallas kernel's executions, from the device's ``XLA Ops`` line,
  where the kernel was called with a ``name=`` (``%jpeg_transform.5 =
  ...``).

Each idle gap is labelled with the leaf span that covers most of it,
summed over threads, each thread's time going to its innermost leaf.
The command prints, as JSON, the window, the device time and executions
of each program, the device time of each named kernel, the count of each
leaf span, and the longest idle gaps so labelled. It reads a trace taken
as ``devtrace.profile_options`` takes it, with the ``devtrace.WINDOW``
annotation around the window.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

import devtrace

#: ``%<instruction>[.<n>] = ...``: the instruction's base name
_INSTRUCTION = re.compile(r"%([^ =]+?)(?:\.\d+)? = ")
#: the ``name=`` of every Pallas kernel of the program
KERNELS = ("jpeg_transform", "downsample2x2", "jpeg_inverse",
           "dct8x8_quant", "rgb2ycbcr")
#: the program's spans that name one layer's own work; where two nest on
#: one thread (``decode.*`` inside ``inference.score``), the inner one
#: takes the time
LEAF_SPANS = ("pipeline.fetch", "convert.upload", "convert.dispatch",
              "convert.fetch", "convert.encode", "convert.wrap",
              "convert.pack", "pipeline.store", "stow.archive",
              "export.query", "export.wado", "decode.parse",
              "decode.entropy", "decode.scatter", "decode.inverse",
              "export.tiff", "export.put", "validate.verify",
              "inference.score")


def load(path: str) -> dict:
    """``devtrace.load``'s programs and window, with ``kernels`` (named
    kernel executions per device plane, ``(kernel, start_ns, dur_ns)``),
    ``host`` (leaf spans, ``(thread, name, start_ns, dur_ns)``, where
    ``thread`` is ``<plane>/<line index>``) and ``ops`` (the trace's count
    of ``XLA Ops`` events)."""
    from jax.profiler import ProfileData

    raw = devtrace.load(path)
    kernels: dict[str, list] = {}
    host: list = []
    n_ops = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name in raw["devices"]:
            kev = kernels.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    n_ops += 1
                    m = _INSTRUCTION.match(e.name)
                    if m and m.group(1) in KERNELS:
                        kev.append((m.group(1), e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                host += [(f"{plane.name}/{i}", e.name, e.start_ns,
                          e.duration_ns)
                         for e in line.events if e.name in LEAF_SPANS]
    return {**raw, "kernels": kernels, "host": host, "ops": n_ops}


def reduce(raw: dict) -> dict | None:
    """``devtrace.reduce``, plus ``kernels`` (``(kernel, start, end)`` of
    every plane) and ``host`` (``(thread, span, start, end)``) clipped to
    the window the same way, in seconds from its start, and ``ops``."""
    red = devtrace.reduce(raw)
    if red is None:
        return None
    w0, wd = raw["window"]
    w1 = w0 + wd

    def clip(s, d):
        return (max(s, w0) - w0) * 1e-9, (min(s + d, w1) - w0) * 1e-9

    red["kernels"] = [(n, *clip(s, d)) for evs in raw["kernels"].values()
                      for n, s, d in evs if s < w1 and s + d > w0]
    red["host"] = [(th, n, *clip(s, d)) for th, n, s, d in raw["host"]
                   if s < w1 and s + d > w0]
    red["ops"] = raw["ops"]
    return red


def kernel_time(red: dict) -> dict[str, float]:
    """Device seconds of each named kernel in the window, summed over its
    executions on every plane, longest first."""
    per: dict[str, float] = defaultdict(float)
    for n, a, b in red["kernels"]:
        per[n] += b - a
    return dict(sorted(per.items(), key=lambda kv: -kv[1]))


def innermost(host: list[tuple]) -> list[tuple[str, float, float]]:
    """Per thread, the time of each leaf span that no other leaf span on
    that thread nests inside: ``(span, start, end)`` pieces.

    Annotations of one thread nest, so a sweep with a stack charges every
    instant to the innermost open span.
    """
    threads: dict[str, list] = defaultdict(list)
    for th, n, a, b in host:
        threads[th].append((n, a, b))
    out = []
    for evs in threads.values():
        stack: list[tuple[str, float, float]] = []
        t = 0.0
        for n, a, b in sorted(evs, key=lambda e: (e[1], -e[2])):
            while stack and stack[-1][2] <= a:
                top = stack.pop()
                out.append((top[0], t, top[2]))
                t = top[2]
            if stack:
                out.append((stack[-1][0], t, a))
            stack.append((n, a, min(b, stack[-1][2]) if stack else b))
            t = a
        while stack:
            top = stack.pop()
            out.append((top[0], t, top[2]))
            t = top[2]
    return [(n, a, b) for n, a, b in out if b > a]


def idle_gaps(red: dict, top: int = 10) -> list[list]:
    """The ``top`` longest idle gaps, longest first, as ``[label,
    seconds]``: the leaf span that covers most of the gap, summed over
    threads, or ``no span``."""
    leaf = innermost(red["host"])
    labelled = []
    for a, b in sorted(red["gaps"], key=lambda g: g[0] - g[1])[:top]:
        cover: dict[str, float] = defaultdict(float)
        for name, sa, sb in leaf:
            ov = min(b, sb) - max(a, sa)
            if ov > 0:
                cover[name] += ov
        labelled.append([max(cover, key=cover.get) if cover else "no span",
                         b - a])
    return labelled


def report(red: dict, top: int = 10) -> dict:
    """What the command prints for a reduced trace."""
    runs: dict[str, int] = defaultdict(int)
    for evs in red["events"].values():
        for n, _, _ in evs:
            runs[devtrace.program(n)] += 1
    spans: dict[str, int] = defaultdict(int)
    for _, n, _, _ in red["host"]:
        spans[n] += 1
    return {"window_s": red["window_s"], "busy_s": red["busy_s"],
            "programs": devtrace.breakdown(red, [], 0.0, top)["device_ops"],
            "executions": dict(runs), "kernels": kernel_time(red),
            "xla_ops_events": red["ops"], "spans": dict(spans),
            "idle_gaps": idle_gaps(red, top)}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    red = reduce(load(args[0]))
    if red is None:
        print("hostplane: no window or no device execution in the trace",
              file=sys.stderr)
        return 1
    print(json.dumps(report(red)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
