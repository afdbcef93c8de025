"""Record the small chip trace kept as ``fixtures/convert_named.xplane.pb``.

    python3 bench/tests/record_fixture.py --out <dir>

On one TPU: one 1024^2 slide converted (the pyramid program with the
``jpeg_transform`` and ``downsample2x2`` kernels), one tile encoded by the
per-tile path (``rgb2ycbcr``, ``dct8x8_quant``) and one frame decoded
(``jpeg_inverse``), each warmed first, then once more inside a traced
window traced as the benchmark traces (``devtrace.profile_options``) with
the program's tracer armed on the profiler's annotation. Writes the trace
file to ``--out`` and prints, as the last line, the JSON of what
``hostplane.py`` reads from it; ``test_hostplane.py`` asserts those
numbers.
Without a TPU it exits 1.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

NAME = "convert_named.xplane.pb"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 1
    import devtrace
    import hostplane
    import scanner
    from repro.core import tracing
    from repro.wsi import convert_wsi_to_dicom, study_levels
    from repro.wsi.dicom import Part10Index
    from repro.wsi.jpeg import decode_tile, encode_tile

    pixels, tif = scanner.scan(1024, 1024, 256, 7.0)
    meta = {"slide_id": "landing/fixture.svs"}

    def work():
        tar = convert_wsi_to_dicom(tif, meta)
        level0 = study_levels(tar)["level_0.dcm"]
        frame = Part10Index(level0).read_frame(0)
        encode_tile(pixels[:256, :256])
        return decode_tile(frame)

    work()  # compile outside the trace
    out = Path(args.out)
    raw_dir = out / "raw"
    shutil.rmtree(raw_dir, ignore_errors=True)
    jax.profiler.start_trace(str(raw_dir),
                             profiler_options=devtrace.profile_options())
    tracer = tracing.arm(annotate=jax.profiler.TraceAnnotation)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            work()
    finally:
        tracing.disarm()
        jax.profiler.stop_trace()
    (path,) = raw_dir.rglob("*.xplane.pb")
    shutil.copy(path, out / NAME)
    shutil.rmtree(raw_dir)
    red = hostplane.reduce(hostplane.load(str(out / NAME)))
    print(json.dumps({"bytes": (out / NAME).stat().st_size,
                      **hostplane.report(red),
                      "armed": sorted({s.name for s in tracer.spans})}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
