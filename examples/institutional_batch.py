"""Institutional-scale batch conversion — the paper's Figure 2/3 experiment,
with a real event-driven multi-slide batch up front.

    PYTHONPATH=src python examples/institutional_batch.py [--images 50]
        [--real-slides 4] [--real-size 1024] [--concurrency N]

What it demonstrates, and what to expect:

1. **Real mode** — ``--real-slides`` synthetic PSV slides are dropped into
   the landing bucket of a ``RealScheduler``-backed ``ConversionPipeline``;
   the event chain (object notification → pub/sub push → autoscaled
   wsi2dcm service) converts them with the pipelined JAX engine, up to
   ``--concurrency`` in parallel per instance (default: cores // 2).
   Prints the batch wall time vs a serial loop of the same conversions and
   verifies every study landed in the DICOM store. ``auto_export=True``
   closes the retrieval loop: every stored instance triggers the dicom2tiff
   export hop, and the final printout reports the ``pipeline.export.*``
   counters plus the derived-bucket tiled TIFFs.
2. **Paper scale** — the three workflows (serial, 16-way parallel VM pool,
   event-driven autoscaling) simulated at the paper's scale in the
   discrete-event simulator, calibrated by the measured real conversion,
   and the Figure-3 instance timeline for a 50-slide burst. Expect
   autoscaling to lose at n=1 (cold start) and win clearly by n≥10.
"""
import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.fig2_workflows import (autoscaling_time, measure_service_time,
                                       parallel_time, serial_time)
from benchmarks.fig3_autoscaling import run as fig3_run

from repro.core import ConversionPipeline, RealScheduler, tracing
from repro.core.dashboard import build_report
from repro.wsi import SyntheticScanner, convert_wsi_to_dicom, study_levels


def run_real_batch(n: int, size: int, concurrency: int) -> None:
    """Push a real multi-slide batch through the event-driven wiring."""
    scanner = SyntheticScanner(seed=42)
    slides = {f"slides/batch{i:03d}.psv": scanner.scan(size, size, 256)
              for i in range(n)}

    # warm the jit caches so neither run pays compile time
    convert_wsi_to_dicom(next(iter(slides.values())))

    # serial reference: the same slides, one at a time
    t0 = time.perf_counter()
    for key, psv in slides.items():
        convert_wsi_to_dicom(psv, {"slide_id": key})
    t_serial = time.perf_counter() - t0

    # one instance, `concurrency` parallel conversions: the per-instance
    # concurrency this PR adds (instance scale-out is what the paper-scale
    # simulation below demonstrates)
    sched = RealScheduler(workers=max(8, 4 * concurrency))
    with tracing.capture(now=sched.now) as tracer:
        pipe = ConversionPipeline(
            sched, convert=convert_wsi_to_dicom, max_instances=1,
            concurrency=concurrency, cold_start=0.0, scale_down_delay=5.0,
            auto_export=True,
        )
        t0 = time.perf_counter()
        pipe.run_batch(slides)
        t_batch = time.perf_counter() - t0
        sched.run(until=30.0)  # store ingest + subscribers + export drain

    print(f"real event-driven batch: {n} × {size}² slides, "
          f"concurrency={concurrency}")
    print(f"  serial loop      : {t_serial:6.2f}s")
    print(f"  event-driven     : {t_batch:6.2f}s "
          f"({t_serial / t_batch:.2f}× vs serial)")
    for key in pipe.dicom.list():
        study = study_levels(pipe.dicom.get(key).data)
        n_dcm = sum(1 for k in study if k.endswith(".dcm"))
        print(f"  gs://dicom-store/{key}: {n_dcm} levels, "
              f"{len(pipe.dicom.get(key).data):,} bytes")
    studies = pipe.store_service.search_studies()
    print(f"  enterprise store: {len(studies)} studies, "
          f"{sum(pipe.store_service.study_summary(s)['n_instances'] for s in studies)} instances | "
          f"validated: {len(pipe.validator.checked)}, "
          f"ml-scored: {len(pipe.ml_subscriber.predictions)}")
    g = pipe.metrics.get
    print(f"  dicom2tiff export (auto, event-driven): "
          f"requests={g('pipeline.export.requests'):g}, "
          f"frames decoded={g('pipeline.export.frames_decoded'):g}, "
          f"bytes written={g('pipeline.export.bytes_written'):,.0f}, "
          f"dead-lettered={g('pipeline.export.dead_lettered'):g}")
    print(f"  gs://wsi-derived: {len(pipe.derived.list())} level TIFFs "
          f"across {len(studies)} studies")
    print(f"  cold starts: {pipe.service.cold_starts}, "
          f"acks: {g('sub.wsi2dcm-push.acks'):g}")
    # the dashboard's per-slide critical path: where each slide's
    # end-to-end time went (broker/queue vs conversion vs store I/O)
    report = build_report(pipe.metrics, tracer, title="real batch")
    lat = report["histograms"].get("sub.wsi2dcm-push.latency", {})
    if lat:
        print(f"  delivery latency: p50={lat['p50']:.2f}s "
              f"p95={lat['p95']:.2f}s p99={lat['p99']:.2f}s")
    for t in report["traces"]:
        a, dur = t["attribution"], max(t["duration"], 1e-9)
        print(f"  trace {t['slide']}: {t['duration']:.2f}s = "
              f"queue {100 * a['queue'] / dur:.0f}% + "
              f"compute {100 * a['compute'] / dur:.0f}% + "
              f"store {100 * a['store'] / dur:.0f}% "
              f"({t['n_spans']} spans, "
              f"{'OK' if not t['problems'] else t['problems']})")
    print()
    sched.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=50,
                    help="batch size for the paper-scale simulation")
    ap.add_argument("--tau", type=float, default=90.0,
                    help="per-slide conversion seconds at paper scale")
    ap.add_argument("--real-slides", type=int, default=4,
                    help="slides in the real event-driven batch (0 skips)")
    ap.add_argument("--real-size", type=int, default=1024,
                    help="real slide edge length (pixels, multiple of 256)")
    ap.add_argument("--concurrency", type=int,
                    default=max(1, (os.cpu_count() or 2) // 2),
                    help="parallel conversions per instance in real mode")
    args = ap.parse_args()

    if args.real_slides > 0:
        run_real_batch(args.real_slides, args.real_size, args.concurrency)

    tau_m = measure_service_time()
    print(f"measured per-slide conversion (256² synthetic): {tau_m:.3f}s")
    print(f"simulating at paper scale with tau={args.tau}s\n")

    print(f"{'n':>4} {'serial':>10} {'parallel16':>11} {'autoscaling':>12}")
    for n in (1, 10, 25, args.images):
        s = serial_time(n, args.tau)
        p = parallel_time(n, args.tau)
        a = autoscaling_time(n, args.tau)
        print(f"{n:>4} {s:>9.0f}s {p:>10.0f}s {a:>11.0f}s")

    print("\nFigure 3 — avg instances per minute (50-slide burst):")
    minutes, pipe = fig3_run(n=args.images, tau=args.tau)
    for m, v in minutes:
        print(f"  {m:3d}m | {'#' * int(v)} {v:.0f}")
    print(f"\ncold starts: {pipe.service.cold_starts}, "
          f"conversions: {pipe.done_count()}")


if __name__ == "__main__":
    main()
