"""Conversion hot-path benchmark: batched/pipelined/concurrent A/Bs.

Single-slide section (synthetic 1024² slide, 16 tiles of 256²):

- per-stage µs of the batched path — transform dispatch (one fused
  ``jpeg_transform`` per level), host entropy coding (vectorized symbol
  stream), DICOM Part-10 wrap;
- the same 256×256 tile encode through both paths (per-tile vs batched);
- end-to-end slide conversion MPix/s: per-tile vs batched-sync vs pipelined.

Multi-slide section (the paper's batch-conversion scenario):

- **sync** — slides converted one after another, ``pipelined=False``;
- **pipelined** — same serial order, the overlapping engine;
- **pipelined + concurrent** — the batch pushed through the real
  event-driven wiring (landing bucket → pub/sub → autoscaled service →
  DICOM store) with ``concurrency`` parallel real conversions per instance.

Mixed-format section (the paper's scanner-interoperability scenario):
every slide delivered twice — as PSV and as SVS-shaped tiled TIFF — into
one landing bucket served by one sniffing deployment; each pair's study
tars are asserted byte-identical.

Byte-identity is asserted across all three: every study tar (UIDs seeded
per slide) must be identical bit-for-bit, so the speedups cannot come from
computing something different.

On the CPU the kernels' ``auto`` implementation is the jnp oracle (on a
TPU it is the native Pallas kernel), so CPU numbers time the oracle: the
batched transform and the per-tile baseline both run jnp graphs.

Writes ``BENCH_convert.json`` into the working directory and prints a CSV
summary (same format as the other benchmark modules). ``--fast`` shrinks
sizes/reps for the CI smoke (same assertions, looser timings).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core import ConversionPipeline, RealScheduler
from repro.kernels import jpeg_transform
from repro.launch.cache import enable_compile_cache
from repro.wsi.convert import (TRANSFER_STATS, ConvertOptions,
                               convert_wsi_to_dicom)
from repro.wsi.dicom import TS_JPEG_BASELINE, new_uid, write_part10
from repro.wsi.jpeg import encode_coef_batch, encode_tile, encode_tiles_batch
from repro.wsi.slide import PSVReader, SyntheticScanner

MIXED_FORMATS = ("psv", "tiff")

SLIDE, TILE = 1024, 256


def _time(fn, reps=5) -> float:
    """Warm then average wall seconds per call."""
    fn()
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _single_slide(slide: int, reps: int) -> dict:
    psv = SyntheticScanner(seed=0).scan(slide, slide, TILE)
    rd = PSVReader(psv)
    bh, bw = rd.grid
    tiles = np.stack([rd.read_tile(r, c)
                      for r in range(bh) for c in range(bw)])
    n_tiles = tiles.shape[0]
    chw = np.transpose(tiles, (0, 3, 1, 2)).astype(np.float32)

    # --- stage timings (whole level = all tiles) -----------------------
    t_transform = _time(lambda: np.asarray(jpeg_transform(chw)))
    coef = np.asarray(jpeg_transform(chw))
    t_entropy = _time(lambda: encode_coef_batch(coef))
    frames = encode_coef_batch(coef)
    suid, seuid = new_uid(), new_uid()
    t_wrap = _time(lambda: write_part10(
        frames=frames, rows=TILE, cols=TILE, total_rows=slide,
        total_cols=slide, transfer_syntax=TS_JPEG_BASELINE,
        study_uid=suid, series_uid=seuid, instance_number=1,
        metadata={0: "bench", 1: "level=0"}))

    # --- the 256×256 tile encode A/B ----------------------------------
    t_per_tile = _time(lambda: [encode_tile(t) for t in tiles], reps=reps)
    t_batched = _time(lambda: encode_tiles_batch(tiles), reps=reps)
    per_frames = [encode_tile(t) for t in tiles]
    bat_frames = encode_tiles_batch(tiles)
    identical = all(a == b for a, b in zip(per_frames, bat_frames))
    assert identical, "batched JPEG bytes diverge from the per-tile path"
    speedup = t_per_tile / t_batched

    # --- end-to-end slide conversion: per-tile / sync / pipelined ------
    # (interleaved best-of rounds: container drift hits all variants alike)
    mpix = slide * slide / 1e6
    # fresh ConvertOptions per call: a reused one resumes from its manifest
    variants = {"sync": dict(pipelined=False),
                "pipe": dict(pipelined=True),
                "per_tile": dict(batched=False)}
    best = {k: float("inf") for k in variants}
    for k, kw in variants.items():  # warm jit caches
        convert_wsi_to_dicom(psv, options=ConvertOptions(**kw))
    for _ in range(max(2, reps)):
        for k, kw in variants.items():
            t0 = time.perf_counter()
            convert_wsi_to_dicom(psv, options=ConvertOptions(**kw))
            best[k] = min(best[k], time.perf_counter() - t0)
    t_e2e_sync, t_e2e_pipe, t_e2e_p = (best["sync"], best["pipe"],
                                       best["per_tile"])

    # e2e byte identity with shared UIDs: pipelined ≡ sync
    uids = json.dumps([new_uid(), new_uid()])
    e2e_sync = convert_wsi_to_dicom(psv, options=ConvertOptions(
        pipelined=False, manifest={"uids": uids}))
    e2e_pipe = convert_wsi_to_dicom(psv, options=ConvertOptions(
        pipelined=True, manifest={"uids": uids}))
    assert e2e_pipe == e2e_sync, "pipelined study tar diverges from sync"

    # the fused-pyramid round-trip gate: one streamed upload and one
    # jitted dispatch per slide — the whole pixel pyramid stays on device
    TRANSFER_STATS.reset()
    convert_wsi_to_dicom(psv, options=ConvertOptions(pipelined=True))
    transfers = {"uploads": TRANSFER_STATS.uploads,
                 "dispatches": TRANSFER_STATS.dispatches,
                 "coef_fetches": TRANSFER_STATS.fetches}
    assert TRANSFER_STATS.uploads == 1 and TRANSFER_STATS.dispatches == 1, \
        f"fused engine issued extra host↔device round trips: {transfers}"

    return {
        "slide": {"hw": slide, "tile": TILE, "tiles": n_tiles},
        "stage_us": {
            "transform_dispatch": t_transform * 1e6,
            "entropy": t_entropy * 1e6,
            "dicom_wrap": t_wrap * 1e6,
        },
        "tile_encode_256": {
            "per_tile_us": t_per_tile / n_tiles * 1e6,
            "batched_us": t_batched / n_tiles * 1e6,
            "speedup": speedup,
            "bytes_identical": identical,
        },
        "dispatches_per_level": {"per_tile": 4 * n_tiles, "batched": 1},
        "fused_transfers": transfers,
        "end_to_end": {
            "per_tile_s": t_e2e_p,
            "sync_s": t_e2e_sync,
            "pipelined_s": t_e2e_pipe,
            "per_tile_mpix_s": mpix / t_e2e_p,
            "sync_mpix_s": mpix / t_e2e_sync,
            "pipelined_mpix_s": mpix / t_e2e_pipe,
            "pipelined_speedup_vs_sync": t_e2e_sync / t_e2e_pipe,
            "sync_speedup_vs_per_tile": t_e2e_p / t_e2e_sync,
            "bytes_identical": True,
        },
    }


def _multi_slide(n_slides: int, slide: int, reps: int,
                 concurrency: int | None = None,
                 instances: int = 1) -> dict:
    """The batch A/B: serial sync vs serial pipelined vs event-driven
    concurrent, all byte-identical (per-slide seeded UIDs).

    ``concurrency`` defaults to ``cores // 2`` (min 1): each pipelined
    conversion already keeps ~2 threads busy (XLA pool + host entropy
    coder), so running more conversions than that in parallel just
    thrashes the cores and the GIL. The chosen value is recorded in the
    JSON so the A/B is interpretable across machines.
    """
    if concurrency is None:
        concurrency = max(1, (os.cpu_count() or 2) // 2)
    slides = {f"slides/s{i}.psv":
              SyntheticScanner(seed=100 + i).scan(slide, slide, TILE)
              for i in range(n_slides)}
    uids = {k: json.dumps([new_uid(), new_uid()]) for k in slides}

    def convert_one(key: str, data: bytes, pipelined: bool) -> bytes:
        opt = ConvertOptions(pipelined=pipelined,
                             manifest={"uids": uids[key]})
        return convert_wsi_to_dicom(data, {"slide_id": key}, options=opt)

    # warm the jit caches once so all variants time steady-state work
    k0, v0 = next(iter(slides.items()))
    convert_one(k0, v0, False)
    convert_one(k0, v0, True)

    def run_serial(pipelined: bool) -> tuple[float, dict]:
        t0 = time.perf_counter()
        outs = {k: convert_one(k, v, pipelined) for k, v in slides.items()}
        return time.perf_counter() - t0, outs

    def run_concurrent() -> tuple[float, dict]:
        sched = RealScheduler(workers=2 * instances * concurrency)
        # subscribers=False: this bench isolates the conversion wiring —
        # the store's validation/ML fan-out (which would compete for the
        # same cores mid-batch) is benchmarked by store_bench instead
        pipe = ConversionPipeline(
            sched,
            convert=lambda data, meta: convert_one(meta["slide_id"], data,
                                                   True),
            max_instances=instances, concurrency=concurrency,
            cold_start=0.0, scale_down_delay=5.0, subscribers=False,
        )
        # time until the last study is stored — not until the service has
        # also scaled back to zero (idle wind-down is not batch runtime)
        t0 = time.perf_counter()
        outs = pipe.run_batch(slides)
        dt = time.perf_counter() - t0
        sched.shutdown()
        return dt, outs

    # interleave the variants across rounds so drift on a shared container
    # hits all three equally; keep the best round of each (same number of
    # rounds per variant — an uneven best-of would bias the minima)
    t_sync = t_pipe = t_conc = float("inf")
    outs_sync = outs_pipe = outs_conc = None
    for _ in range(reps):
        dt, outs_sync = run_serial(False)
        t_sync = min(t_sync, dt)
        dt, outs_pipe = run_serial(True)
        t_pipe = min(t_pipe, dt)
        dt, outs_conc = run_concurrent()
        t_conc = min(t_conc, dt)
    assert outs_pipe == outs_sync, "pipelined batch diverges from sync"
    assert outs_conc == outs_sync, "concurrent batch diverges from sync"

    mpix = n_slides * slide * slide / 1e6
    return {
        "n_slides": n_slides,
        "hw": slide,
        "concurrency": concurrency,
        "max_instances": instances,
        "sync_s": t_sync,
        "pipelined_s": t_pipe,
        "concurrent_s": t_conc,
        "sync_mpix_s": mpix / t_sync,
        "pipelined_mpix_s": mpix / t_pipe,
        "concurrent_mpix_s": mpix / t_conc,
        "pipelined_speedup": t_sync / t_pipe,
        "concurrent_speedup": t_sync / t_conc,
        "bytes_identical": True,
    }


def _mixed_format(n_slides: int, slide: int,
                  concurrency: int | None = None) -> dict:
    """The mixed-format landing bucket: every slide rendered once, delivered
    twice — as PSV and as SVS-shaped tiled TIFF — through the real
    event-driven wiring. One deployment sniffs and serves both containers,
    and each PSV/TIFF pair (same pixels, seeded UIDs) must produce
    byte-identical study tars, so format support cannot come from a
    different compute path."""
    if concurrency is None:
        concurrency = max(1, (os.cpu_count() or 2) // 2)
    scanners = {f"s{i}": SyntheticScanner(seed=300 + i)
                for i in range(n_slides)}
    slides, metadata = {}, {}
    container_bytes = {f: 0 for f in MIXED_FORMATS}
    for sid, sc in scanners.items():
        for fmt in MIXED_FORMATS:
            blob = (sc.scan(slide, slide, TILE) if fmt == "psv"
                    else sc.scan_tiff(slide, slide, TILE))
            key = f"{fmt}/{sid}.{fmt}"
            slides[key] = blob
            metadata[key] = {"slide_id": sid}
            container_bytes[fmt] += len(blob)
    uids = {sid: json.dumps([new_uid(), new_uid()]) for sid in scanners}

    def convert(data, meta):
        opt = ConvertOptions(manifest={"uids": uids[meta["slide_id"]]})
        return convert_wsi_to_dicom(data, {"slide_id": meta["slide_id"]},
                                    options=opt)

    convert(next(iter(slides.values())), {"slide_id": "s0"})  # warm jit
    sched = RealScheduler(workers=2 * concurrency)
    pipe = ConversionPipeline(
        sched, convert=convert, max_instances=1, concurrency=concurrency,
        cold_start=0.0, scale_down_delay=5.0, subscribers=False,
    )
    t0 = time.perf_counter()
    outs = pipe.run_batch(slides, metadata)
    dt = time.perf_counter() - t0
    sched.shutdown()
    for sid in scanners:
        assert outs[f"psv/{sid}.psv"] == outs[f"tiff/{sid}.tiff"], \
            f"{sid}: TIFF study tar diverges from the PSV delivery"
    fmt_counts = {f: int(pipe.metrics.get(f"pipeline.format.{f}"))
                  for f in MIXED_FORMATS}
    assert fmt_counts == {f: n_slides for f in MIXED_FORMATS}
    mpix = len(slides) * slide * slide / 1e6
    return {
        "n_slides": len(slides),
        "hw": slide,
        "concurrency": concurrency,
        "formats_converted": fmt_counts,
        "container_bytes": container_bytes,
        "batch_s": dt,
        "mpix_s": mpix / dt,
        "cross_format_bytes_identical": True,
    }


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller slides, fewer reps, same "
                         "byte-identity assertions")
    args = ap.parse_args(argv)
    enable_compile_cache()
    slide = 512 if args.fast else SLIDE
    reps = 1 if args.fast else 3
    n_slides = 3 if args.fast else 4

    single = _single_slide(slide, reps)
    multi = _multi_slide(n_slides, slide, reps)
    mixed = _mixed_format(2 if args.fast else 3, slide)
    result = {**single, "multi_slide": multi, "mixed_format": mixed}
    with open("BENCH_convert.json", "w") as f:
        json.dump(result, f, indent=2)

    st, te, e2e, ms = (result["stage_us"], result["tile_encode_256"],
                       result["end_to_end"], multi)
    n_tiles = result["slide"]["tiles"]
    print("name,value,derived")
    print(f"transform_dispatch_us,{st['transform_dispatch']:.0f},"
          f"{n_tiles}tiles/1dispatch")
    print(f"entropy_us,{st['entropy']:.0f},vectorized")
    print(f"dicom_wrap_us,{st['dicom_wrap']:.0f},part10")
    print(f"tile_encode_per_tile_us,{te['per_tile_us']:.0f},baseline")
    print(f"tile_encode_batched_us,{te['batched_us']:.0f},"
          f"speedup={te['speedup']:.2f}x identical={te['bytes_identical']}")
    print(f"e2e_sync_mpix_s,{e2e['sync_mpix_s']:.2f},"
          f"per_tile={e2e['per_tile_mpix_s']:.2f}")
    print(f"e2e_pipelined_mpix_s,{e2e['pipelined_mpix_s']:.2f},"
          f"speedup_vs_sync={e2e['pipelined_speedup_vs_sync']:.2f}x")
    tr = result["fused_transfers"]
    print(f"fused_transfers,ok,uploads={tr['uploads']} "
          f"dispatches={tr['dispatches']} "
          f"coef_fetches={tr['coef_fetches']}")
    print(f"batch_sync_s,{ms['sync_s']:.3f},{ms['n_slides']}x{ms['hw']}²")
    print(f"batch_pipelined_s,{ms['pipelined_s']:.3f},"
          f"speedup={ms['pipelined_speedup']:.2f}x")
    print(f"batch_concurrent_s,{ms['concurrent_s']:.3f},"
          f"speedup={ms['concurrent_speedup']:.2f}x "
          f"identical={ms['bytes_identical']}")
    mx = mixed
    print(f"mixed_format_batch_s,{mx['batch_s']:.3f},"
          f"{mx['n_slides']}slides:" +
          "+".join(f"{n}x{f}" for f, n in mx['formats_converted'].items()) +
          f" cross_format_identical={mx['cross_format_bytes_identical']}")
    print("wrote BENCH_convert.json")


if __name__ == "__main__":
    main()
