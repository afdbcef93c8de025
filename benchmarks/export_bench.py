"""Export subsystem benchmark: batched inverse JPEG path + dicom2tiff e2e.

Decode section (the acceptance-gated one): every tile of a pyramid level
is decoded two ways —

- **per-tile (seed)** — ``[decode_tile(j) for j in frames]``: a per-symbol
  Python Huffman loop plus one fused inverse dispatch per tile;
- **batched** — ``decode_tiles_batch(frames)``: the lockstep vectorized
  entropy decoder (one numpy step per symbol *position* across the whole
  level) plus a single fused ``jpeg_inverse`` dispatch.

Pixel identity between the two paths and coefficient-exact
``decode_coef_batch ∘ encode_coef_batch`` are asserted; the speedup is
recorded and must exceed 1x at **every** ``batch_scaling`` point, small
batches included (the jitted lockstep entropy engine keeps 16-tile levels
ahead of the per-tile loop — the old numpy lockstep lost there at 0.82x).
Bigger levels (and multi-frame WADO pulls) still win more.

Export section: a synthetic slide is converted, STOWed into a
``DicomStoreService``, and exported to a tiled-TIFF pyramid through
``ExportService`` (QIDO + frame-level WADO reads). Asserts, in both
modes: repeated export is byte-identical, export after a simulated crash
(fresh service + ``rebuild_index()``) is byte-identical, every exported
TIFF reopens through the ``open_slide`` sniffer, and the level-0 TIFF
survives a full-circle re-conversion into a new DICOM study.

Writes ``BENCH_export.json`` and prints a CSV summary. ``--fast`` shrinks
the decode workload for the CI smoke; every assertion is identical.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import SimScheduler
from repro.core.storage import ObjectStore
from repro.kernels import jpeg_transform
from repro.launch.cache import enable_compile_cache
from repro.wsi.convert import ConvertOptions, convert_wsi_to_dicom
from repro.wsi.export import ExportService
from repro.wsi.formats import open_slide
from repro.wsi.jpeg import (decode_coef_batch, decode_tile,
                            decode_tiles_batch, encode_coef_batch)
from repro.wsi.slide import PSVReader, SyntheticScanner
from repro.wsi.store_service import DicomStoreService

TILE = 256


def _level_frames(hw: int, seed: int = 3) -> tuple[list[bytes], np.ndarray]:
    """One pyramid level's JPEG frames (+ their exact coefficients)."""
    rd = PSVReader(SyntheticScanner(seed=seed).scan(hw, hw, TILE))
    bh, bw = rd.grid
    tiles = np.stack([rd.read_tile(r, c)
                      for r in range(bh) for c in range(bw)])
    chw = np.transpose(tiles, (0, 3, 1, 2)).astype(np.float32)
    coef = np.asarray(jpeg_transform(chw))
    return encode_coef_batch(coef), coef


def _decode_section(hw: int, scaling_ns: list[int]) -> dict:
    frames, coef = _level_frames(hw)
    n = len(frames)

    # entropy decode∘encode must be coefficient-exact
    assert (decode_coef_batch(frames) == coef).all(), \
        "decode_coef_batch diverges from the encoded coefficients"

    # warm both paths (the fused inverse jits per batch shape)
    decode_tile(frames[0])
    decode_tiles_batch(frames)

    t0 = time.perf_counter()
    per = [decode_tile(j) for j in frames]
    t_per = time.perf_counter() - t0
    t0 = time.perf_counter()
    bat = decode_tiles_batch(frames)
    t_bat = time.perf_counter() - t0
    assert (np.stack(per) == bat).all(), \
        "batched decode diverges from the per-tile loop"
    speedup = t_per / t_bat
    assert speedup > 1.0, \
        f"batched decode only {speedup:.2f}x over per-tile (< 1x) at n={n}"

    scaling = []
    for sn in scaling_ns:
        if sn > n:
            continue
        sub = frames[:sn]
        decode_tiles_batch(sub)  # warm this batch shape's jit
        t0 = time.perf_counter()
        p = [decode_tile(j) for j in sub]
        tp = time.perf_counter() - t0
        t0 = time.perf_counter()
        b = decode_tiles_batch(sub)
        tb = time.perf_counter() - t0
        assert (np.stack(p) == b).all()
        # the small-batch cliff gate: the batched path must win at EVERY
        # batch size, not just whole-level batches (the jitted lockstep
        # entropy engine is what holds this at n=16 — see wsi/entropy_jax)
        assert tp / tb > 1.0, \
            f"batched decode only {tp / tb:.2f}x over per-tile at n={sn}"
        scaling.append({"n_tiles": sn, "per_tile_us": tp / sn * 1e6,
                        "batched_us": tb / sn * 1e6, "speedup": tp / tb})

    return {
        "hw": hw,
        "tile": TILE,
        "n_tiles": n,
        "per_tile_us": t_per / n * 1e6,
        "batched_us": t_bat / n * 1e6,
        "speedup": speedup,
        "pixel_identical": True,
        "coef_roundtrip_exact": True,
        "batch_scaling": scaling,
    }


def _snapshot(derived) -> dict:
    return {k: derived.get(k).data for k in derived.list()}


def _export_section(slide_hw: int) -> dict:
    psv = SyntheticScanner(seed=21).scan(slide_hw, slide_hw, TILE)
    archive = convert_wsi_to_dicom(
        psv, {"slide_id": "bench"}, options=ConvertOptions())

    sched = SimScheduler()
    store = ObjectStore(sched)
    svc = DicomStoreService(store.bucket("dicom"), sched)
    svc.store_study_archive("studies/bench.tar", archive)
    (study,) = svc.search_studies()
    exporter = ExportService(svc, store.bucket("derived"))

    t0 = time.perf_counter()
    keys = exporter.export_study(study)
    t_export = time.perf_counter() - t0
    clean = _snapshot(exporter.derived)
    frames_decoded = int(
        svc.metrics.get("pipeline.export.frames_decoded"))

    # repeated export, full re-derivation forced: byte-identical TIFFs
    # (idempotent bucket no-ops) — proves determinism, not just the
    # generation-skip shortcut
    t0 = time.perf_counter()
    exporter.export_study(study, skip_unchanged=False)
    t_re = time.perf_counter() - t0
    assert _snapshot(exporter.derived) == clean, \
        "repeated export changed derived TIFF bytes"

    # default path: unchanged levels are skipped without fetch/decode
    exporter.export_study(study)
    assert svc.metrics.get("pipeline.export.levels_unchanged") \
        == len(keys), "generation-skip did not engage on re-export"
    assert _snapshot(exporter.derived) == clean

    # simulated crash: a fresh service over the same bucket, index rebuilt
    # from the checkpoint + blob rescan, must export byte-identically
    svc2 = DicomStoreService(store.bucket("dicom"), sched)
    svc2.rebuild_index()
    exporter2 = ExportService(svc2, store.bucket("derived2"))
    exporter2.export_study(study)
    assert _snapshot(exporter2.derived) == \
        {k: v for k, v in clean.items()}, \
        "post-rebuild export changed derived TIFF bytes"

    # every exported level reopens through the format sniffer
    total_px = 0
    for key in keys:
        rd = open_slide(exporter.derived.get(key).data)
        total_px += rd.H * rd.W
        assert rd.tile == TILE and rd.metadata.get("study") == study

    # full circle: the exported level-0 TIFF re-converts into a new study
    tif0 = exporter.derived.get(keys[0]).data
    circle = convert_wsi_to_dicom(tif0, {"slide_id": "full-circle"})

    return {
        "slide_hw": slide_hw,
        "levels_exported": len(keys),
        "frames_decoded": frames_decoded,
        "export_s": t_export,
        "reexport_s": t_re,
        "mpix_s": total_px / 1e6 / t_export,
        "tiff_bytes": sum(len(v) for v in clean.values()),
        "repeat_identical": True,
        "rebuild_identical": True,
        "reopens_via_sniffer": True,
        "full_circle_bytes": len(circle),
    }


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller level/slide, same assertions")
    args = ap.parse_args(argv)
    enable_compile_cache()
    decode_hw = 2048 if args.fast else 4096
    scaling_ns = [16, 64] if args.fast else [16, 64, 256]
    slide_hw = 512 if args.fast else 1024

    decode = _decode_section(decode_hw, scaling_ns)
    export = _export_section(slide_hw)
    result = {"decode": decode, "export": export}
    with open("BENCH_export.json", "w") as f:
        json.dump(result, f, indent=2)

    print("name,value,derived")
    print(f"decode_per_tile_us,{decode['per_tile_us']:.0f},"
          f"{decode['n_tiles']}tiles/{decode['hw']}^2")
    print(f"decode_batched_us,{decode['batched_us']:.0f},"
          f"speedup={decode['speedup']:.2f}x "
          f"pixel_identical={decode['pixel_identical']} "
          f"coef_exact={decode['coef_roundtrip_exact']}")
    for s in decode["batch_scaling"]:
        print(f"decode_scaling_n{s['n_tiles']},{s['speedup']:.2f}x,"
              f"{s['batched_us']:.0f}us/tile")
    print(f"export_s,{export['export_s']:.3f},"
          f"{export['levels_exported']}levels/{export['slide_hw']}^2 "
          f"{export['mpix_s']:.2f}MPix/s")
    print(f"reexport_s,{export['reexport_s']:.3f},"
          f"identical={export['repeat_identical']}")
    print(f"rebuild_export,ok,identical={export['rebuild_identical']}")
    print(f"full_circle,ok,{export['full_circle_bytes']}B study tar "
          f"from the exported TIFF")
    print("wrote BENCH_export.json")


if __name__ == "__main__":
    main()
