"""DICOM store benchmark: indexed WADO, STOW/QIDO, crash rebuild.

WADO section (the acceptance-gated one): a 256-frame encapsulated WSM
instance is served frame-by-frame two ways —

- **reparse baseline** — every fetch runs ``read_part10(blob)[1][i]``,
  i.e. a full Part-10 parse materializing all 256 frames to return one
  (what the seed ``DicomStoreService.retrieve_frame`` did);
- **indexed** — one :class:`~repro.wsi.dicom.Part10Index` scan, then each
  fetch is a single slice at the indexed offset; also measured through
  ``DicomStoreService.retrieve_frame`` (bucket read + LRU'd index).

Every frame is asserted byte-identical between the paths, and the indexed
path must be ≥ 10× faster per fetch (it is orders of magnitude faster —
O(frame) vs O(file)).

Store section: STOW throughput for converted study archives, re-STOW
idempotency (QIDO/WADO snapshots byte-identical), QIDO query latency, and
crash recovery — the index rebuilt from the bucket checkpoint + blob
rescan must serve a byte-identical snapshot.

Writes ``BENCH_store.json`` and prints a CSV summary. ``--fast`` shrinks
fetch counts/reps for the CI smoke; the byte-identity and ≥ 10× WADO
assertions are identical in both modes.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core import SimScheduler
from repro.core.storage import ObjectStore
from repro.launch.cache import enable_compile_cache
from repro.wsi.convert import ConvertOptions, convert_wsi_to_dicom
from repro.wsi.dicom import Part10Index, read_part10, write_part10
from repro.wsi.jpeg import encode_tile
from repro.wsi.slide import PSVReader, SyntheticScanner
from repro.wsi.store_service import DicomStoreService

N_FRAMES = 256


def _time_per(fn, n: int, reps: int) -> float:
    """Average seconds per op over ``reps`` rounds of ``n`` calls."""
    fn(0)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        for i in range(n):
            fn(i)
    return (time.perf_counter() - t0) / (reps * n)


def _make_instance(n_frames: int) -> bytes:
    """One encapsulated WSM instance with ``n_frames`` JPEG tile frames."""
    rd = PSVReader(SyntheticScanner(seed=11).scan(512, 512, 256))
    bh, bw = rd.grid
    jpgs = [encode_tile(rd.read_tile(r, c)[:64, :64])
            for r in range(bh) for c in range(bw)]
    frames = [jpgs[i % len(jpgs)] for i in range(n_frames)]
    side = int(n_frames ** 0.5) or 1
    return write_part10(frames=frames, rows=64, cols=64,
                        total_rows=side * 64, total_cols=side * 64)


def _wado_section(fetches: int, reps: int) -> dict:
    blob = _make_instance(N_FRAMES)

    # all three paths must serve byte-identical frames
    ref_frames = read_part10(blob)[1]
    idx = Part10Index(blob)
    assert [idx.read_frame(i) for i in range(N_FRAMES)] == ref_frames, \
        "indexed frames diverge from read_part10"

    sched = SimScheduler()
    svc = DicomStoreService(ObjectStore(sched).bucket("dicom"), sched)
    sop = svc.store_instance(blob)
    assert [svc.retrieve_frame(sop, i) for i in range(N_FRAMES)] \
        == ref_frames, "store-served frames diverge from read_part10"

    fetches = min(fetches, N_FRAMES)
    t_reparse = _time_per(lambda i: read_part10(blob)[1][i], fetches,
                          max(1, reps // 2))
    t_indexed = _time_per(idx.read_frame, fetches, reps)
    t_store = _time_per(lambda i: svc.retrieve_frame(sop, i), fetches, reps)
    speedup = t_reparse / t_indexed
    store_speedup = t_reparse / t_store
    assert speedup >= 10.0, \
        f"indexed WADO only {speedup:.1f}x over reparse-per-fetch (< 10x)"
    return {
        "n_frames": N_FRAMES,
        "instance_bytes": len(blob),
        "fetches": fetches,
        "reparse_us_per_frame": t_reparse * 1e6,
        "indexed_us_per_frame": t_indexed * 1e6,
        "store_us_per_frame": t_store * 1e6,
        "indexed_speedup": speedup,
        "store_speedup": store_speedup,
        "bytes_identical": True,
    }


def _qido_wado_snapshot(svc: DicomStoreService, *, frames_per: int = 1,
                        drop: tuple[str, ...] = ()) -> dict:
    """Everything QIDO/WADO serve, for byte-identity comparisons."""
    snap = {}
    for study in svc.search_studies():
        snap[study] = {
            "summary": svc.study_summary(study),
            "series": svc.search_series(study),
            "instances": [
                {**{k: v for k, v in m.items() if k not in drop},
                 "blob": svc.retrieve(m["sop_instance_uid"]),
                 "frames": [svc.retrieve_frame(m["sop_instance_uid"], i)
                            for i in range(min(m["frames"] or 0,
                                               frames_per))]}
                for m in svc.search_instances(study)],
        }
    return snap


def _store_section(n_studies: int, slide: int) -> dict:
    archives = {
        f"studies/s{i:02d}.tar":
            convert_wsi_to_dicom(
                SyntheticScanner(seed=40 + i).scan(slide, slide, 256),
                {"slide_id": f"S{i}"},
                options=ConvertOptions(min_level_size=slide // 2))
        for i in range(n_studies)}

    sched = SimScheduler()
    bucket = ObjectStore(sched).bucket("dicom")
    svc = DicomStoreService(bucket, sched)

    t0 = time.perf_counter()
    for key, archive in archives.items():
        svc.store_study_archive(key, archive)
    t_stow = time.perf_counter() - t0
    clean = _qido_wado_snapshot(svc)

    # re-STOW everything: idempotent, snapshot byte-identical
    t0 = time.perf_counter()
    for key, archive in archives.items():
        svc.store_study_archive(key, archive)
    t_restow = time.perf_counter() - t0
    assert _qido_wado_snapshot(svc) == clean, \
        "re-STOW changed QIDO/WADO results"

    # QIDO latency over the filled store
    t0 = time.perf_counter()
    n_hits = sum(len(svc.search_instances(s))
                 for s in svc.search_studies(modality="SM"))
    t_qido = time.perf_counter() - t0

    # crash: a fresh service over the same bucket rebuilds from the
    # checkpoint + blob rescan and serves a byte-identical snapshot
    svc2 = DicomStoreService(bucket, sched)
    t0 = time.perf_counter()
    reparsed = svc2.rebuild_index()
    t_rebuild = time.perf_counter() - t0
    assert _qido_wado_snapshot(svc2) == clean, \
        "crash rebuild changed QIDO/WADO results"

    n_instances = sum(len(s["instances"]) for s in clean.values())
    return {
        "n_studies": n_studies,
        "n_instances": n_instances,
        "stow_ms_per_study": t_stow / n_studies * 1e3,
        "restow_ms_per_study": t_restow / n_studies * 1e3,
        "qido_ms": t_qido * 1e3,
        "qido_instances_matched": n_hits,
        "rebuild_ms": t_rebuild * 1e3,
        "rebuild_reparsed": reparsed,
        "restow_identical": True,
        "rebuild_identical": True,
    }


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: fewer fetches/studies, same assertions")
    args = ap.parse_args(argv)
    enable_compile_cache()
    fetches = 64 if args.fast else N_FRAMES
    reps = 1 if args.fast else 3
    n_studies = 3 if args.fast else 8
    slide = 512

    wado = _wado_section(fetches, reps)
    store = _store_section(n_studies, slide)
    result = {"wado": wado, "store": store}
    with open("BENCH_store.json", "w") as f:
        json.dump(result, f, indent=2)

    print("name,value,derived")
    print(f"wado_reparse_us,{wado['reparse_us_per_frame']:.0f},"
          f"{wado['n_frames']}frames/{wado['instance_bytes']}B")
    print(f"wado_indexed_us,{wado['indexed_us_per_frame']:.2f},"
          f"speedup={wado['indexed_speedup']:.0f}x "
          f"identical={wado['bytes_identical']}")
    print(f"wado_store_us,{wado['store_us_per_frame']:.2f},"
          f"speedup={wado['store_speedup']:.0f}x")
    print(f"stow_ms_per_study,{store['stow_ms_per_study']:.1f},"
          f"{store['n_studies']}studies/{store['n_instances']}instances")
    print(f"restow_ms_per_study,{store['restow_ms_per_study']:.1f},"
          f"identical={store['restow_identical']}")
    print(f"qido_ms,{store['qido_ms']:.2f},"
          f"matched={store['qido_instances_matched']}")
    print(f"rebuild_ms,{store['rebuild_ms']:.1f},"
          f"reparsed={store['rebuild_reparsed']} "
          f"identical={store['rebuild_identical']}")
    print("wrote BENCH_store.json")


if __name__ == "__main__":
    main()
