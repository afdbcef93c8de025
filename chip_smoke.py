"""Smoke run of the slide → DICOM → export path on a TPU.

    python3 chip_smoke.py              # the main path on one chip
    python3 chip_smoke.py --chips 4    # the sharded path on a four-chip host

One chip: three synthetic 8192×8192 slides with 256-px tiles, one of them
delivered as an SVS-shaped TIFF, go through the event-driven service the
way ``repro.launch.convert`` stands it up — landing bucket → pub/sub →
converters on three threads at once → DICOM store — twice: a cold pass
that compiles, then a warm pass. Level 0 lives whole on the chip, and
three such conversions in flight fit its 16 GB. Then it checks that:

- every slide converted and none was dead-lettered (the first dead letter
  fails the run with the converter's own exception);
- QIDO finds every study with every level, and WADO frames decode;
- for sampled tiles of every level, the chip's coefficients are within ±1
  of the jnp oracle run on the host CPU in this process, and at most
  0.01% of them differ;
- level-0 PSNR against the scanner's pixels is at least the 34.9 dB that
  ``examples/quickstart.py`` reports on the CPU;
- ``encode_tile`` and ``encode_tiles_batch`` give byte-identical frames on
  the chip, equal to the frames the pipeline stored;
- one study exports through ``ExportService`` (``jpeg_inverse`` and the
  jitted entropy decoder on the chip), and its level-0 TIFF reopens
  through ``open_slide`` with pixels equal to the WADO-decoded frames.

``--chips 4`` runs only the sharded path: one slide converted on the
default four-device mesh (cold, then warm) and on a one-device mesh in the
same process; the study tars must be byte-identical, and every chip must
have held its band of level 0.

Each phase prints its time on its own line. The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``; it is
printed only when every phase passed. Without a TPU, or run outside a
checkout of this repository, the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SIZE = 8192
TILE = 256
N_SLIDES = 3
#: level-0 PSNR that examples/quickstart.py reports on the CPU
MIN_PSNR_DB = 34.9
#: share of coefficients that may differ (by 1) from the CPU oracle
MAX_DIFF_SHARE = 1e-4
#: sampled tiles per level for the oracle comparison
SAMPLES = 8


class CheckFailed(Exception):
    pass


def check(ok, what) -> None:
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not ok:
        raise CheckFailed(what)


@contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"phase {name}: {time.perf_counter() - t0:.3f} s", flush=True)


def _level_dims() -> list[int]:
    dims = [SIZE]
    while dims[-1] // 2 >= 256:  # ConvertOptions' default min_level_size
        dims.append(dims[-1] // 2)
    return dims


def _slide_tiles(data: bytes):
    """The scanner's level-0 pixels, (n, T, T, 3) row-major tiles."""
    import numpy as np
    from repro.wsi.formats import open_slide

    rd = open_slide(data)
    bh, bw = rd.grid
    return np.stack([rd.read_tile(r, c) for r in range(bh) for c in range(bw)])


def _wait(cond, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.05)


def _frames_equal(stored: bytes, frame: bytes) -> bool:
    """A stored fragment is the frame plus DICOM's even-length pad byte."""
    return stored[:len(frame)] == frame and stored[len(frame):] in (b"", b"\0")


def _convert_pass(pipe, slides: dict, failures: list, tag: str) -> dict:
    keyed = {f"{tag}/{k}": v for k, v in slides.items()}
    try:
        return pipe.run_batch(keyed, timeout=900.0)
    except RuntimeError:
        if failures:  # a dead letter: surface the converter's exception
            raise failures[0]
        raise


def _oracle_check(jax, svc, metas: list, tiles, rng) -> tuple[int, int, int]:
    """Chip coefficients (entropy-decoded from WADO frames, an exact
    inverse) vs ``ref.jpeg_transform_ref`` on the host CPU, for sampled
    tiles of every level. Returns (compared, differing, max |diff|)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.wsi.jpeg import decode_coef_batch

    cpu = jax.devices("cpu")[0]
    down = jax.jit(lambda x: jnp.clip(jnp.round(ref.downsample2x2_ref(x)),
                                      0, 255))
    transform = jax.jit(ref.jpeg_transform_ref)
    bw = SIZE // TILE
    level = tiles.reshape(bw, bw, TILE, TILE, 3).transpose(4, 0, 2, 1, 3)
    level = jax.device_put(level.reshape(3, SIZE, SIZE).astype(np.float32),
                           cpu)
    compared = differing = worst = 0
    for li, meta in enumerate(metas):
        n = svc.frame_index(meta["sop_instance_uid"]).n_frames
        per_row = int(level.shape[2]) // TILE
        idx = sorted({0, n - 1, n // 2,
                      *rng.choice(n, min(n, SAMPLES - 3), replace=False)})
        chip = decode_coef_batch(
            [svc.retrieve_frame(meta["sop_instance_uid"], int(i))
             for i in idx])
        host = np.asarray(level)
        batch = np.stack([host[:, (i // per_row) * TILE:(i // per_row + 1)
                               * TILE, (i % per_row) * TILE:
                               (i % per_row + 1) * TILE] for i in idx])
        want = np.asarray(transform(jax.device_put(batch, cpu),
                                    ref.JPEG_LUMA_Q, ref.JPEG_CHROMA_Q))
        diff = np.abs(chip.astype(np.int64) - want)
        compared += diff.size
        differing += int((diff > 0).sum())
        worst = max(worst, int(diff.max()))
        print(f"  level {li}: {len(idx)} tiles, {int((diff > 0).sum())} of "
              f"{diff.size} coefficients differ", flush=True)
        if li + 1 < len(metas):
            level = down(level)
    return compared, differing, worst


def one_chip(jax) -> None:
    import numpy as np
    from repro.core import ConversionPipeline, RealScheduler
    from repro.wsi import (SyntheticScanner, convert_wsi_to_dicom,
                           encode_tile, psnr, read_part10, study_levels)
    from repro.wsi.formats import open_slide
    from repro.wsi.jpeg import decode_frames, encode_tiles_batch

    dims = _level_dims()
    with phase(f"scan {N_SLIDES} slides {SIZE}x{SIZE}"):
        slides = {}
        for i in range(N_SLIDES):
            scanner = SyntheticScanner(seed=i)
            if i == 0:
                slides["slides/s0.svs"] = scanner.scan_tiff(SIZE, SIZE, TILE)
            else:
                slides[f"slides/s{i}.psv"] = scanner.scan(SIZE, SIZE, TILE)

    failures: list[Exception] = []

    def convert(data, meta):
        try:
            return convert_wsi_to_dicom(data, meta)
        except Exception as exc:
            failures.append(exc)
            raise

    sched = RealScheduler(workers=4 * N_SLIDES)
    try:
        # one delivery attempt: a failing conversion dead-letters at once
        pipe = ConversionPipeline(
            sched, convert=convert, max_instances=N_SLIDES, cold_start=0.0,
            scale_down_delay=2.0, ack_deadline=1200.0,
            max_delivery_attempts=1)
        with phase("convert pass 1 (cold: compiles)"):
            first = _convert_pass(pipe, slides, failures, "pass1")
        with phase("convert pass 2 (warm)"):
            second = _convert_pass(pipe, slides, failures, "pass2")
        print(f"converted {len(first) + len(second)} slides, dead-lettered "
              f"{len(pipe.dead_lettered)}", flush=True)
        check(not pipe.dead_lettered, f"dead-lettered: {pipe.dead_lettered}")

        svc = pipe.store_service
        uids = {}
        with phase("store: QIDO finds every study"):
            for k, tar in {**first, **second}.items():
                uids[k] = json.loads(study_levels(tar)["study.json"])[
                    "study_uid"]
            _wait(lambda: all(len(svc.search_instances(u)) == len(dims)
                              for u in uids.values()), 300.0,
                  "QIDO to list every level of every study")
            for u in uids.values():
                for li, meta in enumerate(svc.search_instances(u)):
                    n = svc.frame_index(meta["sop_instance_uid"]).n_frames
                    check(n == (dims[li] // TILE) ** 2,
                          f"study {u} level {li} holds {n} frames")

        for k in slides:  # the warm pass emitted the same frames
            a, b = study_levels(first[f"pass1/{k}"]), \
                study_levels(second[f"pass2/{k}"])
            for name in a:
                if name.endswith(".dcm"):
                    check(read_part10(a[name])[1] == read_part10(b[name])[1],
                          f"{k} {name}: warm pass frames differ")

        decoded, scanner_tiles = {}, {}
        with phase("WADO: decode level 0 of every slide"):
            for k, data in slides.items():
                meta0 = svc.search_instances(uids[f"pass1/{k}"])[0]
                sop = meta0["sop_instance_uid"]
                frames = [svc.retrieve_frame(sop, i) for i in
                          range(svc.frame_index(sop).n_frames)]
                decoded[k] = decode_frames(
                    frames, transfer_syntax=meta0["transfer_syntax"],
                    rows=TILE, cols=TILE)
                scanner_tiles[k] = _slide_tiles(data)
        for k in slides:
            db = psnr(scanner_tiles[k], decoded[k])
            print(f"level-0 PSNR {k}: {db:.3f} dB", flush=True)
            check(db >= MIN_PSNR_DB, f"{k}: level-0 PSNR {db} dB")

        with phase("coefficients vs the CPU oracle"):
            rng = np.random.default_rng(0)
            compared = differing = worst = 0
            for k in slides:
                c, d, w = _oracle_check(
                    jax, svc, svc.search_instances(uids[f"pass1/{k}"]),
                    scanner_tiles[k], rng)
                compared, differing, worst = \
                    compared + c, differing + d, max(worst, w)
        print(f"coefficients: {differing} of {compared} differ from the CPU "
              f"oracle ({differing / compared:.2e}), max |diff| {worst}",
              flush=True)
        check(worst <= 1 and differing <= MAX_DIFF_SHARE * compared,
              "coefficients disagree with the CPU oracle")

        with phase("byte contract: encode_tile vs encode_tiles_batch"):
            k = "slides/s1.psv"
            tiles8 = scanner_tiles[k][:8]
            per_tile = [encode_tile(t) for t in tiles8]
            batched = encode_tiles_batch(tiles8)
            check(per_tile == batched, "encode_tile and encode_tiles_batch differ")
            sop = svc.search_instances(uids[f"pass1/{k}"])[0][
                "sop_instance_uid"]
            check(all(_frames_equal(svc.retrieve_frame(sop, i), f)
                      for i, f in enumerate(batched)),
                  "stored frames differ from encode_tiles_batch")

        with phase("export one study (dicom2tiff)"):
            k = "slides/s0.svs"
            uid = uids[f"pass1/{k}"]
            pipe.request_export(uid)
            _wait(lambda: pipe.export_dead_lettered or any(
                s == uid for s, _ in pipe.export_service.exported), 600.0,
                "the export")
            check(not pipe.export_dead_lettered,
                  f"export dead-lettered: {pipe.export_dead_lettered}")
            tif = pipe.derived.get(f"{uid}/level_0.tiff").data
            check(open_slide(tif).grid == (SIZE // TILE, SIZE // TILE),
                  "exported level 0 does not reopen at full size")
            check(np.array_equal(_slide_tiles(tif), decoded[k]),
                  "exported level-0 pixels differ from the WADO frames")
    finally:
        sched.shutdown()


def _memory_report(devices) -> None:
    """Every chip held at least its band of level 0 (f32 rows)."""
    band = 3 * SIZE * SIZE * 4 // len(devices)
    for d in devices:
        st = d.memory_stats()
        print(f"memory {d}: peak {st['peak_bytes_in_use']} B, in use "
              f"{st['bytes_in_use']} B", flush=True)
        check(st["peak_bytes_in_use"] >= band,
              f"{d} never held its band of level 0")


def four_chips(jax) -> None:
    import numpy as np
    from jax.sharding import Mesh
    from repro.wsi import ConvertOptions, SyntheticScanner, convert_wsi_to_dicom
    from repro.wsi.dicom import new_uid

    with phase(f"scan 1 slide {SIZE}x{SIZE}"):
        psv = SyntheticScanner(seed=11).scan(SIZE, SIZE, TILE)
    uids = json.dumps([new_uid(), new_uid()])

    def convert(mesh):
        return convert_wsi_to_dicom(
            psv, {"slide_id": "mesh"},
            options=ConvertOptions(manifest={"uids": uids}, mesh=mesh))

    with phase("convert on the 4-device mesh (cold: compiles)"):
        tar4 = convert(None)
    _memory_report(jax.devices())
    with phase("convert on the 4-device mesh (warm)"):
        tar4_warm = convert(None)
    with phase("convert on a 1-device mesh (cold: compiles)"):
        tar1 = convert(Mesh(np.array(jax.devices()[:1]), ("data",)))
    print(f"study tar {len(tar4)} B; 4-device cold == warm: "
          f"{tar4 == tar4_warm}; 4-device == 1-device: {tar4 == tar1}",
          flush=True)
    check(tar4 == tar4_warm == tar1, "study tars differ across meshes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a four-chip host")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: expected {args.chips} chip(s), found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    with phase("total"):
        (four_chips if args.chips == 4 else one_chip)(jax)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
