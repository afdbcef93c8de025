"""Multi-format ingestion: SlideReader protocol, registry/sniff, tiled TIFF,
and cross-format conversion byte-identity (direct + through the event-driven
pipeline)."""
import json
import struct

import numpy as np
import pytest

from _convert_ref import reference_level_frames, study_frames

from repro.core import ConversionPipeline, RealScheduler
from repro.core import clock
from repro.wsi import (ConvertOptions, PSVReader, SyntheticScanner,
                       convert_wsi_to_dicom, open_slide, sniff, study_levels)
from repro.wsi.dicom import new_uid
from repro.wsi.formats import (SlideReader, TiffSlideReader, formats,
                               write_tiff)


def _tiles(seed=3, H=512, W=512, tile=256):
    return SyntheticScanner(seed=seed)._render_tiles(H, W, tile)


# ---------------------------------------------------------------------------
# registry / sniff
# ---------------------------------------------------------------------------
def test_sniff_matrix():
    sc = SyntheticScanner(seed=1)
    assert sniff(sc.scan(256, 256, 256)) == "psv"
    assert sniff(sc.scan_tiff(256, 256, 256)) == "tiff"
    be = write_tiff(_tiles(1, 256, 256), 256, 256, 256, byteorder=">")
    assert sniff(be) == "tiff"  # big-endian (MM) classic TIFF


@pytest.mark.parametrize("blob", [b"", b"garbage!", b"\x00" * 64])
def test_sniff_unknown_container_is_actionable(blob):
    with pytest.raises(ValueError, match="supported formats are.*psv.*tiff"):
        sniff(blob)


def test_registry_lists_both_formats():
    fmts = formats()
    assert set(fmts) >= {"psv", "tiff"}
    assert ".svs" in fmts["tiff"].extensions


def test_readers_satisfy_protocol():
    sc = SyntheticScanner(seed=2)
    for blob in (sc.scan(256, 256, 256), sc.scan_tiff(256, 256, 256)):
        rd = open_slide(blob)
        assert isinstance(rd, SlideReader)
        assert rd.grid == (1, 1)
        assert rd.read_tile(0, 0).shape == (256, 256, 3)
        assert isinstance(rd.metadata, dict)


# ---------------------------------------------------------------------------
# tiled TIFF reader/writer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("byteorder", ["<", ">"])
def test_tiff_round_trip_both_byteorders(byteorder):
    tiles = _tiles(7, 512, 768, 256)
    blob = write_tiff(tiles, 512, 768, 256, byteorder=byteorder,
                      description="repro test|AppMag = 40|MPP = 0.25")
    rd = TiffSlideReader(blob)
    assert (rd.H, rd.W, rd.tile) == (512, 768, 256)
    assert rd.grid == (2, 3)
    for (r, c), t in rd.tiles():
        assert np.array_equal(t, tiles[(r, c)])
    assert rd.metadata["AppMag"] == "40"
    assert rd.metadata["MPP"] == "0.25"
    assert rd.metadata["vendor"] == "repro test"


def test_tiff_matches_psv_pixels_exactly():
    sc = SyntheticScanner(seed=11)
    rp = PSVReader(sc.scan(512, 512, 256))
    rt = TiffSlideReader(sc.scan_tiff(512, 512, 256))
    assert rp.grid == rt.grid
    for (k1, t1), (k2, t2) in zip(rp.tiles(), rt.tiles()):
        assert k1 == k2
        assert np.array_equal(t1, t2)


def test_tiff_writer_is_deterministic():
    tiles = _tiles(4, 256, 256)
    assert write_tiff(tiles, 256, 256, 256) == write_tiff(tiles, 256, 256, 256)


def test_truncated_tiff_raises_at_open():
    blob = SyntheticScanner(seed=5).scan_tiff(512, 512, 256)
    for cut in (4, 100, len(blob) // 2, len(blob) - 10):
        with pytest.raises(ValueError, match="TIFF"):
            TiffSlideReader(blob[:cut])


def test_corrupt_tiff_tile_raises_on_read():
    blob = bytearray(SyntheticScanner(seed=5).scan_tiff(512, 512, 256))
    rd = TiffSlideReader(bytes(blob))
    off = rd._offsets[0]
    blob[off:off + 8] = b"\xff" * 8  # smash the first tile's zlib stream
    with pytest.raises(ValueError, match="corrupt TIFF tile"):
        TiffSlideReader(bytes(blob)).read_tile(0, 0)


def test_unsupported_tiff_layouts_are_actionable():
    # striped TIFF (StripOffsets instead of TileOffsets)
    def ifd(entries):
        body = struct.pack("<H", len(entries))
        for tag, typ, count, value in entries:
            body += struct.pack("<HHII", tag, typ, count, value)
        return body + struct.pack("<I", 0)

    header = b"II" + struct.pack("<HI", 42, 8)
    striped = header + ifd([(256, 4, 1, 64), (257, 4, 1, 64),
                            (273, 4, 1, 8), (278, 4, 1, 64)])
    with pytest.raises(ValueError, match="striped layout"):
        open_slide(striped)

    # JPEG-compressed tiled TIFF
    jpeg = header + ifd([(256, 4, 1, 64), (257, 4, 1, 64), (259, 3, 1, 7),
                         (322, 4, 1, 64), (323, 4, 1, 64),
                         (324, 4, 1, 8), (325, 4, 1, 0)])
    with pytest.raises(ValueError, match="(?i)jpeg"):
        open_slide(jpeg)

    # BigTIFF magic
    with pytest.raises(ValueError, match="BigTIFF"):
        open_slide(b"II" + struct.pack("<HI", 43, 8) + b"\x00" * 16)


def test_zero_tile_containers_raise_cleanly():
    # crafted headers declaring tile=0 must be a clear ValueError, never a
    # ZeroDivisionError surfacing as the dlq_reason
    psv0 = b"PSV1" + struct.pack("<IIII", 512, 512, 0, 0)
    with pytest.raises(ValueError, match="corrupt PSV"):
        open_slide(psv0)
    header = b"II" + struct.pack("<HI", 42, 8)
    body = struct.pack("<H", 5)
    for tag, typ, count, value in [(256, 4, 1, 64), (257, 4, 1, 64),
                                   (322, 4, 1, 0), (323, 4, 1, 0),
                                   (324, 4, 1, 8)]:
        body += struct.pack("<HHII", tag, typ, count, value)
    tif0 = header + body + struct.pack("<I", 0)
    with pytest.raises(ValueError, match="corrupt TIFF"):
        open_slide(tif0)


def test_core_simulation_import_stays_light():
    """repro.core is the discrete-event simulation substrate; importing it
    must not drag in the jax converter stack (format sniffing is lazy)."""
    import os
    import subprocess
    import sys

    import repro
    src = os.path.dirname(list(repro.__path__)[0])
    env = {**os.environ,
           "PYTHONPATH": src + os.pathsep + os.environ.get("PYTHONPATH", "")}
    code = ("import sys, repro.core; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code],
                          env=env).returncode == 0


def test_truncated_psv_raises_at_open():
    blob = SyntheticScanner(seed=5).scan(512, 512, 256)
    for cut in (30, len(blob) // 2):
        with pytest.raises(ValueError, match="truncated PSV"):
            PSVReader(blob[:cut])


def test_misaligned_slide_dims_raise():
    tiles = _tiles(1, 256, 256)
    blob = write_tiff(tiles, 200, 256, 256)  # H not a tile multiple
    with pytest.raises(ValueError, match="tile-aligned"):
        convert_wsi_to_dicom(blob)


# ---------------------------------------------------------------------------
# cross-format conversion byte-identity
# ---------------------------------------------------------------------------
def test_cross_format_study_tars_are_byte_identical():
    """Same pixels as PSV and as tiled TIFF, same manifest UIDs → identical
    study tar, whose levels equal the plain reference."""
    sc = SyntheticScanner(seed=21)
    psv = sc.scan(512, 512, 256)
    tif = sc.scan_tiff(512, 512, 256)
    uids = json.dumps([new_uid(), new_uid()])
    outs = {name: convert_wsi_to_dicom(
        blob, {"slide_id": "X"}, ConvertOptions(manifest={"uids": uids}))
        for name, blob in (("psv", psv), ("tiff", tif))}
    assert outs["tiff"] == outs["psv"]
    assert len(study_levels(outs["psv"])) == 3  # study.json + 2 levels
    assert study_frames(outs["psv"]) == reference_level_frames(psv, 256)


def test_mixed_format_batch_through_event_driven_pipeline():
    """One deployment, one landing bucket, three containers (.psv/.tiff/.svs)
    — every slide converts, and the PSV/TIFF deliveries of identical pixels
    produce byte-identical study tars end to end."""
    sc = SyntheticScanner(seed=23)
    psv = sc.scan(512, 512, 256)
    tif = sc.scan_tiff(512, 512, 256)
    svs = SyntheticScanner(seed=24).scan_tiff(256, 256, 256)
    uids = {"S": json.dumps([new_uid(), new_uid()]),
            "V": json.dumps([new_uid(), new_uid()])}

    def convert(data, meta):
        opt = ConvertOptions(manifest={"uids": uids[meta["slide_id"]]})
        return convert_wsi_to_dicom(data, {"slide_id": meta["slide_id"]},
                                    options=opt)

    sched = RealScheduler(workers=4)
    pipe = ConversionPipeline(
        sched, convert=convert, max_instances=2, cold_start=0.0,
        scale_down_delay=2.0, subscribers=False,
    )
    outs = pipe.run_batch(
        {"psv/slide.psv": psv, "tiff/slide.tiff": tif, "svs/extra.svs": svs},
        metadata={"psv/slide.psv": {"slide_id": "S"},
                  "tiff/slide.tiff": {"slide_id": "S"},
                  "svs/extra.svs": {"slide_id": "V"}},
        timeout=240.0)
    sched.shutdown()
    assert outs["psv/slide.psv"] == outs["tiff/slide.tiff"]
    assert outs["svs/extra.svs"] != outs["psv/slide.psv"]
    assert pipe.metrics.get("pipeline.format.psv") == 1
    assert pipe.metrics.get("pipeline.format.tiff") == 2


def test_garbage_landing_object_dead_letters_with_actionable_reason():
    """Unknown container in the landing bucket → DLQ with the sniff error as
    dlq_reason, and run_batch fails fast instead of timing out."""
    sched = RealScheduler(workers=4)
    pipe = ConversionPipeline(
        sched, convert=lambda data, meta: convert_wsi_to_dicom(data, meta),
        max_instances=2, cold_start=0.0, scale_down_delay=2.0,
        max_delivery_attempts=2, min_backoff=0.05, max_backoff=0.05,
        subscribers=False,
    )
    t0 = clock.monotonic()
    with pytest.raises(RuntimeError,
                       match="dead-lettered.*unknown slide container"):
        pipe.run_batch({"slides/junk.psv": b"not a slide at all"},
                       timeout=120.0)
    assert clock.monotonic() - t0 < 60.0  # fail-fast, not the full timeout
    assert pipe.dead_lettered and \
        "supported formats" in pipe.dead_lettered[0][1]
    sched.shutdown()
