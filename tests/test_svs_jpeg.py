"""JPEG SVS: the container reader, the table-general decoders, the 4:2:0
inverse and the transcoding engine, against the plain reference
(``bench/reference_svs.py``, which imports nothing of the program) and the
benchmark's stand-in scanner (``bench/scanner_jpeg.py``)."""
from __future__ import annotations

import io
import struct
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))

import reference  # noqa: E402
import reference_svs  # noqa: E402
import scanner_jpeg  # noqa: E402

from repro.core import tracing  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.wsi import (ConvertOptions, Part10Index, convert_wsi_to_dicom,  # noqa: E402
                       decode_frames, jpeg, study_levels)
from repro.wsi.dicom import TS_JPEG_BASELINE, write_part10  # noqa: E402
from repro.wsi.formats import open_slide, write_tiff  # noqa: E402

SEED = 11.5


@pytest.fixture(scope="module")
def svs512():
    return scanner_jpeg.scan(512, 512, 256, SEED)


def _pillow(shape=(64, 128), subsampling=2, optimize=False, seed=0):
    from PIL import Image

    rng = np.random.default_rng(seed)
    y, x = np.mgrid[:shape[0], :shape[1]]
    px = np.stack([128 + 60 * np.sin(x / 9.0 + seed), 90 + 50 * np.cos(y / 7.0),
                   160 + 40 * np.sin((x + y) / 5.0)], -1)
    px = np.clip(px + rng.normal(0, 12, px.shape), 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(px).save(buf, "JPEG", quality=70, subsampling=subsampling,
                             optimize=optimize)
    return buf.getvalue()


def _planes_of(comps_per_tile):
    return [np.stack(c) for c in zip(*comps_per_tile)]


# ------------------------------------------------------------------ reader
@pytest.mark.parametrize("photometric", [2, 6])
def test_reader_serves_the_jpeg_level0_of_a_multi_ifd_svs(photometric):
    record, svs = scanner_jpeg.scan(1024, 1024, 256, SEED,
                                    photometric=photometric)
    rd = open_slide(svs)
    # level 0 out of three IFDs (level 0, stripped thumbnail, reduced level)
    assert (rd.H, rd.W, rd.tile, rd.grid) == (1024, 1024, 256, (4, 4))
    assert rd.chroma_subsampling == (2, 2)
    assert rd.jpeg_tables == record["tables"]
    assert rd.metadata["vendor"].startswith("Aperio")
    frames = rd.jpeg_frames()
    assert len(frames) == 16
    for frame, tile in zip(frames, record["tiles"]):
        assert frame == jpeg.merge_tables(tile, record["tables"])
        assert reference_svs.segments(frame)[1] == \
            reference_svs.segments(tile)[1]
    img, _ = reference_svs.scanner_decode(record, scanner_jpeg.Q_LUMA,
                                          scanner_jpeg.Q_CHROMA)
    got = rd.read_tile(1, 2).astype(int)
    assert np.abs(got - img[256:512, 512:768]).max() <= 1


def test_reader_checks_ycbcr_subsampling_against_the_stream():
    _, svs = scanner_jpeg.scan(512, 512, 256, SEED, photometric=6)
    tag = struct.pack("<HHI", 530, 3, 2) + struct.pack("<HH", 2, 2)
    bad = svs.replace(tag, struct.pack("<HHI", 530, 3, 2)
                      + struct.pack("<HH", 2, 1))
    assert bad != svs
    with pytest.raises(ValueError, match="YCbCrSubsampling"):
        open_slide(bad)


def test_deflate_tiff_frames_are_unchanged():
    """The Deflate/RGB container keeps its engine: level 0 re-encoded at
    4:4:4, YBR_FULL, byte-identical to the batched encoder."""
    rng = np.random.default_rng(3)
    tiles = {(r, c): rng.integers(0, 255, (256, 256, 3), dtype=np.uint8)
             for r in range(2) for c in range(2)}
    blob = write_tiff(tiles, 512, 512, 256)
    rd = open_slide(blob)
    assert rd.jpeg_frames() is None and rd.jpeg_tables is None
    with tracing.capture() as tr:
        lv = study_levels(convert_wsi_to_dicom(blob))
    idx = Part10Index(lv["level_0.dcm"])
    assert idx.get_str(0x0028, 0x0004) == "YBR_FULL"
    want = jpeg.encode_tiles_batch(np.stack([tiles[k] for k in sorted(tiles)]))
    assert [idx.read_frame(i)[:len(want[i])] for i in range(4)] == want
    (slide,) = tr.spans_named("convert.slide")
    assert slide.attrs["transcoded_frames"] == 0
    (up,) = tr.spans_named("convert.upload")
    assert up.attrs["bytes"] == 512 * 512 * 3 * 4


# ---------------------------------------------------------------- decoders
@pytest.mark.parametrize("subsampling,optimize", [
    (2, False), (2, True), (0, True), (0, False), (1, True)],
    ids=["420-annexk", "420-optimised", "444-optimised", "444-annexk",
         "422-optimised"])
def test_engines_decode_to_the_reference_coefficients(subsampling, optimize):
    """Pillow's libjpeg at quality 70: 4:2:0, 4:2:2 and 4:4:4 streams, with
    Annex K or optimised (non-Annex-K) Huffman tables — both lockstep
    engines and the per-tile loop give the reference's coefficients."""
    # optimised tables are the image's own: a batch shares one image's
    jpgs = [_pillow(subsampling=subsampling, optimize=optimize, seed=s)
            for s in ((0, 0, 0) if optimize else range(3))]
    want = _planes_of([reference_svs.decode_components(j) for j in jpgs])
    H, W, coding, scans = jpeg._parse_batch(jpgs)
    assert coding.subsampled == (subsampling != 0)
    zz = {e: jpeg._run_packed(*jpeg._pack_scans(scans, H, W, e, coding),
                              H, W, coding) for e in ("numpy", "jax")}
    np.testing.assert_array_equal(zz["numpy"], zz["jax"])
    for got, ref in zip(jpeg.decode_components(jpgs), want):
        np.testing.assert_array_equal(got, ref)
    from repro.wsi.jpeg import _BitReader, _decode_blocks
    start, end = jpeg._parse_stream(jpgs[1])[2:4]
    for got, ref in zip(_decode_blocks(_BitReader(jpgs[1][start:end]), H, W,
                                       coding), want):
        np.testing.assert_array_equal(got, ref[1])


def test_subsampled_pixels_agree_with_pillow_and_across_paths():
    """The batched and per-tile decodes are pixel-identical; against
    libjpeg's integer iDCT and biased upsampler they differ by a few
    levels at most."""
    from PIL import Image

    jpgs = [_pillow(seed=s) for s in range(2)]
    batch = jpeg.decode_tiles_batch(jpgs)
    np.testing.assert_array_equal(batch[1], jpeg.decode_tile(jpgs[1]))
    lib = np.asarray(Image.open(io.BytesIO(jpgs[1])).convert("RGB"))
    assert np.abs(batch[1].astype(int) - lib).max() <= 4


@pytest.mark.parametrize("cut", ["truncate", "garbage"])
def test_engines_raise_the_same_corrupt_errors_on_subsampled_scans(cut):
    jpgs = [_pillow(seed=s) for s in range(2)]
    H, W, coding, scans = jpeg._parse_batch(jpgs)
    rng = np.random.default_rng(5)
    bad = scans[1][:scans[1].size // 2] if cut == "truncate" \
        else rng.integers(0, 256, scans[1].size).astype(np.uint8)
    errs = []
    for engine in ("jax", "numpy"):
        with pytest.raises(ValueError, match="corrupt JPEG") as ei:
            jpeg._run_packed(*jpeg._pack_scans([scans[0], bad], H, W,
                                               engine, coding), H, W, coding)
        errs.append(str(ei.value))
    assert errs[0] == errs[1]


def test_streams_outside_the_baseline_subset_are_refused():
    jpg = _pillow()
    adobe = b"\xff\xee" + struct.pack(">H", 14) + b"Adobe" + bytes(
        [0, 100, 0, 0, 0, 0, 0])
    rgb = jpg[:2] + adobe + jpg[2:]
    with pytest.raises(ValueError, match="unsupported JPEG stream: RGB"):
        jpeg.decode_tiles_batch([rgb, rgb])
    assert jpeg.photometric(jpg) == "YBR_FULL_422"
    assert jpeg.photometric(_pillow(subsampling=0)) == "YBR_FULL"
    with pytest.raises(ValueError, match="corrupt JPEGTables"):
        jpeg.merge_tables(jpg, b"\x00\x01")


# ----------------------------------------------------------------- inverse
def test_inverse420_kernel_equals_oracle_within_the_reference_band(svs512):
    """The Pallas kernel (interpret mode) equals the jnp oracle; both equal
    the float64 reference decode wherever the band can decide a rounding."""
    record, _ = svs512
    comps = jpeg.decode_components(
        [jpeg.merge_tables(t, record["tables"]) for t in record["tiles"]])
    q = np.stack([scanner_jpeg.Q_LUMA, scanner_jpeg.Q_CHROMA,
                  scanner_jpeg.Q_CHROMA]).astype(np.float32)
    chroma = np.stack(comps[1:], axis=1)
    oracle = np.asarray(ops.jpeg_inverse420(comps[0], chroma, q, impl="ref"))
    kernel = np.asarray(ops.jpeg_inverse420(comps[0], chroma, q,
                                            impl="pallas"))
    np.testing.assert_array_equal(kernel, oracle)
    img, amb = reference_svs.scanner_decode(record, scanner_jpeg.Q_LUMA,
                                            scanner_jpeg.Q_CHROMA)
    tiles = oracle.reshape(2, 2, 3, 256, 256).transpose(0, 3, 1, 4, 2) \
        .reshape(512, 512, 3)
    differ = (tiles != img).any(axis=-1)
    assert not (differ & ~amb).any()


def test_upsampler_is_the_centred_triangle_filter():
    from repro.kernels.ref import upsample_matrix

    m = upsample_matrix(8, 4)
    c = np.array([1.0, 5.0, 9.0, 13.0])
    np.testing.assert_allclose(m @ c, reference_svs.upsample(c, 0))
    np.testing.assert_allclose(m @ c, [1, 2, 4, 6, 8, 10, 12, 13])
    np.testing.assert_array_equal(upsample_matrix(4, 4), np.eye(4))


# --------------------------------------------------------------- transcode
def test_transcoding_keeps_level0_and_matches_the_reference(svs512):
    """A 512² JPEG SVS through ``convert_wsi_to_dicom``: level 0's frames
    are the scanner's scans with the tables merged in (YBR_FULL_422),
    level 1 holds the reference pyramid's coefficients, the level-0
    decode ran on the device with the scans, not pixels, uploaded."""
    record, svs = svs512
    with tracing.capture() as tr:
        lv = study_levels(convert_wsi_to_dicom(svs, {"slide_id": "s"}))
    assert sorted(lv) == ["level_0.dcm", "level_1.dcm", "study.json"]
    idx0 = Part10Index(lv["level_0.dcm"])
    idx0.verify()
    assert idx0.get_str(0x0028, 0x0004) == "YBR_FULL_422"
    for i, tile in enumerate(record["tiles"]):
        frame = idx0.read_frame(i)
        assert reference_svs.segments(frame)[1] == \
            reference_svs.segments(tile)[1]
        assert frame.rstrip(b"\0") == jpeg.merge_tables(tile,
                                                        record["tables"])
    img, amb = reference_svs.scanner_decode(record, scanner_jpeg.Q_LUMA,
                                            scanner_jpeg.Q_CHROMA)
    level1 = reference.pyramid(img, 2)[1]
    idx1 = Part10Index(lv["level_1.dcm"])
    assert idx1.get_str(0x0028, 0x0004) == "YBR_FULL"
    want, band = reference.forward(level1)
    foot = np.repeat(np.repeat(reference_svs.footprint(amb, 1), 8, 0), 8, 1)
    got = reference.decode_coefficients(idx1.read_frame(0))
    keep = ~band & ~foot[None]
    assert keep.mean() > 0.5
    np.testing.assert_array_equal(got[keep], want[keep])
    (slide,) = tr.spans_named("convert.slide")
    assert slide.attrs["transcoded_frames"] == 4
    (dec,) = tr.spans_named("convert.decode")
    (up,) = tr.spans_named("convert.upload")
    assert dec.attrs["frames"] == 4 and dec.attrs["blocks"] == 4 * 1536
    assert dec.attrs["lanes"] == 4 and dec.attrs["steps"] >= 2 * 1536
    assert up.attrs["bytes"] == dec.attrs["bytes_in"] < 512 * 512
    names = {d.name for d in tr.descendants(dec)}
    assert {"decode.parse", "convert.upload", "decode.entropy",
            "decode.inverse"} <= names


def test_transcoding_resumes_from_a_checkpointed_level0(svs512):
    _, svs = svs512
    opt = ConvertOptions()
    full = convert_wsi_to_dicom(svs, options=opt)
    level0 = opt.manifest["0"]
    del opt.manifest["1"]
    assert convert_wsi_to_dicom(svs, options=opt) == full
    assert opt.manifest["0"] == level0


def test_subscribers_and_validation_accept_ybr_full_422(svs512):
    record, _ = svs512
    frames = [jpeg.merge_tables(t, record["tables"]) for t in record["tiles"]]
    for n in (1, 4):
        rgb = decode_frames(frames[:n], transfer_syntax=TS_JPEG_BASELINE,
                            rows=256, cols=256)
        assert rgb.shape == (n, 256, 256, 3)
    kw = dict(rows=256, cols=256, total_rows=512, total_cols=512,
              transfer_syntax=TS_JPEG_BASELINE)
    Part10Index(write_part10(frames=frames, photometric="YBR_FULL_422",
                             **kw)).verify()
    with pytest.raises(ValueError, match="YBR_FULL for YBR_FULL_422 frames"):
        Part10Index(write_part10(frames=frames, **kw)).verify()
    std = jpeg.encode_tiles_batch(np.zeros((1, 256, 256, 3), np.uint8))
    with pytest.raises(ValueError, match="YBR_FULL_422 for YBR_FULL frames"):
        Part10Index(write_part10(frames=std, photometric="YBR_FULL_422",
                                 **kw)).verify()
