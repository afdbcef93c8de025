"""Whole-level batched JPEG path: fused kernel differential, byte-exactness
of the batched entropy coder, and device-resident pyramid parity."""
import io
import tarfile

import jax.numpy as jnp
import numpy as np
import pytest

from _convert_ref import reference_level_frames, study_frames

from repro.kernels import jpeg_transform
from repro.kernels import ref
from repro.wsi import (ConvertOptions, SyntheticScanner, convert_wsi_to_dicom,
                       decode_tile, encode_tile, read_part10, study_levels)
from repro.wsi.jpeg import encode_tiles_batch
from repro.wsi.slide import PSVReader

RNG = np.random.default_rng(7)


# --------------------------------------------------------------------------
# fused jpeg_transform kernel vs jnp oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,h,w", [(1, 8, 128), (2, 64, 128), (3, 32, 256)])
@pytest.mark.parametrize("seed", [0, 1])
def test_jpeg_transform_pallas_matches_ref(n, h, w, seed):
    rng = np.random.default_rng(seed)
    tiles = jnp.asarray(rng.integers(0, 256, size=(n, 3, h, w))
                        .astype(np.float32))
    out = jpeg_transform(tiles, impl="pallas")
    expect = ref.jpeg_transform_ref(tiles)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_jpeg_transform_matches_unfused_chain():
    """Fused kernel == rgb2ycbcr + per-channel dct8x8_quant, bit for bit."""
    from repro.kernels import dct8x8_quant, rgb2ycbcr

    tiles = RNG.integers(0, 256, size=(2, 3, 64, 128)).astype(np.float32)
    fused = np.asarray(jpeg_transform(jnp.asarray(tiles), impl="pallas"))
    qs = [ref.JPEG_LUMA_Q, ref.JPEG_CHROMA_Q, ref.JPEG_CHROMA_Q]
    for n in range(tiles.shape[0]):
        ycc = np.asarray(rgb2ycbcr(jnp.asarray(tiles[n])))
        for c in range(3):
            plane = np.asarray(dct8x8_quant(jnp.asarray(ycc[c]),
                                            jnp.asarray(qs[c])))
            np.testing.assert_array_equal(plane, fused[n, c])


def test_jpeg_transform_unaligned_falls_back_to_ref():
    tiles = jnp.asarray(RNG.integers(0, 256, size=(2, 3, 24, 72))
                        .astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(jpeg_transform(tiles)),
        np.asarray(ref.jpeg_transform_ref(tiles)))


# --------------------------------------------------------------------------
# batched entropy coder vs per-tile reference loop
# --------------------------------------------------------------------------
def test_batched_jpeg_bytes_identical_to_per_tile():
    psv = SyntheticScanner(seed=3).scan(512, 512, 256)
    rd = PSVReader(psv)
    tiles = np.stack([rd.read_tile(r, c) for r in range(2) for c in range(2)])
    per = [encode_tile(t) for t in tiles]
    bat = encode_tiles_batch(tiles)
    assert len(per) == len(bat)
    for a, b in zip(per, bat):
        assert a == b


@pytest.mark.parametrize("kind", ["noise", "flat", "gradient"])
def test_batched_bytes_identical_on_adversarial_content(kind):
    """Worst cases for the run-length vectorization: dense symbols (noise),
    long zero runs / EOB everywhere (flat), smooth DC drift (gradient)."""
    if kind == "noise":
        tiles = RNG.integers(0, 256, size=(2, 64, 128, 3)).astype(np.uint8)
    elif kind == "flat":
        tiles = np.full((2, 64, 128, 3), 200, np.uint8)
        tiles[0, 11, 13] = [0, 255, 7]  # one outlier block
    else:
        g = np.linspace(0, 255, 64 * 128).reshape(64, 128)
        one = np.stack([g, g[::-1], 255 - g], axis=-1).astype(np.uint8)
        tiles = np.stack([one, one[:, ::-1]])
    per = [encode_tile(t) for t in tiles]
    bat = encode_tiles_batch(tiles)
    for a, b in zip(per, bat):
        assert a == b


def test_out_of_range_coefficients_raise():
    """Categories beyond the baseline tables must raise, not alias/corrupt."""
    from repro.wsi.jpeg import encode_coef_batch

    coef = np.zeros((1, 3, 8, 8), np.int32)
    coef[0, 0, 0, 1] = 1 << 20  # AC category 21 would alias into the run nibble
    with pytest.raises(ValueError, match="AC coefficient"):
        encode_coef_batch(coef)

    coef = np.zeros((1, 3, 8, 8), np.int32)
    coef[0, 0, 0, 0] = 1 << 14  # DC diff category 15: no baseline code
    with pytest.raises(ValueError, match="DC difference"):
        encode_coef_batch(coef)


def test_unknown_impl_rejected():
    tiles = jnp.zeros((1, 3, 8, 128), jnp.float32)
    with pytest.raises(ValueError, match="impl"):
        jpeg_transform(tiles, impl="interpret")


def test_batched_roundtrip_decodes():
    psv = SyntheticScanner(seed=4).scan(256, 256, 256)
    tile = PSVReader(psv).read_tile(0, 0)
    jpg = encode_tiles_batch(tile[None])[0]
    rec = decode_tile(jpg)
    assert rec.shape == tile.shape
    err = np.abs(rec.astype(np.int32) - tile.astype(np.int32)).mean()
    assert err < 8.0  # q50 baseline quality


# --------------------------------------------------------------------------
# device-resident pyramid vs the plain host reference
# --------------------------------------------------------------------------
def test_device_pyramid_matches_host_pyramid():
    psv = SyntheticScanner(seed=5).scan(1024, 1024, 256)
    got = study_frames(convert_wsi_to_dicom(psv))
    assert len(got) == 3  # 1024 → 512 → 256
    assert got == reference_level_frames(psv, 256)


def test_batched_handles_levels_smaller_than_tile():
    """min_level_size below the tile size: the deepest levels hold no full
    frame; the converter must agree with the reference (and not crash) all
    the way down."""
    psv = SyntheticScanner(seed=9).scan(512, 512, 256)
    lv = study_levels(convert_wsi_to_dicom(
        psv, options=ConvertOptions(min_level_size=128)))
    assert "level_2.dcm" in lv  # the 128x128 sub-tile level exists
    got = [read_part10(lv[f"level_{i}.dcm"])[1] for i in range(3)]
    assert got[2] == []
    assert got == reference_level_frames(psv, 128)


def test_raw_path_device_pyramid_matches_host():
    psv = SyntheticScanner(seed=6).scan(512, 512, 256)
    got = study_frames(convert_wsi_to_dicom(psv, options=ConvertOptions(
        jpeg=False, min_level_size=256)))
    assert got == reference_level_frames(psv, 256, jpeg=False)


# --------------------------------------------------------------------------
# converter satellites: tar member guard, single-store manifest
# --------------------------------------------------------------------------
def test_study_levels_skips_non_file_members():
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        d = tarfile.TarInfo("levels/")
        d.type = tarfile.DIRTYPE
        tar.addfile(d)
        info = tarfile.TarInfo("levels/level_0.dcm")
        payload = b"not-a-real-dcm"
        info.size = len(payload)
        tar.addfile(info, io.BytesIO(payload))
    out = study_levels(buf.getvalue())
    assert out == {"levels/level_0.dcm": payload}


def test_manifest_is_single_store_and_clearable():
    psv = SyntheticScanner(seed=8).scan(256, 256, 256)
    opt = ConvertOptions()
    tar_bytes = convert_wsi_to_dicom(psv, options=opt)
    # the manifest holds every finished level (plus the minted study/series
    # UIDs that make resume byte-exact); the tar is written from it
    assert set(opt.manifest) == {"0", "uids"}
    assert study_levels(tar_bytes)["level_0.dcm"] == opt.manifest["0"]
    opt.clear_manifest()
    assert opt.manifest == {}
