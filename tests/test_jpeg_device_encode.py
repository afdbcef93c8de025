"""The device Huffman coder (``wsi/entropy_encode_jax``) against the numpy
coder it stands in for: byte-identical scans on hand-built edge cases and
scanner pixels, the per-tile fallback, and a pipelined conversion that
codes on the device."""
import jax.numpy as jnp
import numpy as np
import pytest

from _convert_ref import reference_level_frames, study_frames

from repro.core import tracing
from repro.kernels import jpeg_transform
from repro.wsi import SyntheticScanner, convert_wsi_to_dicom
from repro.wsi import jpeg
from repro.wsi.slide import PSVReader

#: small tiles for the hand-built cases: 64 units a component, a 2048-byte
#: slab
T = 64


def _put(coef, t, c, block, z, v):
    """Set zigzag slot ``z`` of block ``block`` (row-major) of component
    ``c`` of tile ``t``."""
    by, bx = divmod(block, coef.shape[-1] // 8)
    py, px = divmod(int(jpeg._ZIGZAG[z]), 8)
    coef[t, c, by * 8 + py, bx * 8 + px] = v


def _tiles(n=2):
    return np.zeros((n, 3, T, T), np.int32)


def scanner_512():
    rgb = np.stack([PSVReader(SyntheticScanner(seed=3).scan(512, 512, 256))
                    .read_tile(r, c) for r in range(2) for c in range(2)])
    return np.asarray(jpeg_transform(
        jnp.asarray(rgb.transpose(0, 3, 1, 2).astype(np.float32))))


def random_sparse():
    rng = np.random.default_rng(5)
    coef = rng.integers(-60, 61, size=(2, 3, T, T)).astype(np.int32)
    coef[rng.random(coef.shape) < 0.95] = 0
    return coef


def dc_category_11():
    coef = _tiles()
    for b in range(64):  # differences of ±2047 between neighbours
        for c in range(3):
            _put(coef, 0, c, b, 0, 1024 if b % 2 else -1023)
            _put(coef, 1, c, b, 0, -1024 if b % 2 else 1023)
    return coef


def ac_category_10():
    coef = _tiles()
    for b in range(64):
        for c in range(3):
            _put(coef, 0, c, b, 1 + b % 63, 1023 if b % 2 else -1023)
            _put(coef, 1, c, b, 63 - b % 40, -512 if c else 700)
    return coef


def zero_runs():
    """Runs of 15, 16, 31 and 47 zeros before a coefficient: 0 to 3 ZRLs,
    from the DC and between coefficients."""
    coef = _tiles()
    for b, run in enumerate((15, 16, 31, 47)):
        for c in range(3):
            _put(coef, 0, c, b, run + 1, 3)
            _put(coef, 1, c, b, 1, -2)
            _put(coef, 1, c, b, 2 + run, 5)
    return coef


def last_slot_nonzero():
    """A nonzero at slot 63 ends the block with no EOB."""
    coef = _tiles()
    for b in range(64):
        for c in range(3):
            _put(coef, 0, c, b, 63, 1 + b)
            _put(coef, 1, c, b, 62, -1)
            _put(coef, 1, c, b, 63, 7)
    return coef


def all_zero():
    return _tiles()


def needs_stuffing():
    """Long runs of ZRLs and all-ones codes put 0xFF bytes in the scan."""
    coef = _tiles()
    for b in range(0, 64, 2):
        _put(coef, 0, 0, b, 49, 1)
        _put(coef, 1, 0, b, 63, -1023)
    return coef


def noise_over_capacity():
    """Dense noise in one tile: more events and bits than its slab holds."""
    coef = random_sparse()
    coef[0] = np.random.default_rng(9).integers(-1000, 1001,
                                                size=(3, T, T))
    return coef


def ac_out_of_range():
    coef = random_sparse()
    _put(coef, 1, 2, 5, 7, 1024)  # category 11: no baseline AC code
    return coef


def no_tiles():
    return np.zeros((0, 3, T, T), np.int32)


@pytest.mark.parametrize("case,host_tiles", [
    (scanner_512, 0), (random_sparse, 0), (dc_category_11, 0),
    (ac_category_10, 0), (zero_runs, 0), (last_slot_nonzero, 0),
    (all_zero, 0), (needs_stuffing, 0), (noise_over_capacity, 1),
    (ac_out_of_range, None), (no_tiles, 0),
], ids=lambda p: getattr(p, "__name__", str(p)))
def test_device_coder_matches_numpy_coder(case, host_tiles):
    """Scan for scan, the device coder emits the numpy coder's bytes,
    stuffing and flush included; a tile over its slab is coded on the
    host, and an out-of-range coefficient raises the numpy coder's
    ``ValueError`` (``host_tiles`` None)."""
    coef = case()
    if host_tiles is None:
        with pytest.raises(ValueError, match="out of range") as want:
            jpeg._entropy_encode_batch(coef)
        with pytest.raises(ValueError) as got:
            jpeg._device_scans(jnp.asarray(coef))
        assert str(got.value) == str(want.value)
        return
    want = jpeg._entropy_encode_batch(coef) if len(coef) else []
    scans, host, copied = jpeg._device_scans(jnp.asarray(coef))
    assert scans == want
    assert host == host_tiles
    slab = coef.shape[2] * coef.shape[3] // 2
    assert copied >= len(coef) * slab + host * coef[:1].nbytes
    if case is needs_stuffing:
        assert all(b"\xff\x00" in s for s in scans)


def test_device_batches_dispatch_by_size_and_place(monkeypatch):
    """``encode_coef_batch`` codes a device array of at least
    ``_DEVICE_MIN_UNITS`` units on the device, in dispatches of at most
    ``_DEVICE_PX`` pixels, and numpy input or a small device batch on the
    host — with the same JFIF bytes."""
    coef = np.tile(random_sparse(), (86, 1, 1, 1))  # 172 tiles × 192 units
    assert len(coef) * 192 >= jpeg._DEVICE_MIN_UNITS > 4 * 192
    monkeypatch.setattr(jpeg, "_DEVICE_PX", 50 * T * T)  # 50+50+50+22
    with tracing.capture() as tracer:
        host = jpeg.encode_coef_batch(coef)
        dev = jpeg.encode_coef_batch(jnp.asarray(coef))
        small = jpeg.encode_coef_batch(jnp.asarray(coef[:4]))
    assert dev == host and small == host[:4]
    got = [(sp.attrs["device_tiles"], sp.attrs["host_tiles"],
            sp.attrs["bytes_in"] > 0) for sp in tracer.spans_named(
        "jpeg.encode")]
    assert got == [(0, 172, False), (172, 0, True), (0, 4, True)]


def test_pipelined_conversion_codes_on_the_device():
    """A 2048² slide: level 0 (four 16-tile chunks) is coded on the
    device, the 4-, 2- and 1-tile chunks of the levels below on the host;
    every frame is counted once, the device level copies back less than
    1 B/px, and every level's frames equal the plain reference, whose
    per-tile encoder codes everything on the host."""
    psv = SyntheticScanner(seed=17).scan(2048, 2048, 256)
    with tracing.capture() as tracer:
        tar = convert_wsi_to_dicom(psv, {"slide_id": "dev"})
    assert study_frames(tar) == reference_level_frames(psv, 256)
    enc = tracer.spans_named("convert.encode")
    assert [sp.attrs["frames"] for sp in enc] == [64, 16, 4, 1]
    for sp in enc:
        assert sp.attrs["device_tiles"] + sp.attrs["host_tiles"] \
            == sp.attrs["frames"]
    assert enc[0].attrs["device_tiles"] == 64
    assert enc[0].attrs["bytes_in"] < 2048 * 2048
    (slide,) = tracer.spans_named("convert.slide")
    assert (slide.attrs["device_tiles"], slide.attrs["host_tiles"]) \
        == (64, 21)
