"""Batched inverse JPEG path: fused inverse-kernel differential, the
vectorized entropy decoder vs the per-tile loop (pixel identity +
coefficient-exact round-trip), and decode hardening against truncated or
garbage bitstreams."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.kernels import jpeg_inverse, jpeg_transform
from repro.kernels import ref
from repro.wsi.jpeg import (decode_coef_batch, decode_tile,
                            decode_tiles_batch, encode_coef_batch,
                            encode_tile, encode_tiles_batch)
from repro.wsi.slide import PSVReader, SyntheticScanner

RNG = np.random.default_rng(13)


def _tissue_tiles(n, hw=256, seed=3):
    rd = PSVReader(SyntheticScanner(seed=seed).scan(1024, 1024, hw))
    bh, bw = rd.grid
    tiles = [rd.read_tile(r, c) for r in range(bh) for c in range(bw)]
    return np.stack((tiles * (n // len(tiles) + 1))[:n])


# --------------------------------------------------------------------------
# fused jpeg_inverse kernel vs jnp oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,h,w", [(1, 8, 128), (2, 64, 128), (3, 32, 256)])
@pytest.mark.parametrize("seed", [0, 1])
def test_jpeg_inverse_pallas_matches_ref(n, h, w, seed):
    rng = np.random.default_rng(seed)
    tiles = rng.integers(0, 256, size=(n, 3, h, w)).astype(np.float32)
    coef = jpeg_transform(jnp.asarray(tiles))
    out = jpeg_inverse(coef, impl="pallas")
    expect = ref.jpeg_inverse_ref(coef)
    assert out.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_jpeg_inverse_batch_size_independent():
    """Pixel identity between the batched and per-tile decode paths rests
    on the fused inverse producing the same bytes for any batch size."""
    tiles = RNG.integers(0, 256, size=(4, 3, 64, 128)).astype(np.float32)
    coef = np.asarray(jpeg_transform(jnp.asarray(tiles)))
    full = np.asarray(jpeg_inverse(coef))
    for i in range(4):
        one = np.asarray(jpeg_inverse(coef[i : i + 1]))[0]
        np.testing.assert_array_equal(one, full[i])


def test_jpeg_inverse_roundtrips_transform():
    """inverse ∘ transform ≈ identity up to quantization loss."""
    tiles = _tissue_tiles(4)
    chw = np.transpose(tiles, (0, 3, 1, 2)).astype(np.float32)
    rec = np.asarray(jpeg_inverse(jpeg_transform(jnp.asarray(chw))))
    err = np.abs(rec.astype(np.int32) - chw.astype(np.int32)).mean()
    assert err < 8.0  # q50 baseline quality


def test_jpeg_inverse_unaligned_falls_back_to_ref():
    coef = jnp.asarray(RNG.integers(-64, 64, size=(2, 3, 24, 72)),
                       jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(jpeg_inverse(coef)),
        np.asarray(ref.jpeg_inverse_ref(coef)))


# --------------------------------------------------------------------------
# batched entropy decoder vs per-tile reference loop
# --------------------------------------------------------------------------
def test_decode_batch_pixel_identical_to_per_tile():
    jpgs = encode_tiles_batch(_tissue_tiles(6))
    per = np.stack([decode_tile(j) for j in jpgs])
    bat = decode_tiles_batch(jpgs)
    np.testing.assert_array_equal(per, bat)


@pytest.mark.parametrize("kind", ["noise", "flat", "gradient"])
def test_decode_batch_identical_on_adversarial_content(kind):
    """Worst cases for the lockstep decoder: dense symbols (noise), EOB
    everywhere with one outlier (flat), smooth DC drift (gradient)."""
    if kind == "noise":
        tiles = RNG.integers(0, 256, size=(3, 64, 128, 3)).astype(np.uint8)
    elif kind == "flat":
        tiles = np.full((3, 64, 128, 3), 200, np.uint8)
        tiles[1, 11, 13] = [0, 255, 7]  # one outlier block
    else:
        g = np.linspace(0, 255, 64 * 128).reshape(64, 128)
        one = np.stack([g, g[::-1], 255 - g], axis=-1).astype(np.uint8)
        tiles = np.stack([one, one[:, ::-1], one[::-1]])
    jpgs = encode_tiles_batch(tiles)
    per = np.stack([decode_tile(j) for j in jpgs])
    np.testing.assert_array_equal(per, decode_tiles_batch(jpgs))
    np.testing.assert_array_equal(
        decode_coef_batch(jpgs),
        np.asarray(jpeg_transform(jnp.asarray(
            np.transpose(tiles, (0, 3, 1, 2)).astype(np.float32)))))


def test_decode_coef_batch_is_exact_inverse():
    tiles = _tissue_tiles(5)
    chw = np.transpose(tiles, (0, 3, 1, 2)).astype(np.float32)
    coef = np.asarray(jpeg_transform(jnp.asarray(chw)))
    np.testing.assert_array_equal(
        decode_coef_batch(encode_coef_batch(coef)), coef)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 4), st.booleans())
def test_coef_roundtrip_property(seed, n, sparse):
    """encode_coef_batch → decode_coef_batch is exact for any in-range
    coefficient content (random dense and sparse blocks)."""
    rng = np.random.default_rng(seed)
    coef = rng.integers(-1023, 1024, size=(n, 3, 16, 16)).astype(np.int32)
    if sparse:
        coef *= rng.random(coef.shape) < 0.05  # long zero runs / ZRLs
    np.testing.assert_array_equal(
        decode_coef_batch(encode_coef_batch(coef)), coef)


def test_decode_batch_empty_and_geometry_guard():
    assert decode_coef_batch([]).shape == (0, 3, 0, 0)
    assert decode_tiles_batch([]).shape == (0, 0, 0, 3)
    a = encode_tile(np.zeros((8, 8, 3), np.uint8))
    b = encode_tile(np.zeros((16, 16, 3), np.uint8))
    with pytest.raises(ValueError, match="mixed tile geometries"):
        decode_coef_batch([a, b])


# --------------------------------------------------------------------------
# hardening: truncated / garbage bitstreams
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tissue_jpg():
    return encode_tile(_tissue_tiles(1, seed=7)[0])


@pytest.mark.parametrize("cut", [0, 1, 2, 3, 19, 0.25, 0.5, 0.9, -1])
def test_decode_tile_truncation_raises_corrupt(tissue_jpg, cut):
    """Truncation anywhere — header, tables, or mid-scan — must be the
    actionable corrupt-JPEG ValueError, never IndexError or a hang."""
    n = len(tissue_jpg)
    cut = int(n * cut) if isinstance(cut, float) else (n + cut if cut < 0
                                                      else cut)
    with pytest.raises(ValueError, match="corrupt JPEG"):
        decode_tile(tissue_jpg[:cut])
    with pytest.raises(ValueError, match="corrupt JPEG"):
        decode_coef_batch([tissue_jpg[:cut]])


def test_decode_tile_garbage_raises_corrupt(tissue_jpg):
    rng = np.random.default_rng(0)
    for blob in (b"", b"\xff", b"not a jpeg at all",
                 rng.integers(0, 256, 512).astype(np.uint8).tobytes(),
                 tissue_jpg[:30] + b"\x00" * 40):
        with pytest.raises(ValueError, match="corrupt JPEG"):
            decode_tile(blob)
        with pytest.raises(ValueError, match="corrupt JPEG"):
            decode_coef_batch([blob])


def test_decode_tile_scan_bitflip_never_escapes_value_error(tissue_jpg):
    """Corrupting scan bytes may still decode (a different valid stream) or
    must raise the corrupt-JPEG error — both decoders, same contract."""
    from repro.wsi.jpeg import _parse_stream

    _, _, start, _, _ = _parse_stream(tissue_jpg)
    rng = np.random.default_rng(1)
    for _ in range(12):
        mut = bytearray(tissue_jpg)
        i = rng.integers(start, len(tissue_jpg) - 2)
        mut[i] ^= 1 << int(rng.integers(0, 8))
        for api in (decode_tile, lambda b: decode_tiles_batch([b])):
            try:
                api(bytes(mut))
            except ValueError as exc:
                assert str(exc).startswith("corrupt JPEG")


def test_decode_tile_accepts_dicom_even_length_pad(tissue_jpg):
    """Encapsulated DICOM fragments pad odd-length JPEGs with one 0x00."""
    padded = tissue_jpg + b"\x00"
    np.testing.assert_array_equal(decode_tile(padded),
                                  decode_tile(tissue_jpg))
    np.testing.assert_array_equal(decode_tiles_batch([padded])[0],
                                  decode_tile(tissue_jpg))


# --------------------------------------------------------------------------
# jitted lockstep entropy engine vs the numpy oracle
# --------------------------------------------------------------------------
def _scans(jpgs):
    """Unstuffed scan arrays + geometry, as the lockstep engines see
    them."""
    from repro.wsi import jpeg as J

    scans, H, W = [], None, None
    for j in jpgs:
        H, W, s, e, _ = J._parse_stream(j)
        scans.append(J._unstuff(np.frombuffer(j, np.uint8)[s:e]))
    return scans, H, W


def _lockstep(scans, H, W, engine="auto"):
    """The lockstep decode as ``decode_coef_batch`` composes it: zigzag
    coefficients, DC slots holding differentials."""
    from repro.wsi import jpeg as J

    return J._run_packed(*J._pack_scans(scans, H, W, engine), H, W)


@pytest.mark.parametrize("kind", ["noise", "gradient"])
def test_entropy_engines_coefficient_exact(kind):
    """engine="jax" (lax.while_loop lockstep) must match engine="numpy"
    coefficient-for-coefficient, odd batch sizes included (pad lanes)."""
    if kind == "noise":
        tiles = RNG.integers(0, 256, size=(5, 64, 128, 3)).astype(np.uint8)
    else:
        g = np.linspace(0, 255, 64 * 128).reshape(64, 128)
        one = np.stack([g, g[::-1], 255 - g], axis=-1).astype(np.uint8)
        tiles = np.stack([one, one[:, ::-1], one[::-1]])
    scans, H, W = _scans(encode_tiles_batch(tiles))
    np.testing.assert_array_equal(
        _lockstep(scans, H, W, engine="jax"),
        _lockstep(scans, H, W, engine="numpy"))


def test_entropy_engines_raise_identical_errors(tissue_jpg):
    """Both engines must raise the same actionable string at the same
    failure class: truncation, garbage (invalid Huffman code)."""
    scans, H, W = _scans([tissue_jpg] * 2)
    for mutate in (
        lambda s: s[: max(4, s.size // 2)],          # mid-stream truncation
        lambda s: s[:2],                             # near-empty scan
        lambda s: RNG.integers(0, 256, s.size).astype(np.uint8),  # garbage
    ):
        bad = [scans[0], mutate(scans[1].copy())]
        errs = []
        for engine in ("jax", "numpy"):
            with pytest.raises(ValueError, match="corrupt JPEG") as ei:
                _lockstep(bad, H, W, engine=engine)
            errs.append(str(ei.value))
        assert errs[0] == errs[1], errs


def test_entropy_engine_auto_thresholds():
    """auto routes big batches to the jitted engine, tiny ones to numpy."""
    from repro.wsi import jpeg as J

    assert J._JAX_MIN_UNITS > 0 and J._JAX_MAX_BYTES > 0
    tiles = _tissue_tiles(2)
    scans, H, W = _scans(encode_tiles_batch(tiles))
    # 2 tiles × 3072 units ≥ _JAX_MIN_UNITS → the jax engine; equality with
    # the numpy oracle is the contract either way
    np.testing.assert_array_equal(
        _lockstep(scans, H, W),
        _lockstep(scans, H, W, engine="numpy"))
    assert J._pack_scans(scans, H, W)[0] == "jax"
    with pytest.raises(ValueError, match="engine"):
        _lockstep(scans, H, W, engine="cuda")


# --------------------------------------------------------------------------
# the jitted engine's 32-bit window at its widest reads
# --------------------------------------------------------------------------
def _scanner_coding():
    """The stand-in scanner's coding: 4:2:0, Annex K tables."""
    from repro.wsi import jpeg as J

    return J.Coding(sampling=((2, 2), (1, 1), (1, 1)), q=J._STANDARD.q,
                    dc=(0, 1, 1), ac=(2, 3, 3), huff=J._STANDARD.huff)


def _wide_blocks(n, rng):
    """``n`` zigzag blocks of one component in bitstream order, each with
    the widest reads a baseline scan has: a DC alternating ±600 (a
    category-11 difference after the first) and, after a run of 15 zeros,
    an AC of category 10 (symbol 0xFA: a 16-bit code and 10 bits); then a
    random count of ±1 (3 bits each), so the wide symbols start at every
    bit phase of a word."""
    zz = np.zeros((n, 64), np.int64)
    zz[:, 0] = np.where(np.arange(n) % 2, -600, 600)
    zz[:, 16] = rng.choice([-1, 1], n) * rng.integers(512, 1024, n)
    for b, f in enumerate(rng.integers(0, 47, n)):
        zz[b, 17:17 + f] = rng.choice([-1, 1], f)
    return zz


def _wide_scans(coding_name, n=3, H=64, W=128):
    """``n`` H×W tiles of ``_wide_blocks`` → unstuffed scans and the
    coding: 4:4:4 through ``encode_coef_batch``, 4:2:0 through the stand-in
    scanner's coder (``encode_coef_batch`` writes 4:4:4 only)."""
    import sys
    from pathlib import Path

    from repro.wsi import jpeg as J

    rng = np.random.default_rng(29)
    if coding_name == "444-annexk":
        nat = np.zeros((n * 3, H // 8 * (W // 8), 64), np.int64)
        for c in range(n * 3):
            nat[c][:, J._ZIGZAG] = _wide_blocks(nat.shape[1], rng)
        coef = (nat.reshape(n, 3, H // 8, W // 8, 8, 8)
                .transpose(0, 1, 2, 4, 3, 5).reshape(n, 3, H, W))
        scans, _, _ = _scans(encode_coef_batch(coef))
        return scans, J._STANDARD
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    import scanner_jpeg

    nm = (H // 16) * (W // 16)
    units = np.zeros((n, nm, 6, 64), np.int64)
    for t in range(n):
        units[t, :, :4] = _wide_blocks(nm * 4, rng).reshape(nm, 4, 64)
        units[t, :, 4] = _wide_blocks(nm, rng)
        units[t, :, 5] = _wide_blocks(nm, rng)
    return ([J._unstuff(np.frombuffer(s, np.uint8))
             for s in scanner_jpeg._scans(units)], _scanner_coding())


def _wide_symbol_starts(scan, coding, nu):
    """Bit offsets in ``scan`` where a DC category 11 and an AC 0xFA
    symbol start, found by walking the scan symbol by symbol."""
    from repro.wsi import jpeg as J

    sym_lut, len_lut = J._luts(coding.huff)
    bits = int.from_bytes(scan.tobytes() + bytes(4), "big")
    top = 8 * (scan.size + 4) - 16
    upm = len(coding.unit_comps)
    pos, starts = 0, {"dc11": [], "ac_fa": []}
    for u in range(nu):
        k = 0
        while k < 64:
            row = (coding.dc_rows if k == 0 else coding.ac_rows)[u % upm]
            code = (bits >> (top - pos)) & 0xFFFF
            sym, ln = int(sym_lut[row, code]), int(len_lut[row, code])
            if k == 0:
                if sym == 11:
                    starts["dc11"].append(pos)
                pos, k = pos + ln + sym, 1
                continue
            if sym == 0xFA:
                starts["ac_fa"].append(pos)
            pos += ln + (sym & 15)
            if sym == 0x00:
                break
            k += 16 if sym == 0xF0 else (sym >> 4) + 1
    return starts


@pytest.mark.parametrize("coding_name", ["444-annexk", "420-scanner"])
def test_entropy_engines_agree_on_the_widest_reads_at_every_phase(
        coding_name):
    """The jitted engine reads the code and the magnitude bits from one
    32-bit window joined from two words: DC category 11 and AC (15, 10)
    symbols starting at each of the 32 bit phases of a word decode
    coefficient for coefficient as the numpy engine decodes them."""
    from repro.wsi import entropy_jax
    from repro.wsi import jpeg as J

    H, W = 64, 128
    scans, coding = _wide_scans(coding_name, H=H, W=W)
    nu = coding.units(H, W)
    bit0 = entropy_jax.pack_scans(scans, nu)[1]
    for kind in ("dc11", "ac_fa"):
        phases = {int(b + p) % 32 for b, scan in zip(bit0, scans)
                  for p in _wide_symbol_starts(scan, coding, nu)[kind]}
        assert phases == set(range(32)), kind
    zz = {e: J._run_packed(*J._pack_scans(scans, H, W, e, coding), H, W,
                           coding) for e in ("numpy", "jax")}
    np.testing.assert_array_equal(zz["jax"], zz["numpy"])
    assert (np.abs(zz["jax"][..., 16]) >= 512).all()


def test_lockstep_counts_its_steps_and_lanes():
    """Blocks of zeros are two symbols each (DC category 0, EOB), so the
    loop takes exactly two steps a block; the width is the lane count
    padded to a power of two."""
    from repro.core import tracing

    scans, H, W = _scans(encode_coef_batch(np.zeros((3, 3, 64, 128),
                                                    np.int32)))
    with tracing.capture() as tracer:
        with tracing.span("decode.entropy"):
            zz = _lockstep(scans, H, W, engine="jax")
    assert not zz.any()
    (sp,) = tracer.spans
    assert sp.attrs == {"steps": 2 * 3 * 8 * 16, "lanes": 4}


def _while_body_ops(hlo: str) -> str:
    """The instructions of the compiled ``while`` loop's body and of every
    computation it calls (fusions included)."""
    import re

    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    todo = [re.search(r" while\(.*body=%?([\w.\-]+)", hlo).group(1)]
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            for line in comps[name]:
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
    return "\n".join(line for name in seen for line in comps[name])


def test_lockstep_step_reads_two_words_and_one_lut_entry():
    """At the 4:2:0 level-0 shapes of an 8192² slide (1024 lanes of 1536
    blocks), the compiled loop body gathers at most 3 times (the two words
    of the window, the packed LUT entry) and scatters once (the
    coefficient)."""
    import jax

    from repro.wsi import entropy_jax

    coding = _scanner_coding()
    lanes = [jax.ShapeDtypeStruct((1024,), jnp.int32)] * 3
    hlo = entropy_jax._lockstep.lower(
        jax.ShapeDtypeStruct((1 << 23,), jnp.uint32), *lanes,
        jax.ShapeDtypeStruct((4 * 65536,), jnp.int32),
        nu=coding.units(256, 256), dc_rows=coding.dc_rows,
        ac_rows=coding.ac_rows).compile().as_text()
    body = _while_body_ops(hlo)
    assert body.count(" gather(") <= 3
    assert body.count(" scatter(") == 1


@pytest.mark.parametrize("hw,n,engine", [(256, 2, "jax"), (64, 2, "numpy")])
def test_decode_path_spans_name_its_stages(hw, n, engine):
    """The batched decode runs as ``decode.parse`` (container parse,
    unstuffing, packing), ``decode.entropy`` (the lockstep loop of the
    engine ``auto`` picked, and its fetch), ``decode.scatter`` (DC
    integration, zigzag scatter) and ``decode.inverse``; the pixels are
    the same with the tracer armed."""
    from repro.core import tracing

    jpgs = encode_tiles_batch(_tissue_tiles(n, hw=hw))
    plain = decode_tiles_batch(jpgs)
    with tracing.capture() as tracer:
        traced = decode_tiles_batch(jpgs)
    np.testing.assert_array_equal(plain, traced)
    # the jitted loop also records its trip count and width
    loop = tracer.spans[1].attrs.get("steps")
    assert (loop is None) == (engine == "numpy")
    counts = {} if loop is None else {"steps": loop, "lanes": n}
    assert loop is None or loop >= 2 * 3 * (hw // 8) ** 2
    assert [(sp.name, sp.attrs) for sp in tracer.spans] == [
        ("decode.parse", {"frames": n}),
        ("decode.entropy", {"engine": engine, **counts}),
        ("decode.scatter", {}),
        ("decode.inverse", {})]
    assert all(sp.status == "ok" for sp in tracer.spans)
