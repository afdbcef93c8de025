"""Plain reference for the slide -> DICOM -> TIFF path, written from the
standards and sharing nothing with the system under test.

- Pyramid: each level is the 2x2 box mean of the one above, rounded half
  to even, in exact integer arithmetic; a level is added while the short
  edge halved stays at or above ``min_level_size``.
- Forward JPEG transform (ITU-T T.81 baseline, JFIF colour): level-shifted
  BT.601 YCbCr, 8x8 DCT-II, quantisation by the Annex K tables (quality
  50), rounded half to even. Computed in float64.
- Inverse: dequantise, 8x8 inverse DCT, YCbCr -> RGB, round, clip to
  [0, 255]. Float64.
- A baseline Huffman decoder that reads the quantised coefficients back
  out of a stored JPEG frame, and a tiled-TIFF tile reader.
- The control: the same transforms with every matrix product computed in
  three bfloat16 passes (``hi*hi + hi*lo + lo*hi``), which is what an
  accelerator's "high" float32 precision does; the configuration states
  float32 at "highest".
"""
from __future__ import annotations

import struct
import zlib

import numpy as np

#: ITU-T T.81 Annex K.1, Table K.1 (luminance) and K.2 (chrominance)
LUMA_Q = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99]], np.float64)
CHROMA_Q = np.array([
    [17, 18, 24, 47, 99, 99, 99, 99],
    [18, 21, 26, 66, 99, 99, 99, 99],
    [24, 26, 56, 99, 99, 99, 99, 99],
    [47, 66, 99, 99, 99, 99, 99, 99]] + [[99] * 8] * 4, np.float64)
QTABLES = (LUMA_Q, CHROMA_Q, CHROMA_Q)

#: zigzag scan position -> natural (row-major) index, T.81 Figure A.6
ZIGZAG = np.array([
    0, 1, 8, 16, 9, 2, 3, 10, 17, 24, 32, 25, 18, 11, 4, 5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6, 7, 14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63])


def dct_matrix() -> np.ndarray:
    """Orthonormal 8-point DCT-II matrix (float64): ``X^ = C X C^T``."""
    k = np.arange(8)
    c = np.cos((2 * k[None, :] + 1) * k[:, None] * np.pi / 16) * np.sqrt(0.25)
    c[0] /= np.sqrt(2.0)
    return c


C = dct_matrix()


# ---------------------------------------------------------------- pyramid
def level_dims(size: int, min_level_size: int) -> list[int]:
    """Sides of the pyramid levels of a square slide."""
    dims = [size]
    while dims[-1] // 2 >= min_level_size:
        dims.append(dims[-1] // 2)
    return dims


def downsample(img: np.ndarray) -> np.ndarray:
    """(H, W, 3) uint8 -> (H/2, W/2, 3): 2x2 mean rounded half to even."""
    s = (img[0::2, 0::2].astype(np.uint16) + img[1::2, 0::2]
         + img[0::2, 1::2] + img[1::2, 1::2])
    q, r = s >> 2, s & 3
    return (q + (r == 3) + ((r == 2) & (q & 1).astype(bool))).astype(np.uint8)


def pyramid(img: np.ndarray, n_levels: int) -> list[np.ndarray]:
    out = [img]
    while len(out) < n_levels:
        out.append(downsample(out[-1]))
    return out


# ------------------------------------------------------- float64 transforms
def _ycbcr(rgb: np.ndarray) -> np.ndarray:
    """(T, T, 3) RGB -> (3, T, T) level-shifted YCbCr (JFIF)."""
    r, g, b = (rgb[..., i].astype(np.float64) for i in range(3))
    return np.stack([0.299 * r + 0.587 * g + 0.114 * b - 128.0,
                     -0.168736 * r - 0.331264 * g + 0.5 * b,
                     0.5 * r - 0.418688 * g - 0.081312 * b])


def _blocks(planes: np.ndarray) -> np.ndarray:
    """(3, T, T) -> (3, T/8, 8, T/8, 8)."""
    c, h, w = planes.shape
    return planes.reshape(c, h // 8, 8, w // 8, 8)


def _q3() -> np.ndarray:
    return np.stack(QTABLES)[:, None, :, None, :]


#: relative width of the band around a rounding boundary inside which the
#: stated precision (float32) cannot decide the rounding: a value v whose
#: distance to the nearest half-integer is under BAND * max(|v|, 16) in its
#: own units (DCT output for coefficients, pixel value for pixels) is left
#: out of the comparison. float32's own error is about 1e-7 relative.
BAND = 1e-6


def _ambiguous(v: np.ndarray, scale) -> np.ndarray:
    """v is a value before rounding, in units of ``scale``."""
    t = v / scale
    return np.abs(np.abs(t - np.floor(t)) - 0.5) * scale \
        < BAND * np.maximum(np.abs(v), 16.0)


def forward(tile: np.ndarray, matmul=None) -> tuple[np.ndarray, np.ndarray]:
    """(T, T, 3) uint8 RGB -> (3, T, T) int32 quantised DCT coefficients,
    blocks in place, and the mask of those the band leaves out.
    ``matmul`` replaces the float64 products (the control)."""
    x = _blocks(_ycbcr(tile))
    if matmul is None:
        y = np.einsum("ui,caibr,vr->caubv", C, x, C, optimize=True)
    else:
        y = _blockwise(x, C, matmul)
    q = np.round(y / _q3())
    shape = (3, tile.shape[0], tile.shape[1])
    return (q.reshape(shape).astype(np.int32),
            _ambiguous(y, _q3()).reshape(shape))


def inverse(coef: np.ndarray, matmul=None) -> tuple[np.ndarray, np.ndarray]:
    """(3, T, T) quantised coefficients -> (T, T, 3) uint8 RGB, and the
    mask of samples the band leaves out."""
    x = _blocks(coef.astype(np.float64)) * _q3()
    if matmul is None:
        p = np.einsum("ui,caubv,vr->caibr", C, x, C, optimize=True)
    else:
        p = _blockwise(x, C.T, matmul)
    y, cb, cr = p.reshape(3, coef.shape[1], coef.shape[2])
    y = y + 128.0
    rgb = np.stack([y + 1.402 * cr, y - 0.344136 * cb - 0.714136 * cr,
                    y + 1.772 * cb], axis=-1)
    return (np.clip(np.round(rgb), 0, 255).astype(np.uint8),
            _ambiguous(rgb, 1.0))


def _blockwise(x: np.ndarray, m: np.ndarray, matmul) -> np.ndarray:
    """``m @ B @ m^T`` for every 8x8 block B of x (3, a, 8, b, 8), with
    the products done by ``matmul`` on float32 operands."""
    c, a, _, b, _ = x.shape
    blk = x.transpose(0, 1, 3, 2, 4).reshape(-1, 8, 8).astype(np.float32)
    m32 = m.astype(np.float32)
    t = matmul(np.broadcast_to(m32, blk.shape), blk)
    t = matmul(t, np.broadcast_to(m32.T, blk.shape))
    return t.reshape(c, a, b, 8, 8).transpose(0, 1, 3, 2, 4).astype(np.float64)


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept in f32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def matmul_bf16x3(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """float32 product in three bfloat16 passes, accumulated in float32:
    the accelerator's "high" precision, one step below "highest"."""
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (np.matmul(ah, bh) + np.matmul(ah, bl)) + np.matmul(al, bh)


# --------------------------------------------------------- baseline decoder
def _huff_lut(bits: list[int], vals: list[int]) -> tuple[list, list]:
    """T.81 Annex C code assignment -> 16-bit peek tables (symbol, length);
    length 0 marks an invalid code."""
    sym = np.zeros(1 << 16, np.int32)
    ln = np.zeros(1 << 16, np.int32)
    code = k = 0
    for length in range(1, 17):
        for _ in range(bits[length - 1]):
            lo = code << (16 - length)
            hi = (code + 1) << (16 - length)
            sym[lo:hi], ln[lo:hi] = vals[k], length
            code, k = code + 1, k + 1
        code <<= 1
    return sym.tolist(), ln.tolist()


def _segments(jpg: bytes):
    """Yield (marker, payload) up to SOS; then ('scan', entropy bytes)."""
    if jpg[:2] != b"\xff\xd8":
        raise ValueError("no SOI")
    pos = 2
    while pos + 4 <= len(jpg):
        if jpg[pos] != 0xFF:
            raise ValueError(f"no marker at {pos}")
        code = jpg[pos + 1]
        (n,) = struct.unpack_from(">H", jpg, pos + 2)
        payload = jpg[pos + 4:pos + 2 + n]
        yield code, payload
        pos += 2 + n
        if code == 0xDA:
            end = jpg.rfind(b"\xff\xd9")
            if end < pos:
                raise ValueError("no EOI")
            yield "scan", jpg[pos:end]
            return
    raise ValueError("no SOS")


def decode_coefficients(jpg: bytes) -> np.ndarray:
    """One baseline, 3-component, non-subsampled JPEG -> (3, H, W) int32
    quantised coefficients, blocks in place."""
    tables, comps, H = {}, [], None
    for code, seg in _segments(jpg):
        if code == 0xC4:
            p = 0
            while p < len(seg):
                tc_th = seg[p]
                bits = list(seg[p + 1:p + 17])
                vals = list(seg[p + 17:p + 17 + sum(bits)])
                tables[tc_th >> 4, tc_th & 15] = _huff_lut(bits, vals)
                p += 17 + sum(bits)
        elif code == 0xC0:
            _, H, W, nc = struct.unpack_from(">BHHB", seg)
            if nc != 3 or any(seg[7 + 3 * i] != 0x11 for i in range(3)):
                raise ValueError("not 3-component 4:4:4 baseline")
        elif code == 0xDA:
            ns = seg[0]
            comps = [(seg[2 + 2 * i] >> 4, seg[2 + 2 * i] & 15)
                     for i in range(ns)]
        elif code == "scan":
            scan = seg
    if H is None or len(comps) != 3:
        raise ValueError("no frame header or scan")
    data = np.frombuffer(scan.replace(b"\xff\x00", b"\xff"), np.uint8)
    bits = np.unpackbits(np.concatenate([data, np.zeros(4, np.uint8)]))
    peek = np.zeros(len(bits) - 16, np.int64)
    for k in range(16):
        peek = (peek << 1) | bits[k:k + len(peek)]
    peek = peek.tolist()
    nbits = 8 * len(data)
    luts = [(tables[0, td], tables[1, ta]) for td, ta in comps]
    bh, bw = H // 8, W // 8
    out = np.zeros((bh * bw, 3, 64), np.int32)
    flat = out.reshape(-1).tolist()
    zz = ZIGZAG.tolist()
    pred = [0, 0, 0]
    pos = 0
    for blk in range(bh * bw):
        for c in range(3):
            (dsym, dlen), (asym, alen) = luts[c]
            base = (blk * 3 + c) * 64
            v = peek[pos]
            s, n = dsym[v], dlen[v]
            if not n:
                raise ValueError("invalid DC code")
            pos += n
            diff = 0
            if s:
                m = peek[pos] >> (16 - s)
                pos += s
                diff = m if m >> (s - 1) else m - (1 << s) + 1
            pred[c] += diff
            flat[base] = pred[c]
            k = 1
            while k < 64:
                v = peek[pos]
                rs, n = asym[v], alen[v]
                if not n:
                    raise ValueError("invalid AC code")
                pos += n
                r, s = rs >> 4, rs & 15
                if not s:
                    if r != 15:
                        break
                    k += 16
                    continue
                k += r
                if k > 63:
                    raise ValueError("AC run past the block")
                m = peek[pos] >> (16 - s)
                pos += s
                flat[base + zz[k]] = m if m >> (s - 1) else m - (1 << s) + 1
                k += 1
            if pos > nbits:
                raise ValueError("scan truncated")
    coef = np.asarray(flat, np.int32).reshape(bh, bw, 3, 8, 8)
    return coef.transpose(2, 0, 3, 1, 4).reshape(3, H, W)


# --------------------------------------------------------------- TIFF tiles
def tiff_tile(data: bytes, index: int) -> tuple[np.ndarray, tuple[int, int]]:
    """Tile ``index`` (row-major) of a little-endian tiled RGB TIFF, and the
    image's (H, W)."""
    if data[:4] != b"II*\0":
        raise ValueError("not a little-endian classic TIFF")
    (ifd,) = struct.unpack_from("<I", data, 4)
    (n,) = struct.unpack_from("<H", data, ifd)
    tags = {}
    for i in range(n):
        tag, typ, count = struct.unpack_from("<HHI", data, ifd + 2 + 12 * i)
        if typ not in (3, 4):
            continue
        fmt = "H" if typ == 3 else "I"
        pos = ifd + 2 + 12 * i + 8
        if count * (2 if typ == 3 else 4) > 4:
            (pos,) = struct.unpack_from("<I", data, pos)
        tags[tag] = struct.unpack_from(f"<{count}{fmt}", data, pos)
    t = tags[322][0]
    off, cnt = tags[324][index], tags[325][index]
    raw = data[off:off + cnt]
    if tags.get(259, (1,))[0] in (8, 32946):
        raw = zlib.decompress(raw)
    return (np.frombuffer(raw, np.uint8).reshape(t, t, 3),
            (tags[257][0], tags[256][0]))
