"""Plain reference for transcoding a JPEG SVS, extending ``reference`` and
sharing nothing with the system under test.

- A baseline Huffman decoder for streams with subsampled chroma: tables
  and sampling factors from the stream, one block at a time, one DC
  predictor per component (ITU-T T.81, F.2).
- The scanner decode, float64: dequantise the scanner's own coefficients
  by its tables, 8x8 inverse DCT, upsample the chroma by the triangle
  filter of centred (JFIF) siting — each output sample 3/4 of the nearer
  chroma sample plus 1/4 of the next one out, vertically then
  horizontally, replicating at each tile's edge (a tile is its own JPEG
  image) — YCbCr -> RGB, round half to even, clip to [0, 255]. This is
  libjpeg's h2v2 "fancy" upsampler in float, without its integer rounding
  bias. Vectorised over a whole slide, in bands of tile rows.
- The samples whose rounding the stated float32 precision cannot decide
  (``reference.BAND``), which the comparison leaves out with every
  coefficient of a pyramid block whose footprint covers one.
"""
from __future__ import annotations

import struct

import numpy as np

import reference


def upsample(c: np.ndarray, axis: int) -> np.ndarray:
    """Double ``c`` along ``axis`` by the centred triangle filter, the edge
    samples replicated."""
    n = c.shape[axis]
    i = np.arange(n)
    prev = np.take(c, np.maximum(i - 1, 0), axis=axis)
    nxt = np.take(c, np.minimum(i + 1, n - 1), axis=axis)
    out = np.stack([0.75 * c + 0.25 * prev, 0.75 * c + 0.25 * nxt],
                   axis=axis + 1)
    shape = list(c.shape)
    shape[axis] = 2 * n
    return out.reshape(shape)


def _idct(coef: np.ndarray, q: np.ndarray, matmul=None) -> np.ndarray:
    """(h, w) quantised coefficients (blocks in place) -> (h, w) samples."""
    h, w = coef.shape
    x = coef.astype(np.float64).reshape(h // 8, 8, w // 8, 8) \
        * q[None, :, None, :]
    C = reference.C
    if matmul is None:
        p = np.einsum("ui,aubv,vr->aibr", C, x, C, optimize=True)
    else:
        p = reference._blockwise(x[None], C.T, matmul)[0]
    return p.reshape(h, w)


def decode_band(y: np.ndarray, cb: np.ndarray, cr: np.ndarray,
                q_luma: np.ndarray, q_chroma: np.ndarray, tile: int,
                matmul=None) -> tuple[np.ndarray, np.ndarray]:
    """One band of tile rows of 4:2:0 coefficient planes -> (RGB uint8
    (h, w, 3), samples the band leaves out (h, w) bool)."""
    yp = _idct(y, q_luma, matmul) + 128.0
    nt = y.shape[1] // tile
    chroma = []
    for c in (cb, cr):
        p = _idct(c, q_chroma, matmul)
        t = p.reshape(tile // 2, nt, tile // 2).transpose(1, 0, 2)
        t = upsample(upsample(t, 1), 2)
        chroma.append(t.transpose(1, 0, 2).reshape(tile, nt * tile))
    pb, pr = chroma
    rgb = np.stack([yp + 1.402 * pr, yp - 0.344136 * pb - 0.714136 * pr,
                    yp + 1.772 * pb], axis=-1)
    return (np.clip(np.round(rgb), 0, 255).astype(np.uint8),
            reference._ambiguous(rgb, 1.0).any(axis=-1))


def scanner_decode(record: dict, q_luma: np.ndarray, q_chroma: np.ndarray,
                   matmul=None) -> tuple[np.ndarray, np.ndarray]:
    """The scanner's level 0 decoded from its own coefficients: (H, W, 3)
    uint8 and the (H, W) mask of samples within the band of a rounding
    boundary. ``matmul`` replaces the float64 products (the control)."""
    y, cb, cr, t = record["y"], record["cb"], record["cr"], record["tile"]
    H, W = y.shape
    img = np.empty((H, W, 3), np.uint8)
    amb = np.empty((H, W), bool)
    for r in range(0, H, t):
        c = slice(r // 2, (r + t) // 2)
        img[r:r + t], amb[r:r + t] = decode_band(
            y[r:r + t], cb[c], cr[c], q_luma, q_chroma, t, matmul)
    return img, amb


def footprint(amb: np.ndarray, level: int, block: int = 8) -> np.ndarray:
    """Level-0 left-out samples -> the level-``level`` blocks whose
    footprint (block · 2^level level-0 samples a side) covers one."""
    f = block << level
    H, W = amb.shape
    return amb.reshape(H // f, f, W // f, f).any(axis=(1, 3))


# ------------------------------------------------------------------ decoder
def decode_components(jpg: bytes) -> list[np.ndarray]:
    """One baseline 3-component JPEG (any of 4:4:4, 4:2:2, 4:2:0; Y
    carrying the largest sampling) -> per component its (h, w) int32
    quantised coefficients, blocks in place."""
    tables, comps, frame = {}, [], None
    for code, seg in reference._segments(jpg):
        if code == 0xC4:
            p = 0
            while p < len(seg):
                bits = list(seg[p + 1:p + 17])
                vals = list(seg[p + 17:p + 17 + sum(bits)])
                tables[seg[p] >> 4, seg[p] & 15] = reference._huff_lut(
                    bits, vals)
                p += 17 + sum(bits)
        elif code == 0xC0:
            _, H, W, nc = struct.unpack_from(">BHHB", seg)
            frame = [(seg[7 + 3 * i] >> 4, seg[7 + 3 * i] & 15)
                     for i in range(nc)]
        elif code == 0xDA:
            comps = [(seg[2 + 2 * i] >> 4, seg[2 + 2 * i] & 15)
                     for i in range(seg[0])]
        elif code == "scan":
            scan = seg
    if frame is None or len(comps) != 3:
        raise ValueError("no frame header or scan")
    hm, vm = max(h for h, _ in frame), max(v for _, v in frame)
    mr, mc = H // (8 * vm), W // (8 * hm)
    data = np.frombuffer(scan.replace(b"\xff\x00", b"\xff"), np.uint8)
    bits = np.unpackbits(np.concatenate([data, np.zeros(4, np.uint8)]))
    peek = np.zeros(len(bits) - 16, np.int64)
    for k in range(16):
        peek = (peek << 1) | bits[k:k + len(peek)]
    peek = peek.tolist()
    out = [np.zeros((mr * v, mc * h, 64), np.int32) for h, v in frame]
    zz = reference.ZIGZAG.tolist()
    pred = [0, 0, 0]
    pos = 0
    for m in range(mr * mc):
        r, c = divmod(m, mc)
        for ci, (h, v) in enumerate(frame):
            (dsym, dlen), (asym, alen) = tables[0, comps[ci][0]], \
                tables[1, comps[ci][1]]
            for k in range(h * v):
                blk = [0] * 64
                s, n = dsym[peek[pos]], dlen[peek[pos]]
                if not n:
                    raise ValueError("invalid DC code")
                pos += n
                diff = 0
                if s:
                    m_ = peek[pos] >> (16 - s)
                    pos += s
                    diff = m_ if m_ >> (s - 1) else m_ - (1 << s) + 1
                pred[ci] += diff
                blk[0] = pred[ci]
                z = 1
                while z < 64:
                    rs, n = asym[peek[pos]], alen[peek[pos]]
                    if not n:
                        raise ValueError("invalid AC code")
                    pos += n
                    run, s = rs >> 4, rs & 15
                    if not s:
                        if run != 15:
                            break
                        z += 16
                        continue
                    z += run
                    if z > 63:
                        raise ValueError("AC run past the block")
                    m_ = peek[pos] >> (16 - s)
                    pos += s
                    blk[zz[z]] = m_ if m_ >> (s - 1) else m_ - (1 << s) + 1
                    z += 1
                out[ci][r * v + k // h, c * h + k % h] = blk
    planes = []
    for blocks in out:
        bh, bw = blocks.shape[:2]
        planes.append(blocks.reshape(bh, bw, 8, 8).transpose(0, 2, 1, 3)
                      .reshape(bh * 8, bw * 8))
    return planes


def segments(jpg: bytes) -> tuple[list[tuple[int, bytes]], bytes]:
    """A stream's marker segments before the scan, and its entropy-coded
    data (SOS header to EOI, exclusive). A tables-only stream has none."""
    segs, pos = [], 2
    while pos + 4 <= len(jpg) and jpg[pos] == 0xFF and jpg[pos + 1] != 0xD9:
        (n,) = struct.unpack_from(">H", jpg, pos + 2)
        segs.append((jpg[pos + 1], jpg[pos + 4:pos + 2 + n]))
        pos += 2 + n
        if segs[-1][0] == 0xDA:
            return segs, jpg[pos:jpg.rfind(b"\xff\xd9")]
    return segs, b""
