"""A stand-in slide scanner that writes what real ones write: an
Aperio-shaped SVS whose tiles are baseline JPEG.

Level 0 is rendered with ``scanner.render_band`` and encoded here in
vectorised numpy, as a scanner's own codec would: JFIF YCbCr, chroma
box-averaged 2x2 (4:2:0), float64 8x8 DCT, the Annex K tables scaled to
quality 70 the IJG way, Annex K Huffman tables (libjpeg's default without
optimisation). Every tile is an abbreviated stream (SOI, SOF0, SOS, scan,
EOI); the tables they share sit once in the TIFF ``JPEGTables`` tag (TIFF
Technical Note 2, Compression 7). The file chains three IFDs, as an SVS
does: level 0 (tiled JPEG), a stripped uncompressed thumbnail, and one
reduced tiled JPEG level. Nothing here imports the system under test, and
no third-party codec is used.
"""
from __future__ import annotations

import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
from scanner import render_band

#: the scanner's JPEG quality (the ``Q=70`` of public Aperio descriptions)
QUALITY = 70

# ITU-T T.81 Annex K.3: Huffman tables (BITS, HUFFVAL)
DC_L = ([0, 1, 5, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0], list(range(12)))
DC_C = ([0, 3, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0], list(range(12)))
AC_L = ([0, 2, 1, 3, 3, 2, 4, 3, 5, 5, 4, 4, 0, 0, 1, 0x7D], [
    0x01, 0x02, 0x03, 0x00, 0x04, 0x11, 0x05, 0x12, 0x21, 0x31, 0x41, 0x06,
    0x13, 0x51, 0x61, 0x07, 0x22, 0x71, 0x14, 0x32, 0x81, 0x91, 0xA1, 0x08,
    0x23, 0x42, 0xB1, 0xC1, 0x15, 0x52, 0xD1, 0xF0, 0x24, 0x33, 0x62, 0x72,
    0x82, 0x09, 0x0A, 0x16, 0x17, 0x18, 0x19, 0x1A, 0x25, 0x26, 0x27, 0x28,
    0x29, 0x2A, 0x34, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3A, 0x43, 0x44, 0x45,
    0x46, 0x47, 0x48, 0x49, 0x4A, 0x53, 0x54, 0x55, 0x56, 0x57, 0x58, 0x59,
    0x5A, 0x63, 0x64, 0x65, 0x66, 0x67, 0x68, 0x69, 0x6A, 0x73, 0x74, 0x75,
    0x76, 0x77, 0x78, 0x79, 0x7A, 0x83, 0x84, 0x85, 0x86, 0x87, 0x88, 0x89,
    0x8A, 0x92, 0x93, 0x94, 0x95, 0x96, 0x97, 0x98, 0x99, 0x9A, 0xA2, 0xA3,
    0xA4, 0xA5, 0xA6, 0xA7, 0xA8, 0xA9, 0xAA, 0xB2, 0xB3, 0xB4, 0xB5, 0xB6,
    0xB7, 0xB8, 0xB9, 0xBA, 0xC2, 0xC3, 0xC4, 0xC5, 0xC6, 0xC7, 0xC8, 0xC9,
    0xCA, 0xD2, 0xD3, 0xD4, 0xD5, 0xD6, 0xD7, 0xD8, 0xD9, 0xDA, 0xE1, 0xE2,
    0xE3, 0xE4, 0xE5, 0xE6, 0xE7, 0xE8, 0xE9, 0xEA, 0xF1, 0xF2, 0xF3, 0xF4,
    0xF5, 0xF6, 0xF7, 0xF8, 0xF9, 0xFA])
AC_C = ([0, 2, 1, 2, 4, 4, 3, 4, 7, 5, 4, 4, 0, 1, 2, 0x77], [
    0x00, 0x01, 0x02, 0x03, 0x11, 0x04, 0x05, 0x21, 0x31, 0x06, 0x12, 0x41,
    0x51, 0x07, 0x61, 0x71, 0x13, 0x22, 0x32, 0x81, 0x08, 0x14, 0x42, 0x91,
    0xA1, 0xB1, 0xC1, 0x09, 0x23, 0x33, 0x52, 0xF0, 0x15, 0x62, 0x72, 0xD1,
    0x0A, 0x16, 0x24, 0x34, 0xE1, 0x25, 0xF1, 0x17, 0x18, 0x19, 0x1A, 0x26,
    0x27, 0x28, 0x29, 0x2A, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3A, 0x43, 0x44,
    0x45, 0x46, 0x47, 0x48, 0x49, 0x4A, 0x53, 0x54, 0x55, 0x56, 0x57, 0x58,
    0x59, 0x5A, 0x63, 0x64, 0x65, 0x66, 0x67, 0x68, 0x69, 0x6A, 0x73, 0x74,
    0x75, 0x76, 0x77, 0x78, 0x79, 0x7A, 0x82, 0x83, 0x84, 0x85, 0x86, 0x87,
    0x88, 0x89, 0x8A, 0x92, 0x93, 0x94, 0x95, 0x96, 0x97, 0x98, 0x99, 0x9A,
    0xA2, 0xA3, 0xA4, 0xA5, 0xA6, 0xA7, 0xA8, 0xA9, 0xAA, 0xB2, 0xB3, 0xB4,
    0xB5, 0xB6, 0xB7, 0xB8, 0xB9, 0xBA, 0xC2, 0xC3, 0xC4, 0xC5, 0xC6, 0xC7,
    0xC8, 0xC9, 0xCA, 0xD2, 0xD3, 0xD4, 0xD5, 0xD6, 0xD7, 0xD8, 0xD9, 0xDA,
    0xE2, 0xE3, 0xE4, 0xE5, 0xE6, 0xE7, 0xE8, 0xE9, 0xEA, 0xF2, 0xF3, 0xF4,
    0xF5, 0xF6, 0xF7, 0xF8, 0xF9, 0xFA])


def quality_tables(quality: int = QUALITY) -> tuple[np.ndarray, np.ndarray]:
    """Annex K luma and chroma tables scaled to ``quality`` as the IJG
    library does (``jpeg_quality_scaling``), clamped to [1, 255]."""
    scale = 5000 // quality if quality < 50 else 200 - 2 * quality
    return tuple(np.clip((t.astype(np.int64) * scale + 50) // 100, 1, 255)
                 .astype(np.float64)
                 for t in (reference.LUMA_Q, reference.CHROMA_Q))


Q_LUMA, Q_CHROMA = quality_tables()


def _codes(bits, vals) -> tuple[np.ndarray, np.ndarray]:
    """T.81 Annex C code assignment -> (code, length) per symbol value."""
    codes = np.zeros(256, np.int64)
    lens = np.zeros(256, np.int64)
    code = k = 0
    for ln in range(1, 17):
        for _ in range(bits[ln - 1]):
            codes[vals[k]], lens[vals[k]] = code, ln
            code, k = code + 1, k + 1
        code <<= 1
    return codes, lens


#: [luma, chroma] x (DC, AC) code tables
_DC = [_codes(*DC_L), _codes(*DC_C)]
_AC = [_codes(*AC_L), _codes(*AC_C)]


def _segment(code: int, payload: bytes) -> bytes:
    return struct.pack(">BBH", 0xFF, code, len(payload) + 2) + payload


def jpeg_tables() -> bytes:
    """The tables-only stream of the ``JPEGTables`` tag: SOI, DQT, DHT, EOI."""
    out = b"\xff\xd8"
    for tid, q in enumerate((Q_LUMA, Q_CHROMA)):
        out += _segment(0xDB, bytes([tid]) + bytes(
            int(v) for v in q.reshape(64)[reference.ZIGZAG]))
    for cls_id, (bits, vals) in ((0x00, DC_L), (0x10, AC_L), (0x01, DC_C),
                                 (0x11, AC_C)):
        out += _segment(0xC4, bytes([cls_id]) + bytes(bits) + bytes(vals))
    return out + b"\xff\xd9"


def tile_header(tile: int) -> bytes:
    """SOI, SOF0 (Y 2x2, Cb and Cr 1x1) and SOS of an abbreviated tile."""
    sof = struct.pack(">BHHB", 8, tile, tile, 3) + bytes(
        [1, 0x22, 0, 2, 0x11, 1, 3, 0x11, 1])
    sos = bytes([3, 1, 0x00, 2, 0x11, 3, 0x11, 0, 63, 0])
    return b"\xff\xd8" + _segment(0xC0, sof) + _segment(0xDA, sos)


# ------------------------------------------------------------------ encode
def _ycbcr(rgb: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(h, w, 3) RGB -> level-shifted Y (h, w) and centred Cb, Cr box-averaged
    to (h/2, w/2), float64 (JFIF)."""
    r, g, b = (rgb[..., i].astype(np.float64) for i in range(3))
    y = 0.299 * r + 0.587 * g + 0.114 * b - 128.0
    cb = -0.168736 * r - 0.331264 * g + 0.5 * b
    cr = 0.5 * r - 0.418688 * g - 0.081312 * b

    def box(p):
        return 0.25 * (p[0::2, 0::2] + p[0::2, 1::2] + p[1::2, 0::2]
                       + p[1::2, 1::2])
    return y, box(cb), box(cr)


def _quantise(plane: np.ndarray, q: np.ndarray) -> np.ndarray:
    """(h, w) samples -> (h, w) int32 quantised DCT coefficients, blocks in
    place, float64, rounded half to even."""
    h, w = plane.shape
    x = plane.reshape(h // 8, 8, w // 8, 8)
    C = reference.C
    y = np.einsum("ui,aibr,vr->aubv", C, x, C, optimize=True)
    return np.round(y / q[None, :, None, :]).reshape(h, w).astype(np.int32)


def _zz_blocks(plane: np.ndarray) -> np.ndarray:
    """(h, w) blocks in place -> (h/8, w/8, 64) zigzag order."""
    h, w = plane.shape
    return (plane.reshape(h // 8, 8, w // 8, 8).transpose(0, 2, 1, 3)
            .reshape(h // 8, w // 8, 64)[:, :, reference.ZIGZAG])


def _units(y: np.ndarray, cb: np.ndarray, cr: np.ndarray,
           tile: int) -> np.ndarray:
    """Coefficient planes of one band of tiles -> (tiles, MCUs, 6, 64)
    zigzag blocks in bitstream order (MCUs row-major within a tile; each
    MCU Y0 Y1 Y2 Y3 Cb Cr)."""
    nt, m = y.shape[1] // tile, tile // 16
    yb = _zz_blocks(y).reshape(m, 2, nt, m, 2, 64).transpose(2, 0, 3, 1, 4, 5)
    cs = [_zz_blocks(c).reshape(m, nt, m, 1, 64).transpose(1, 0, 2, 3, 4)
          for c in (cb, cr)]
    return np.concatenate([yb.reshape(nt, m, m, 4, 64)] + cs, axis=3) \
        .reshape(nt, m * m, 6, 64)


def _category(v: np.ndarray) -> np.ndarray:
    return np.frexp(np.abs(v).astype(np.float64))[1].astype(np.int64)


def _scans(units: np.ndarray) -> list[bytes]:
    """Huffman-code every tile's units -> one entropy-coded segment per
    tile (flush-padded with 1-bits, 0xFF stuffed)."""
    nt, nm = units.shape[:2]
    chroma = np.array([0, 0, 0, 0, 1, 1])
    u = units.reshape(nt, nm * 6, 64).astype(np.int64)
    nu = nm * 6
    # DC differences per component within each tile
    dc = u[:, :, 0].reshape(nt, nm, 6)
    ydc = dc[:, :, :4].reshape(nt, nm * 4)
    diffs = [np.diff(ydc, axis=1, prepend=0).reshape(nt, nm, 4)]
    for c in (4, 5):
        diffs.append(np.diff(dc[:, :, c], axis=1, prepend=0)[:, :, None])
    diff = np.concatenate(diffs, axis=2).reshape(-1)
    unit_chroma = np.tile(chroma, nt * nm)
    gi = np.arange(nt * nu)  # global unit index, bitstream order
    keys, codes, lens = [], [], []

    def emit(key, code, ln):
        keys.append(key)
        codes.append(code)
        lens.append(ln)

    s = _category(diff)
    for t in (0, 1):
        sel = unit_chroma == t
        c, n = _DC[t][0][s[sel]], _DC[t][1][s[sel]]
        emit(gi[sel] * 256, c, n)
    mag = s > 0
    emit(gi[mag] * 256 + 1, np.where(diff[mag] >= 0, diff[mag],
                                     diff[mag] + (1 << s[mag]) - 1), s[mag])
    ac = u.reshape(nt * nu, 64)[:, 1:]
    bi, pz = np.nonzero(ac)
    vals = ac[bi, pz]
    first = np.ones(bi.size, bool)
    first[1:] = bi[1:] != bi[:-1]
    prev = np.concatenate(([0], pz[:-1]))
    run = np.where(first, pz, pz - prev - 1)
    sa = _category(vals)
    base = bi * 256 + (pz + 1) * 4
    tab = unit_chroma[bi]
    nzrl = run >> 4
    if nzrl.any():
        rep = np.repeat(np.arange(bi.size), nzrl)
        j = np.arange(rep.size) - np.repeat(np.cumsum(nzrl) - nzrl, nzrl)
        zt = tab[rep]
        emit(base[rep] - 3 + j, np.where(zt, _AC[1][0][0xF0], _AC[0][0][0xF0]),
             np.where(zt, _AC[1][1][0xF0], _AC[0][1][0xF0]))
    sym = ((run & 15) << 4) | sa
    emit(base + 1, np.where(tab, _AC[1][0][sym], _AC[0][0][sym]),
         np.where(tab, _AC[1][1][sym], _AC[0][1][sym]))
    emit(base + 2, np.where(vals >= 0, vals, vals + (1 << sa) - 1), sa)
    last = np.full(nt * nu, -1)
    last[bi] = pz
    eob = np.flatnonzero(last < 62)
    et = unit_chroma[eob]
    emit(eob * 256 + 255, np.where(et, _AC[1][0][0], _AC[0][0][0]),
         np.where(et, _AC[1][1][0], _AC[0][1][0]))

    key = np.concatenate(keys)
    order = np.argsort(key, kind="stable")
    code = np.concatenate(codes)[order]
    ln = np.concatenate(lens)[order]
    tile_of = key[order] // (256 * nu)
    return _pack(code, ln, tile_of, nt)


def _pack(code: np.ndarray, ln: np.ndarray, tile_of: np.ndarray,
          nt: int) -> list[bytes]:
    """MSB-first bit packing of each tile's symbols, 1-bit flush pad and
    0xFF byte stuffing."""
    total = np.bincount(tile_of, weights=ln, minlength=nt).astype(np.int64)
    padded = total + (-total) % 8
    start = np.cumsum(padded) - padded
    cum = np.cumsum(ln) - ln
    first = np.searchsorted(tile_of, np.arange(nt))
    off = start[tile_of] + cum - cum[first][tile_of]
    shifted = code << (24 - (off & 7) - ln)
    nbytes = int(padded.sum()) >> 3
    pos = off >> 3
    out = np.bincount(np.concatenate([pos, pos + 1, pos + 2]),
                      weights=np.concatenate([(shifted >> 16) & 255,
                                              (shifted >> 8) & 255,
                                              shifted & 255]),
                      minlength=nbytes + 2)[:nbytes].astype(np.uint8)
    ends = (start + padded) >> 3
    for t in range(nt):  # flush: the pad bits are 1s
        pad = int(padded[t] - total[t])
        if pad:
            out[ends[t] - 1] |= (1 << pad) - 1
    scans = []
    for t in range(nt):
        seg = out[start[t] >> 3:ends[t]]
        ff = np.flatnonzero(seg == 0xFF)
        if ff.size:
            seg = np.insert(seg, ff + 1, 0)
        scans.append(seg.tobytes())
    return scans


def encode_band(rgb: np.ndarray, tile: int) -> tuple[list[np.ndarray],
                                                      list[bytes]]:
    """One band of ``tile`` rows -> its quantised coefficient planes
    (Y, Cb, Cr; blocks in place) and one entropy-coded scan per tile."""
    y, cb, cr = _ycbcr(rgb)
    planes = [_quantise(y, Q_LUMA), _quantise(cb, Q_CHROMA),
              _quantise(cr, Q_CHROMA)]
    return planes, _scans(_units(*planes, tile))


def encode(rgb: np.ndarray, tile: int, threads: int = 8):
    """(H, W, 3) uint8 -> (coefficient planes Y (H, W), Cb, Cr (H/2, W/2)
    int16, tile streams row-major: SOI SOF0 SOS scan EOI)."""
    H, W, _ = rgb.shape
    bands = [rgb[r:r + tile] for r in range(0, H, tile)]
    with ThreadPoolExecutor(threads) as pool:
        done = list(pool.map(lambda b: encode_band(b, tile), bands))
    planes = [np.concatenate([d[0][i] for d in done]).astype(np.int16)
              for i in range(3)]
    head = tile_header(tile)
    return planes, [head + s + b"\xff\xd9" for d in done for s in d[1]]


# -------------------------------------------------------------------- TIFF
def _ifd(entries: list, pos: int, parts: list) -> tuple[bytes, int]:
    """One little-endian IFD whose out-of-line values are appended to
    ``parts`` from file offset ``pos``; returns (IFD bytes, new pos)."""
    packed = []
    for tag, typ, vals in sorted(entries, key=lambda e: e[0]):
        if typ in (2, 7):
            payload, count = bytes(vals), len(vals)
        else:
            payload = struct.pack(f"<{len(vals)}{'H' if typ == 3 else 'I'}",
                                  *vals)
            count = len(vals)
        if len(payload) <= 4:
            value = payload.ljust(4, b"\0")
        else:
            value = struct.pack("<I", pos)
            parts.append(payload)
            pos += len(payload)
            if pos % 2:
                parts.append(b"\0")
                pos += 1
        packed.append(struct.pack("<HHI", tag, typ, count) + value)
    return struct.pack("<H", len(packed)) + b"".join(packed), pos


def svs_bytes(levels: list[tuple[int, int, list[bytes]]], tile: int,
              thumb: np.ndarray, description: str,
              photometric: int = 2) -> bytes:
    """The Aperio-shaped file: each (H, W, tile streams) level as a tiled
    JPEG IFD (level 0 first), the thumbnail as a stripped uncompressed IFD
    after level 0."""
    tables = jpeg_tables()
    parts, pos = [b"II*\0\0\0\0\0"], 8
    images = []  # (kind, payload offsets, counts, H, W)
    for H, W, streams in levels:
        offs = []
        for body in streams:  # TN2: abbreviated tiles, no tables
            offs.append(pos)
            parts.append(body)
            pos += len(body)
            if pos % 2:
                parts.append(b"\0")
                pos += 1
        images.append(("jpeg", offs, [len(s) for s in streams], H, W))
    th, tw = thumb.shape[:2]
    images.insert(1, ("strip", [pos], [thumb.nbytes], th, tw))
    parts.append(thumb.tobytes())
    pos += thumb.nbytes
    if pos % 2:
        parts.append(b"\0")
        pos += 1
    desc = description.encode() + b"\0"
    ifds = []
    for i, (kind, offs, counts, H, W) in enumerate(images):
        entries = [(256, 4, [W]), (257, 4, [H]), (258, 3, [8, 8, 8]),
                   (277, 3, [3]), (284, 3, [1])]
        if i == 0:
            entries.append((270, 2, desc))
        if kind == "jpeg":
            entries += [(259, 3, [7]), (262, 3, [photometric]),
                        (322, 4, [tile]), (323, 4, [tile]),
                        (324, 4, offs), (325, 4, counts),
                        (347, 7, tables)]
            if photometric == 6:
                entries.append((530, 3, [2, 2]))
        else:
            entries += [(259, 3, [1]), (262, 3, [2]), (273, 4, offs),
                        (278, 4, [H]), (279, 4, counts)]
        ifd, pos = _ifd(entries, pos, parts)
        ifds.append((pos, ifd))
        parts.append(ifd + b"\0\0\0\0")
        pos += len(ifd) + 4
        if pos % 2:
            parts.append(b"\0")
            pos += 1
    out = bytearray(b"".join(parts))
    out[4:8] = struct.pack("<I", ifds[0][0])
    for (at, ifd), (nxt, _) in zip(ifds, ifds[1:]):
        struct.pack_into("<I", out, at + len(ifd), nxt)
    return bytes(out)


def scan(H: int, W: int, tile: int, s: float, *, threads: int = 8,
         photometric: int = 2) -> tuple[dict, bytes]:
    """Render one slide with scanner seed ``s`` and write it as a JPEG SVS.

    Returns ``(record, svs)``: the scanner's level-0 quantised coefficient
    planes (``y``, ``cb``, ``cr``), its tile streams (``tiles``) and
    ``tables``, which the reference decodes and the check compares with;
    and the file that lands in the bucket.
    """
    if H % tile or W % tile or tile % 16:
        raise ValueError(f"{H}x{W} is not a multiple of the {tile}-px tile")
    img = np.empty((H, W, 3), np.uint8)

    def band(r: int) -> None:
        img[r:r + tile] = render_band(r, tile, W, s)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(band, range(0, H, tile)))
    planes, tiles = encode(img, tile, threads)
    levels = [(H, W, tiles)]
    f = 4 if min(H, W) // 4 >= tile else 2
    if min(H, W) // f >= tile and (H // f) % tile == 0 \
            and (W // f) % tile == 0:
        small = img.reshape(H // f, f, W // f, f, 3).mean(axis=(1, 3))
        levels.append((H // f, W // f, encode(
            np.round(small).astype(np.uint8), tile, threads)[1]))
    step = max(1, max(H, W) // 256)
    thumb = np.ascontiguousarray(img[::step, ::step])
    del img
    desc = (f"Aperio Image Library (benchmark scanner) {W}x{H} [0,0 {W}x{H}]"
            f" ({tile}x{tile}) JPEG/RGB Q={QUALITY}|AppMag = 20|MPP = 0.5"
            f"|seed = {s}")
    record = {"y": planes[0], "cb": planes[1], "cr": planes[2],
              "tiles": tiles, "tables": jpeg_tables(), "tile": tile}
    return record, svs_bytes(levels, tile, thumb, desc, photometric)
