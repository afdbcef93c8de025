"""Slide inputs for the benchmark: seeded H&E-like pixels in an SVS-shaped
tiled TIFF.

The pixel formula is a copy of the repository's synthetic scanner (smooth
eosin stroma plus hematoxylin nuclei on a 48-px hash lattice), rendered
whole and vectorised in row bands on a few threads instead of tile by
tile. The container is written here too: a classic little-endian tiled
TIFF, 8-bit chunky RGB, Deflate tiles at a low level, Aperio-style
``ImageDescription`` — the layout of an SVS level 0 with Deflate tiles.
Nothing here imports the system under test.
"""
from __future__ import annotations

import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: side of the hash lattice on which nuclei are placed (pixels)
CELL = 48


def render_band(y0: int, h: int, W: int, s: float) -> np.ndarray:
    """Rows ``y0 .. y0+h`` of a slide rendered with scanner seed ``s``,
    as (h, W, 3) uint8."""
    yy = np.arange(y0, y0 + h, dtype=np.float32)[:, None]
    xx = np.arange(W, dtype=np.float32)[None, :]
    base = (0.5 + 0.22 * np.sin(yy / 97.0 + s)
            + 0.18 * np.cos(xx / 131.0 - s * 0.7)
            + 0.10 * np.sin((xx + yy) / 53.0))
    r = 230 - 40 * base
    g = 170 - 70 * base
    b = 200 - 30 * base
    gy, gx = yy // CELL, xx // CELL
    hash_ = np.sin(gy * 12.9898 + gx * 78.233 + s) * 43758.5453
    frac = hash_ - np.floor(hash_)
    cy = (gy + 0.2 + 0.6 * frac) * CELL
    cx = (gx + 0.2 + 0.6 * (frac * 7 % 1)) * CELL
    d2 = (yy - cy) ** 2 + (xx - cx) ** 2
    nucleus = (d2 < (6 + 8 * (frac * 3 % 1)) ** 2) & (frac > 0.35)
    img = np.stack([np.where(nucleus, 80 + 30 * frac, r),
                    np.where(nucleus, 60 + 20 * frac, g),
                    np.where(nucleus, 140 + 40 * frac, b)], axis=-1)
    return np.clip(img, 0, 255).astype(np.uint8)


def _deflate_row(img: np.ndarray, r: int, tile: int, level: int) -> list[bytes]:
    band = img[r * tile:(r + 1) * tile]
    return [zlib.compress(np.ascontiguousarray(
        band[:, c * tile:(c + 1) * tile]).tobytes(), level)
        for c in range(img.shape[1] // tile)]


def tiff_bytes(blobs: list[bytes], H: int, W: int, tile: int,
               description: str) -> bytes:
    """A classic little-endian tiled TIFF holding the given Deflate tiles
    (row-major)."""
    parts = [b"II*\0\0\0\0\0"]
    pos, offsets = 8, []
    for b in blobs:
        offsets.append(pos)
        parts.append(b)
        pos += len(b)
        if pos % 2:
            parts.append(b"\0")
            pos += 1
    desc = description.encode() + b"\0"
    entries = [  # (tag, type, values); 3 = SHORT, 4 = LONG, 2 = ASCII
        (256, 4, [W]), (257, 4, [H]), (258, 3, [8, 8, 8]), (259, 3, [8]),
        (262, 3, [2]), (270, 2, desc), (277, 3, [3]), (284, 3, [1]),
        (322, 4, [tile]), (323, 4, [tile]), (324, 4, offsets),
        (325, 4, [len(b) for b in blobs]),
    ]
    packed = []
    for tag, typ, vals in entries:
        if typ == 2:
            payload = vals
        else:
            payload = struct.pack(f"<{len(vals)}{'H' if typ == 3 else 'I'}",
                                  *vals)
        if len(payload) <= 4:
            value = payload.ljust(4, b"\0")
        else:
            value = struct.pack("<I", pos)
            parts.append(payload)
            pos += len(payload)
            if pos % 2:
                parts.append(b"\0")
                pos += 1
        packed.append(struct.pack("<HHI", tag, typ, len(vals)) + value)
    ifd = pos
    parts.append(struct.pack("<H", len(packed)) + b"".join(packed)
                 + b"\0\0\0\0")
    out = bytearray(b"".join(parts))
    out[4:8] = struct.pack("<I", ifd)
    return bytes(out)


def scan(H: int, W: int, tile: int, s: float, *, level: int = 1,
         threads: int = 8) -> tuple[np.ndarray, bytes]:
    """Render one slide and wrap it as an SVS-shaped tiled TIFF.

    Returns ``(pixels, tiff)``: the (H, W, 3) uint8 level 0 that the
    reference reads, and the container that lands in the bucket.
    """
    if H % tile or W % tile:
        raise ValueError(f"{H}x{W} is not a multiple of the {tile}-px tile")
    img = np.empty((H, W, 3), np.uint8)

    def row(r: int) -> list[bytes]:
        img[r * tile:(r + 1) * tile] = render_band(r * tile, tile, W, s)
        return _deflate_row(img, r, tile, level)

    with ThreadPoolExecutor(threads) as pool:
        rows = list(pool.map(row, range(H // tile)))
    desc = (f"Aperio Image Library (benchmark scanner) {W}x{H} [0,0 {W}x{H}]"
            f" ({tile}x{tile}) Deflate|AppMag = 20|MPP = 0.5|seed = {s}")
    return img, tiff_bytes([b for r in rows for b in r], H, W, tile, desc)
