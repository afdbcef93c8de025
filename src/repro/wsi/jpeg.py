"""JPEG baseline codec: JAX/Pallas transform stage + entropy stage.

Hardware-adaptation split (recorded in DESIGN.md, "Transform/entropy split"):
the transform math (color conversion, 8×8 DCT, quantization) is data-parallel
→ Pallas kernels. Huffman coding is a sequential bitstream per tile, but
every tile is its own scan, and within a tile the symbols, their bit
offsets (a prefix sum) and their packing into words are vectorizable: so
coefficients that already live on the device are Huffman-coded there
(``repro.wsi.entropy_encode_jax``) and only the packed scans come back;
the host keeps the 0xFF byte stuffing and the JFIF wrap.

Two encoder paths, byte-identical by construction (tested):

- ``encode_tile``: the original per-tile path — 4 jitted dispatches per tile
  (rgb2ycbcr + 3× dct8x8_quant) and a per-coefficient Python Huffman loop.
  Kept as the A/B baseline for benchmarks.
- ``encode_tiles_batch``: the whole-level batched path — one fused
  ``jpeg_transform`` dispatch for every tile of a level, then the
  entropy stage (``encode_coef_batch``). Where the coefficients live
  decides where it runs: a device-resident batch of at least
  ``_DEVICE_MIN_UNITS`` units (the pipelined converter's level chunks) on
  the device, numpy input and small device batches in the numpy-vectorized
  symbol-stream coder, whose cost scales with the number of emitted
  symbols and which stays the oracle. A tile the device coder flags (over
  its slab, or a category outside the baseline tables) is coded by numpy,
  which raises its own ``ValueError`` where the input is out of range.

And two decoder paths, pixel-identical by construction (tested) — the
export subsystem's compute spine run in reverse:

- ``decode_tile``: the per-tile path — a per-symbol Python Huffman loop,
  then the fused ``jpeg_inverse`` dispatch. Kept as the A/B baseline.
- ``decode_tiles_batch``: the whole-level batched path — the lockstep
  entropy **decoder** (``decode_coef_batch``: every tile of a level is an
  independent bitstream, so N tiles advance one symbol position per step;
  level-sized batches run the step automaton as a single jitted
  ``lax.while_loop`` dispatch (``repro.wsi.entropy_jax``), tiny batches as
  vectorized numpy steps), then a single fused ``jpeg_inverse`` dispatch
  for the whole level. Entropy ``decode ∘ encode`` is exact at the
  coefficient level (the bitstream is lossless; only quantization loses
  information).

Produces real JFIF bytes (SOI/APP0/DQT/SOF0/DHT/SOS/EOI, standard Annex-K
tables, 4:4:4, byte stuffing). Consumes any baseline stream whose tables
and sampling factors it reads from the stream itself (``Coding``): its own
4:4:4 frames, and a scanner's 4:2:0 tiles once the shared ``JPEGTables``
of their TIFF container are merged in (``merge_tables``). Truncated or
garbage input raises ``ValueError("corrupt JPEG …")`` from every decode
entry point — that string is what the export service turns into an
actionable DLQ reason; streams outside the baseline subset it reads raise
``ValueError("unsupported JPEG …")``.

Both encoder paths are thread-safe (the zigzag gather-index cache is the
only module-level mutable state and is lock-protected), and the heavy numpy
regions and device waits release the GIL — the real-mode pipeline
entropy-codes several slides' levels in parallel worker threads.
"""
from __future__ import annotations

import dataclasses
import struct
from functools import lru_cache

import numpy as np

import jax

from repro.analysis.lockdep import TrackedLock
from repro.core import tracing
from repro.kernels import (dct8x8_quant, jpeg_inverse, jpeg_inverse420,
                           jpeg_transform, rgb2ycbcr)
from repro.kernels.ref import JPEG_CHROMA_Q, JPEG_LUMA_Q
from repro.wsi.dicom import TS_EXPLICIT_LE, TS_JPEG_BASELINE

__all__ = ["encode_tile", "encode_tiles_batch", "encode_coef_batch",
           "decode_tile", "decode_tiles_batch", "decode_coef_batch",
           "decode_components", "decode_frames", "merge_tables",
           "photometric", "psnr"]

# --------------------------------------------------------------------------
# Annex-K Huffman tables
# --------------------------------------------------------------------------
_DC_L_BITS = [0, 1, 5, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0]
_DC_L_VALS = list(range(12))
_DC_C_BITS = [0, 3, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0]
_DC_C_VALS = list(range(12))
_AC_L_BITS = [0, 2, 1, 3, 3, 2, 4, 3, 5, 5, 4, 4, 0, 0, 1, 0x7D]
_AC_L_VALS = [
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
    0xF5, 0xF6, 0xF7, 0xF8, 0xF9, 0xFA,
]
_AC_C_BITS = [0, 2, 1, 2, 4, 4, 3, 4, 7, 5, 4, 4, 0, 1, 2, 0x77]
_AC_C_VALS = [
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
    0xF5, 0xF6, 0xF7, 0xF8, 0xF9, 0xFA,
]

_ZIGZAG = np.array([
    0, 1, 8, 16, 9, 2, 3, 10, 17, 24, 32, 25, 18, 11, 4, 5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6, 7, 14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63,
])


def _build_codes(bits, vals):
    """Canonical Huffman: symbol -> (code, length)."""
    codes = {}
    code = 0
    k = 0
    for ln in range(1, 17):
        for _ in range(bits[ln - 1]):
            codes[vals[k]] = (code, ln)
            code += 1
            k += 1
        code <<= 1
    return codes

_ENC = {
    ("dc", 0): _build_codes(_DC_L_BITS, _DC_L_VALS),
    ("dc", 1): _build_codes(_DC_C_BITS, _DC_C_VALS),
    ("ac", 0): _build_codes(_AC_L_BITS, _AC_L_VALS),
    ("ac", 1): _build_codes(_AC_C_BITS, _AC_C_VALS),
}


@dataclasses.dataclass(frozen=True)
class Coding:
    """What a baseline stream's headers fix for its scan.

    ``sampling`` is each component's (h, v) in scan order (Y at most 2×2,
    chroma 1×1), ``q`` its quantisation table in natural order, ``dc`` and
    ``ac`` the row of ``huff`` — (BITS, HUFFVAL) pairs — it is coded with.
    Hashable: the MCU's pattern of blocks and tables is static per
    coding, so it keys the jitted decoder's compile.
    """

    sampling: tuple[tuple[int, int], ...]
    q: tuple[tuple[int, ...], ...]
    dc: tuple[int, ...]
    ac: tuple[int, ...]
    huff: tuple[tuple[bytes, bytes], ...]

    @property
    def hmax(self) -> int:
        return max(h for h, _ in self.sampling)

    @property
    def vmax(self) -> int:
        return max(v for _, v in self.sampling)

    @property
    def subsampled(self) -> bool:
        return self.hmax * self.vmax > 1

    @property
    def unit_comps(self) -> tuple[int, ...]:
        """The component of each block of an MCU, in bitstream order
        (4:2:0: Y0 Y1 Y2 Y3 Cb Cr)."""
        return tuple(c for c, (h, v) in enumerate(self.sampling)
                     for _ in range(h * v))

    @property
    def dc_rows(self) -> tuple[int, ...]:
        return tuple(self.dc[c] for c in self.unit_comps)

    @property
    def ac_rows(self) -> tuple[int, ...]:
        return tuple(self.ac[c] for c in self.unit_comps)

    def units(self, H: int, W: int) -> int:
        """Blocks of an H×W tile, every component's."""
        mcus = (H // (8 * self.vmax)) * (W // (8 * self.hmax))
        return mcus * len(self.unit_comps)

    def qtables(self) -> np.ndarray:
        """(3, 8, 8) float32 quantisation tables, one per component."""
        return np.array(self.q, np.float32).reshape(3, 8, 8)


def _table(bits, vals) -> tuple[bytes, bytes]:
    return bytes(bits), bytes(vals)


#: what this module's own encoder writes: 4:4:4, Annex K tables
_STANDARD = Coding(
    sampling=((1, 1),) * 3,
    q=tuple(tuple(int(v) for v in t.reshape(64))
            for t in (JPEG_LUMA_Q, JPEG_CHROMA_Q, JPEG_CHROMA_Q)),
    dc=(0, 1, 1), ac=(2, 3, 3),
    huff=(_table(_DC_L_BITS, _DC_L_VALS), _table(_DC_C_BITS, _DC_C_VALS),
          _table(_AC_L_BITS, _AC_L_VALS), _table(_AC_C_BITS, _AC_C_VALS)))


class _BitWriter:
    def __init__(self):
        self.out = bytearray()
        self.acc = 0
        self.nbits = 0

    def put(self, code: int, length: int):
        self.acc = (self.acc << length) | (code & ((1 << length) - 1))
        self.nbits += length
        while self.nbits >= 8:
            byte = (self.acc >> (self.nbits - 8)) & 0xFF
            self.out.append(byte)
            if byte == 0xFF:
                self.out.append(0x00)  # byte stuffing
            self.nbits -= 8
        self.acc &= (1 << self.nbits) - 1

    def flush(self):
        if self.nbits:
            pad = 8 - self.nbits
            self.put((1 << pad) - 1, pad)
        return bytes(self.out)


class _BitReader:
    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0
        self.acc = 0
        self.nbits = 0

    def _fill(self):
        if self.pos >= len(self.data):
            raise ValueError("corrupt JPEG stream: truncated scan data")
        b = self.data[self.pos]
        self.pos += 1
        if b == 0xFF and self.pos < len(self.data) \
                and self.data[self.pos] == 0x00:
            self.pos += 1  # unstuff
        self.acc = (self.acc << 8) | b
        self.nbits += 8

    def get(self, n: int) -> int:
        while self.nbits < n:
            self._fill()
        v = (self.acc >> (self.nbits - n)) & ((1 << n) - 1)
        self.nbits -= n
        self.acc &= (1 << self.nbits) - 1
        return v

    def huff(self, table: dict) -> int:
        code, ln = 0, 0
        while ln < 16:
            code = (code << 1) | self.get(1)
            ln += 1
            sym = table.get((code, ln))
            if sym is not None:
                return sym
        raise ValueError("corrupt JPEG stream: invalid Huffman code")


def _category(v: int) -> int:
    return int(v).bit_length() if v > 0 else int(-v).bit_length()


def _encode_blocks(bw: _BitWriter, planes: list[np.ndarray]):
    """planes: 3 × (H, W) int coefficient planes (blocks in place), 4:4:4."""
    H, W = planes[0].shape
    bh, bwid = H // 8, W // 8
    zz = [
        p.reshape(bh, 8, bwid, 8).transpose(0, 2, 1, 3)
        .reshape(bh, bwid, 64)[:, :, _ZIGZAG]
        for p in planes
    ]
    pred = [0, 0, 0]
    for r in range(bh):
        for c in range(bwid):
            for comp in range(3):
                tid = 0 if comp == 0 else 1
                blk = zz[comp][r, c]
                dc = int(blk[0])
                diff = dc - pred[comp]
                pred[comp] = dc
                s = _category(diff)
                code, ln = _ENC[("dc", tid)][s]
                bw.put(code, ln)
                if s:
                    bw.put(diff if diff >= 0 else diff + (1 << s) - 1, s)
                run = 0
                ac = blk[1:]
                nz = np.nonzero(ac)[0]
                last = nz[-1] if len(nz) else -1
                for i in range(last + 1):
                    v = int(ac[i])
                    if v == 0:
                        run += 1
                        continue
                    while run > 15:
                        code, ln = _ENC[("ac", tid)][0xF0]
                        bw.put(code, ln)
                        run -= 16
                    s = _category(v)
                    code, ln = _ENC[("ac", tid)][(run << 4) | s]
                    bw.put(code, ln)
                    bw.put(v if v >= 0 else v + (1 << s) - 1, s)
                    run = 0
                if last < 62:
                    code, ln = _ENC[("ac", tid)][0x00]  # EOB
                    bw.put(code, ln)


# --------------------------------------------------------------------------
# Vectorized entropy coder (the batched path)
# --------------------------------------------------------------------------
def _code_table_arrays(table: dict, nsym: int):
    codes = np.zeros(nsym, np.uint32)
    lens = np.zeros(nsym, np.int64)
    for sym, (code, ln) in table.items():
        codes[sym] = code
        lens[sym] = ln
    return codes, lens

_DC_ARR = [_code_table_arrays(_ENC[("dc", t)], 12) for t in (0, 1)]
_AC_ARR = [_code_table_arrays(_ENC[("ac", t)], 256) for t in (0, 1)]

# entry-order key: ((block*3 + comp)*65 + slot)*8 + sub — slot is the zigzag
# position (DC=0, AC z∈[1,63], EOB=64); sub orders ZRLs (0..2) before the
# Huffman code (4) before the magnitude bits (5) of the same coefficient.
_SUB_HUFF, _SUB_MAG = 4, 5


def _category_vec(v: np.ndarray) -> np.ndarray:
    """Vectorized bit_length(|v|): frexp's exponent is exact for integers."""
    return np.frexp(np.abs(v).astype(np.float64))[1].astype(np.int64)


def _magnitude_vec(v: np.ndarray, s: np.ndarray) -> np.ndarray:
    """JPEG magnitude bits: v if v ≥ 0 else v + 2^s - 1 (fits in s bits)."""
    return np.where(v >= 0, v, v + (1 << s) - 1).astype(np.uint32)


def _comp_symbols(zz: np.ndarray, comp: int, nb_tile: int):
    """One component's symbol stream: (key, code, length) int64/uint32/int64.

    zz: (n_tiles · nb_tile, 64) zigzagged coefficients — all tiles of a
    level concatenated, blocks in scan (row-major) order within each tile.
    Emits exactly the symbols of the per-coefficient reference loop
    (_encode_blocks) for every tile, each tagged with its bitstream-order
    key (global block index keeps tiles contiguous and ordered; the DC
    predictor resets at tile boundaries since each tile is its own scan).
    """
    tid = 0 if comp == 0 else 1
    dc_codes, dc_lens = _DC_ARR[tid]
    ac_codes, ac_lens = _AC_ARR[tid]
    nb = zz.shape[0]
    base = (np.arange(nb, dtype=np.int64) * 3 + comp) * 65  # key / 8, slot 0

    keys, codes, lens = [], [], []

    # DC: differential against the previous block of the same component,
    # predictor reset to 0 on the first block of every tile
    dc = zz[:, 0].astype(np.int64).reshape(-1, nb_tile)
    prev = np.empty_like(dc)
    prev[:, 0] = 0
    prev[:, 1:] = dc[:, :-1]
    diff = (dc - prev).reshape(-1)
    s_dc = _category_vec(diff)
    if (s_dc > 11).any():  # baseline DC table has categories 0..11
        raise ValueError(
            "DC difference out of range for the baseline Huffman table "
            f"(max |diff|={int(np.abs(diff).max())})")
    keys.append(base * 8 + 0)
    codes.append(dc_codes[s_dc])
    lens.append(dc_lens[s_dc])
    has_mag = s_dc > 0
    keys.append(base[has_mag] * 8 + 1)
    codes.append(_magnitude_vec(diff[has_mag], s_dc[has_mag]))
    lens.append(s_dc[has_mag])

    # AC: run-length between nonzeros within each block
    ac = zz[:, 1:]
    bi, pz = np.nonzero(ac)  # ordered: block-major, position-minor
    vals = ac[bi, pz].astype(np.int64)
    first = np.ones(bi.size, bool)
    first[1:] = bi[1:] != bi[:-1]
    prevpos = np.concatenate(([0], pz[:-1]))
    run = np.where(first, pz, pz - prevpos - 1).astype(np.int64)
    nzrl, rem = run >> 4, run & 15
    slot_key = ((bi * 3 + comp) * 65 + (pz + 1)) * 8

    # ZRL (0xF0) emitted ⌊run/16⌋ times just before the coefficient's symbol
    if nzrl.any():
        rep = np.repeat(np.arange(bi.size), nzrl)
        j = np.arange(rep.size) - np.repeat(np.cumsum(nzrl) - nzrl, nzrl)
        keys.append(slot_key[rep] + j)
        codes.append(np.full(rep.size, ac_codes[0xF0], np.uint32))
        lens.append(np.full(rep.size, ac_lens[0xF0], np.int64))

    s_ac = _category_vec(vals)
    if (s_ac > 10).any():  # baseline AC table has categories 1..10; a
        # larger category would alias into the run nibble of sym below
        raise ValueError(
            "AC coefficient magnitude out of range for the baseline "
            f"Huffman table (max |v|={int(np.abs(vals).max())})")
    sym = (rem << 4) | s_ac
    ac_l = ac_lens[sym]
    keys.append(slot_key + _SUB_HUFF)
    codes.append(ac_codes[sym])
    lens.append(ac_l)
    keys.append(slot_key + _SUB_MAG)
    codes.append(_magnitude_vec(vals, s_ac))
    lens.append(s_ac)

    # EOB for every block whose last nonzero AC sits before position 62
    lastpos = np.full(nb, -1, np.int64)
    lastpos[bi] = pz  # later (= larger pz) assignments win
    eob = lastpos < 62
    keys.append((base[eob] + 64) * 8)
    codes.append(np.full(int(eob.sum()), ac_codes[0x00], np.uint32))
    lens.append(np.full(int(eob.sum()), ac_lens[0x00], np.int64))

    return (np.concatenate(keys), np.concatenate(codes).astype(np.uint32),
            np.concatenate(lens))


_ZZ_IDX_CACHE: dict[tuple[int, int], np.ndarray] = {}
_ZZ_IDX_LOCK = TrackedLock("jpeg._ZZ_IDX_LOCK")


def _zigzag_gather_index(H: int, W: int) -> np.ndarray:
    """Flat (H·W,) index map: plane → row-major 8×8 blocks in zigzag order."""
    key = (H, W)
    with _ZZ_IDX_LOCK:
        cached = _ZZ_IDX_CACHE.get(key)
    if cached is None:
        idx = np.arange(H * W).reshape(H // 8, 8, W // 8, 8)
        idx = idx.transpose(0, 2, 1, 3).reshape(-1, 64)[:, _ZIGZAG]
        cached = np.ascontiguousarray(idx.reshape(-1))
        with _ZZ_IDX_LOCK:
            _ZZ_IDX_CACHE[key] = cached
    return cached


def _stuff(packed: np.ndarray) -> bytes:
    """0xFF byte stuffing over one tile's packed scan bytes."""
    ff = packed == 0xFF
    if ff.any():
        out = np.zeros(packed.size + int(ff.sum()), np.uint8)
        out[np.arange(packed.size) + (np.cumsum(ff) - ff)] = packed
        packed = out  # gaps after each 0xFF stay 0x00 (stuffing)
    return packed.tobytes()


def _pack_bits_tiled(codes: np.ndarray, lens: np.ndarray,
                     tile_ids: np.ndarray, n_tiles: int) -> list[bytes]:
    """MSB-first bit-pack of all tiles' symbol streams in one pass.

    Symbols are sorted, so each tile's run is contiguous. Every tile's
    stream is flush-padded with 1-bits to a byte boundary (as
    ``_BitWriter.flush``) inside one flat bit array, packed with a single
    ``np.packbits``, then split per tile and 0xFF-stuffed.
    """
    totals = np.bincount(tile_ids, weights=lens,
                         minlength=n_tiles).astype(np.int64)
    pads = (-totals) % 8
    padded = totals + pads
    tile_start = np.cumsum(padded) - padded  # bit offset of each tile

    cum = np.cumsum(lens) - lens  # global unpadded bit offsets
    first = np.searchsorted(tile_ids, np.arange(n_tiles))
    offs = tile_start[tile_ids] + (cum - cum[first][tile_ids])

    # scatter each symbol into its ≤3 bytes: align the ≤16-bit code inside
    # a 24-bit window starting at its byte, split into byte lanes, and sum
    # per byte with bincount — bits are disjoint, so the sum is the OR
    byte_pos = offs >> 3
    shifted = (codes.astype(np.int64)
               << (24 - (offs & 7) - lens)).astype(np.uint32)
    n_bytes = int(padded.sum()) >> 3
    pos = np.concatenate([byte_pos, byte_pos + 1, byte_pos + 2])
    val = np.concatenate([(shifted >> 16) & 0xFF, (shifted >> 8) & 0xFF,
                          shifted & 0xFF])
    packed = np.bincount(pos, weights=val,
                         minlength=n_bytes)[:n_bytes].astype(np.uint8)

    byte_start = tile_start >> 3
    byte_end = (tile_start + padded) >> 3
    # flush: each tile's trailing pad bits are 1s (as _BitWriter.flush)
    packed[byte_end - 1] |= ((1 << pads) - 1).astype(np.uint8)
    return [_stuff(packed[byte_start[t]:byte_end[t]])
            for t in range(n_tiles)]


def _entropy_encode_batch(coef: np.ndarray) -> list[bytes]:
    """Vectorized twin of ``_encode_blocks`` over a whole level at once.

    coef: (N, 3, H, W) int coefficient planes (blocks in place, 4:4:4) →
    N entropy-coded scan byte strings, each byte-identical to the
    per-coefficient reference loop's output for that tile.
    """
    N, _, H, W = coef.shape
    bh, bwid = H // 8, W // 8
    nb_tile = bh * bwid
    zz_idx = _zigzag_gather_index(H, W)
    flat = coef.reshape(N, 3, H * W)
    parts = []
    for comp in range(3):
        # one gather: (H, W) plane → (nb, 64) blocks already in zigzag order
        zz = flat[:, comp].take(zz_idx, axis=1).reshape(N * nb_tile, 64)
        parts.append(_comp_symbols(zz, comp, nb_tile))
    keys = np.concatenate([p[0] for p in parts])
    codes = np.concatenate([p[1] for p in parts])
    lens = np.concatenate([p[2] for p in parts])
    order = np.argsort(keys)  # keys are unique → scan order, tiles grouped
    tile_ids = (keys[order] // (8 * 65 * 3)) // nb_tile
    return _pack_bits_tiled(codes[order], lens[order], tile_ids, N)


def _decode_blocks(br: _BitReader, H: int, W: int,
                   coding: "Coding") -> list[np.ndarray]:
    """The per-symbol reference decode of one scan → one coefficient plane
    per component (blocks in place; chroma planes smaller where sampled)."""
    hm, vm = coding.hmax, coding.vmax
    mr, mc = H // (8 * vm), W // (8 * hm)
    decs = _dec_tables(coding.huff)
    out = [np.zeros((mr * v, mc * h, 64), np.int32)
           for h, v in coding.sampling]
    pred = [0] * len(coding.sampling)
    inv_zz = np.argsort(_ZIGZAG)
    for r in range(mr):
        for c in range(mc):
            for comp, (h, v) in enumerate(coding.sampling):
                dc_tab, ac_tab = decs[coding.dc[comp]], decs[coding.ac[comp]]
                for k in range(h * v):
                    blk = np.zeros(64, np.int32)
                    s = br.huff(dc_tab)
                    diff = 0
                    if s:
                        bits = br.get(s)
                        diff = bits if bits >= (1 << (s - 1)) \
                            else bits - (1 << s) + 1
                    pred[comp] += diff
                    blk[0] = pred[comp]
                    z = 1
                    while z < 64:
                        sym = br.huff(ac_tab)
                        if sym == 0x00:
                            break
                        run, s = sym >> 4, sym & 0xF
                        if sym == 0xF0:
                            z += 16
                            continue
                        z += run
                        if z > 63:
                            raise ValueError(
                                "corrupt JPEG stream: AC run past end of "
                                "block")
                        bits = br.get(s)
                        blk[z] = bits if bits >= (1 << (s - 1)) \
                            else bits - (1 << s) + 1
                        z += 1
                    out[comp][r * v + k // h, c * h + k % h] = blk
    planes = []
    for blocks in out:
        bh, bwid = blocks.shape[:2]
        zz = blocks[:, :, inv_zz].reshape(bh, bwid, 8, 8)
        planes.append(zz.transpose(0, 2, 1, 3).reshape(bh * 8, bwid * 8))
    return planes


# --------------------------------------------------------------------------
# Vectorized entropy decoder (the batched export path)
# --------------------------------------------------------------------------
# 16-bit-lookahead Huffman tables: LUT[peek] = (symbol, code length). Codes
# are ≤ 16 bits, so every 16-bit window starting at a code boundary resolves
# the symbol in one gather; windows matching no code have length 0 (corrupt).
def _huff_lut(table: dict) -> tuple[np.ndarray, np.ndarray]:
    sym = np.zeros(1 << 16, np.int16)
    ln = np.zeros(1 << 16, np.int16)
    for s, (code, length) in table.items():
        lo = code << (16 - length)
        sym[lo:lo + (1 << (16 - length))] = s
        ln[lo:lo + (1 << (16 - length))] = length
    return sym, ln


@lru_cache(maxsize=32)
def _luts(huff: tuple) -> tuple[np.ndarray, np.ndarray]:
    """A stream's Huffman tables stacked as 16-bit lookahead LUTs, one row
    per table of ``Coding.huff``: (symbols, code lengths), (R, 65536)."""
    luts = [_huff_lut(_build_codes(list(bits), list(vals)))
            for bits, vals in huff]
    return (np.stack([s for s, _ in luts]), np.stack([n for _, n in luts]))


@lru_cache(maxsize=32)
def _dec_tables(huff: tuple) -> list[dict]:
    """The per-symbol decoder's tables: (code, length) → symbol per row."""
    return [{v: sym for sym, v in _build_codes(list(bits), list(vals))
             .items()} for bits, vals in huff]


# magnitude decode, tabulated per category s: value = bits if bits ≥ 2^(s-1)
# else bits - (2^s - 1)   (s = 0 ⇒ no bits, value 0)
_MAG_MASK = np.array([(1 << s) - 1 for s in range(16)], np.uint64)
_MAG_HALF = np.array([1 << max(s - 1, 0) for s in range(16)], np.int64)
_MAG_EXT = np.array([(1 << s) - 1 for s in range(16)], np.int64)

#: zero bytes appended after every tile's unstuffed scan so the sliding
#: 64-bit window at a (possibly truncated) stream's end stays in bounds —
#: one iteration can advance a corrupt tile's cursor ≤ 27 bits past its end
#: before the overrun check fires
_GUARD = 8


def _unstuff(scan: np.ndarray) -> np.ndarray:
    """Drop the stuffed 0x00 after every 0xFF (vectorized per tile)."""
    if scan.size < 2:
        return scan
    stuffed = (scan[:-1] == 0xFF) & (scan[1:] == 0x00)
    if not stuffed.any():
        return scan
    keep = np.ones(scan.size, bool)
    keep[1:][stuffed] = False
    return scan[keep]


def _window64(buf: np.ndarray) -> np.ndarray:
    """``w[p]`` = bytes ``p..p+7`` of ``buf`` as one big-endian uint64.

    Built once per batch with 8 vectorized passes, so the lockstep loop
    reads each tile's next 57+ lookahead bits with a *single* gather: a
    Huffman code (≤ 16 bits) plus its magnitude bits (≤ 11) plus the ≤ 7
    sub-byte phase is ≤ 34 bits, comfortably inside the window.
    """
    pad = np.concatenate([buf, np.zeros(8, np.uint8)])
    w = np.zeros(buf.size, np.uint64)
    for i in range(8):
        w |= pad[i:i + buf.size].astype(np.uint64) << np.uint64(56 - 8 * i)
    return w


#: batches with at least this many block-component units (N × nu) run the
#: jitted lockstep engine; below it the numpy engine wins because a compile
#: (one per padded lane-count/buffer bucket) would dominate the decode
_JAX_MIN_UNITS = 4096

#: jitted-engine bit cursors are int32 — batches whose concatenated scan
#: buffer would approach 2^31 bits stay on the numpy engine (uint64 windows)
_JAX_MAX_BYTES = 1 << 27


def _pack_scans(scans: list[np.ndarray], H: int, W: int,
                engine: str = "auto", coding: "Coding | None" = None
                ) -> tuple[str, tuple]:
    """The host half of a lockstep decode over N independent scans: the
    engine that decodes them, and the scans laid out for it.

    Every tile of a level is its own bitstream (one scan per tile, DC
    predictors reset at tile boundaries), which is the vectorization axis
    the sequential Huffman dependency cannot remove *within* a stream: all
    N tiles advance one symbol per step. ``coding`` (default: this
    module's own 4:4:4 Annex-K streams) fixes the MCU's blocks and the
    tables each one is coded with. Two engines run the identical
    automaton (coefficient-exact, same error strings — differentially
    tested):

    - ``"numpy"`` — the reference engine: one vectorized numpy step per
      symbol *position*. Interpreter cost is per step, so small batches of
      long scans pay heavily (the 0.82x small-batch cliff).
    - ``"jax"`` — the same automaton compiled into a single
      ``lax.while_loop`` dispatch (``repro.wsi.entropy_jax``): per-step
      cost drops from ~50–90µs of interpreter to a few µs of compiled
      gathers, keeping the batched path ahead of the per-tile loop at
      every batch size (see BENCH_export.json's ``batch_scaling``).
    - ``"auto"`` (default) picks the jitted engine for level-sized work
      and the numpy engine for tiny batches where a compile would
      dominate.

    ``_run_packed`` runs the result.
    """
    if engine not in ("auto", "numpy", "jax"):
        raise ValueError(f"engine must be 'auto', 'numpy' or 'jax': "
                         f"{engine!r}")
    coding = coding or _STANDARD
    nu = coding.units(H, W)
    total_bytes = sum(s.size for s in scans)
    if engine == "jax" or (engine == "auto"
                           and len(scans) * nu >= _JAX_MIN_UNITS
                           and total_bytes < _JAX_MAX_BYTES):
        from repro.wsi.entropy_jax import pack_scans
        return "jax", pack_scans(scans, nu)
    N = len(scans)
    offs = np.zeros(N, np.int64)
    ends = np.zeros(N, np.int64)  # exclusive bit end of each tile's stream
    parts, cur = [], 0
    for i, scan in enumerate(scans):
        offs[i] = cur
        ends[i] = (cur + scan.size) * 8
        parts += [scan, np.zeros(_GUARD, np.uint8)]
        cur += scan.size + _GUARD
    return "numpy", (_window64(np.concatenate(parts)), offs, ends)


def _run_packed(engine: str, packed: tuple, H: int, W: int,
                coding: "Coding | None" = None) -> np.ndarray:
    """The lockstep decode of ``_pack_scans``' result → (N, mcus,
    blocks per MCU, 64) int32 zigzag coefficients — for 4:4:4 streams
    (N, nb, 3, 64) — exactly the symbols the per-tile reference loop
    decodes, with the DC slots holding differentials (``_coef_planes``
    integrates them)."""
    coding = coding or _STANDARD
    if engine == "jax":
        from repro.wsi.entropy_jax import run_packed
        return run_packed(packed, H, W, coding)
    w64, offs, ends = packed
    N = offs.size
    nu = coding.units(H, W)  # blocks per tile, in bitstream order
    upm = len(coding.unit_comps)
    lut_sym, lut_len = _luts(coding.huff)

    pos = offs * 8
    u = np.zeros(N, np.int64)  # unit index: MCU * upm + block of the MCU
    k = np.zeros(N, np.int64)  # next zigzag slot; 0 ⇒ the DC symbol is next
    zzf = np.zeros(N * nu * 64, np.int32)  # flat (tile, unit, slot)
    base = np.arange(N, dtype=np.int64) * (nu * 64)
    active = u < nu
    # unit → the LUT row of its DC and of its AC table
    dc_row = np.array(coding.dc_rows, np.int64)[np.arange(nu + 1) % upm]
    ac_row = np.array(coding.ac_rows, np.int64)[np.arange(nu + 1) % upm]
    _c48, _c64 = np.uint64(48), np.uint64(64)
    _m16, _one = np.uint64(0xFFFF), np.uint64(1)

    while active.any():
        w = w64[pos >> 3]
        sh = (pos & 7).astype(np.uint64)
        code = ((w >> (_c48 - sh)) & _m16).astype(np.int64)
        is_dc = k == 0
        tbl = np.where(is_dc, dc_row[u], ac_row[u])
        sym = lut_sym[tbl, code]
        ln = lut_len[tbl, code]
        # EOB (0x00) and ZRL (0xF0) have zero magnitude bits by construction
        s = np.where(is_dc, sym, sym & 0xF)
        su = s.astype(np.uint64)
        bits = ((w >> (_c64 - sh - ln.astype(np.uint64) - su))
                & _MAG_MASK[s]).astype(np.int64)
        v = np.where(bits >= _MAG_HALF[s], bits, bits - _MAG_EXT[s])
        pos = np.where(active, pos + ln + s, pos)

        is_eob = ~is_dc & (sym == 0x00)
        is_zrl = ~is_dc & (sym == 0xF0)
        is_coef = ~(is_dc | is_eob | is_zrl)
        # sym >> 4 is 0 for every valid DC category and for EOB; ZRL's
        # junk value is never read (its k-update uses k + 16 directly)
        knew = k + (sym >> 4)
        bad = active & ((ln == 0) | (is_coef & (knew > 63)))
        if bad.any():
            if (active & (ln == 0)).any():
                raise ValueError("corrupt JPEG stream: invalid Huffman code")
            raise ValueError("corrupt JPEG stream: AC run past end of block")

        # one scatter: the DC differential at slot 0, AC values at slot knew
        rows = np.flatnonzero(active & (is_dc | is_coef))
        zzf[base[rows] + u[rows] * 64
            + np.where(is_dc, 0, knew)[rows]] = v[rows]

        # next slot: DC → 1; ZRL skips 16; a written value advances past
        # itself; EOB leaves k to be reset below. A run past slot 63 ends
        # the unit, as in the reference loop's `while k < 64` recheck.
        k = np.where(is_dc, 1,
                     np.where(is_zrl, k + 16,
                              np.where(is_coef, knew + 1, k)))
        adv = active & (is_eob | (k >= 64))  # k ≥ 64 implies an AC phase
        u = u + adv
        k = np.where(adv, 0, k)
        active = u < nu
        if (active & (pos > ends)).any():
            raise ValueError("corrupt JPEG stream: truncated scan data")

    return zzf.reshape(N, nu // upm, upm, 64)


def _coef_planes(zz: np.ndarray, H: int, W: int) -> np.ndarray:
    """(N, nb, 3, 64) zigzag coefficients of 4:4:4 streams, DC slots
    holding differentials → (N, 3, H, W) coefficient planes."""
    N, nb = zz.shape[:2]
    # integrate the DC differentials (predictor resets at tile boundaries)
    zz[:, :, :, 0] = np.cumsum(zz[:, :, :, 0], axis=1)
    out = np.empty((N, 3, H * W), np.int32)
    # scatter back through the encoder's zigzag gather index (its inverse)
    out[:, :, _zigzag_gather_index(H, W)] = \
        zz.transpose(0, 2, 1, 3).reshape(N, 3, nb * 64)
    return out.reshape(N, 3, H, W)


_SOI, _EOI = b"\xff\xd8", b"\xff\xd9"
#: frame markers of every process but baseline/extended Huffman (SOF0/1)
_OTHER_SOF = frozenset({0xC2, 0xC3, 0xC5, 0xC6, 0xC7, 0xC9, 0xCA, 0xCB,
                        0xCD, 0xCE, 0xCF})


def _parse_stream(jpg: bytes) -> tuple[int, int, int, int, Coding]:
    """Parse one tile's interchange stream → (H, W, scan start, scan end,
    coding).

    Tables, sampling factors and the colour space come from the stream
    (a tile of a TIFF/SVS container once its shared ``JPEGTables`` are
    merged in, ``merge_tables``): baseline Huffman, 8-bit, three
    components, Y sampled at most 2×2 and chroma 1×1, one interleaved scan.
    Accepts DICOM's even-length convention of one trailing 0x00 pad byte
    after the EOI marker (encapsulated fragments). Truncated or malformed
    containers raise ``ValueError("corrupt JPEG …")``, streams outside
    that subset ``ValueError("unsupported JPEG …")`` — never
    ``IndexError``/``struct.error``. The header up to the scan is parsed
    once per distinct header (every tile of a level shares one).
    """
    if len(jpg) < 4 or jpg[:2] != _SOI:
        raise ValueError("corrupt JPEG stream: missing SOI marker")
    end = len(jpg)
    if jpg[end - 1] == 0x00 and jpg[end - 3:end - 1] == _EOI:
        end -= 1  # DICOM even-length fragment pad
    if jpg[end - 2:end] != _EOI:
        raise ValueError("corrupt JPEG stream: missing EOI marker")
    pos = 0
    while pos + 2 <= end:
        if jpg[pos] != 0xFF:
            raise ValueError(
                f"corrupt JPEG stream: expected a marker at offset {pos}")
        code = jpg[pos + 1]
        pos += 2
        if code in (0xD8, 0xD9):
            continue
        if pos + 2 > end:
            raise ValueError("corrupt JPEG stream: truncated marker segment")
        ln = struct.unpack_from(">H", jpg, pos)[0]
        if ln < 2 or pos + ln > end:
            raise ValueError(
                "corrupt JPEG stream: marker segment overruns container")
        pos += ln
        if code == 0xDA:
            if pos > end - 2:
                raise ValueError("corrupt JPEG stream: no scan data")
            H, W, coding = _header(bytes(jpg[:pos]))
            return H, W, pos, end - 2, coding
    raise ValueError("corrupt JPEG stream: no SOS marker")


@lru_cache(maxsize=64)
def _header(head: bytes) -> tuple[int, int, Coding]:
    """SOI … SOS of a structurally sound stream → (H, W, coding)."""
    qt: dict[int, tuple[int, ...]] = {}
    ht: dict[tuple[int, int], tuple[bytes, bytes]] = {}
    frame = scan = adobe = None
    jfif = False
    pos = 2
    while pos < len(head):
        code = head[pos + 1]
        if code in (0xD8, 0xD9):
            pos += 2
            continue
        ln = struct.unpack_from(">H", head, pos + 2)[0]
        seg = head[pos + 4:pos + 2 + ln]
        pos += 2 + ln
        if code in (0xC0, 0xC1):
            if ln < 9:
                raise ValueError("corrupt JPEG stream: short SOF segment")
            frame = seg
        elif code in _OTHER_SOF:
            raise ValueError(
                f"unsupported JPEG stream: SOF{code - 0xC0} (progressive, "
                "lossless, hierarchical or arithmetic coding) — baseline "
                "Huffman only")
        elif code == 0xDB:
            p = 0
            while p < len(seg):
                if seg[p] >> 4:
                    raise ValueError("unsupported JPEG stream: 16-bit "
                                     "quantisation table")
                if p + 65 > len(seg):
                    raise ValueError("corrupt JPEG stream: short DQT segment")
                nat = np.zeros(64, np.int64)
                nat[_ZIGZAG] = np.frombuffer(seg, np.uint8, 64, p + 1)
                qt[seg[p] & 15] = tuple(int(x) for x in nat)
                p += 65
        elif code == 0xC4:
            p = 0
            while p < len(seg):
                if p + 17 > len(seg):
                    raise ValueError("corrupt JPEG stream: short DHT segment")
                bits = bytes(seg[p + 1:p + 17])
                n = sum(bits)
                vals = bytes(seg[p + 17:p + 17 + n])
                if len(vals) != n or _code_overflow(bits):
                    raise ValueError("corrupt JPEG stream: bad Huffman table")
                cls = seg[p] >> 4
                if any(v > 11 for v in vals) if cls == 0 else \
                        any((v & 15) > 10 for v in vals):
                    raise ValueError("corrupt JPEG stream: Huffman symbol "
                                     "out of the baseline range")
                ht[cls, seg[p] & 15] = (bits, vals)
                p += 17 + n
        elif code == 0xDD:
            if len(seg) >= 2 and struct.unpack_from(">H", seg)[0]:
                raise ValueError("unsupported JPEG stream: restart "
                                 "intervals")
        elif code == 0xE0 and seg[:5] == b"JFIF\0":
            jfif = True
        elif code == 0xEE and seg[:5] == b"Adobe" and len(seg) >= 12:
            adobe = seg[11]
        elif code == 0xDA:
            scan = seg
    if frame is None:
        raise ValueError("corrupt JPEG stream: SOS before SOF")
    prec, H, W, nc = struct.unpack_from(">BHHB", frame)
    if prec != 8:
        raise ValueError(f"unsupported JPEG stream: {prec}-bit samples")
    if nc != 3 or len(frame) < 6 + 3 * nc:
        raise ValueError(f"unsupported JPEG stream: {nc} components (the "
                         "converter reads three: YCbCr)")
    comps = [tuple(frame[6 + 3 * i:9 + 3 * i]) for i in range(nc)]
    ids = tuple(c[0] for c in comps)
    if adobe == 0 or (adobe is None and not jfif and ids == (82, 71, 66)):
        raise ValueError("unsupported JPEG stream: RGB-coded components "
                         "(the converter reads YCbCr)")
    sampling = tuple((c[1] >> 4, c[1] & 15) for c in comps)
    if sampling[0] not in ((1, 1), (2, 1), (1, 2), (2, 2)) \
            or any(s != (1, 1) for s in sampling[1:]):
        raise ValueError(f"unsupported JPEG stream: sampling {sampling} "
                         "(Y at most 2x2, chroma 1x1)")
    hm, vm = sampling[0]
    if not H or not W or H % (8 * vm) or W % (8 * hm):
        raise ValueError(
            f"corrupt JPEG stream: unsupported frame size {H}x{W}")
    if not scan or len(scan) < 1 + 2 * scan[0] + 3 or scan[0] != nc:
        raise ValueError("unsupported JPEG stream: one interleaved scan of "
                         "every component is read")
    sel = [tuple(scan[1 + 2 * i:3 + 2 * i]) for i in range(nc)]
    ss, se, a = scan[1 + 2 * nc:4 + 2 * nc]
    if [s[0] for s in sel] != list(ids) or (ss, se, a) != (0, 63, 0):
        raise ValueError("unsupported JPEG stream: one interleaved scan of "
                         "every component is read")
    for c in comps:
        if c[2] not in qt:
            raise ValueError(
                f"corrupt JPEG stream: no quantisation table {c[2]}")
    used = sorted({(0, t >> 4) for _, t in sel} | {(1, t & 15)
                                                  for _, t in sel})
    for key in used:
        if key not in ht:
            raise ValueError(f"corrupt JPEG stream: no Huffman table "
                             f"{'DC' if key[0] == 0 else 'AC'}{key[1]}")
    row = {key: i for i, key in enumerate(used)}
    return H, W, Coding(
        sampling=sampling, q=tuple(qt[c[2]] for c in comps),
        dc=tuple(row[0, t >> 4] for _, t in sel),
        ac=tuple(row[1, t & 15] for _, t in sel),
        huff=tuple(ht[key] for key in used))


def _code_overflow(bits: bytes) -> bool:
    """Whether canonical code assignment (T.81 Annex C) runs out of codes."""
    code = 0
    for ln in range(1, 17):
        code += bits[ln - 1]
        if code > (1 << ln):
            return True
        code <<= 1
    return False


def merge_tables(tile: bytes, tables: bytes) -> bytes:
    """An abbreviated tile stream and the shared tables-only stream → one
    complete interchange stream.

    TIFF Technical Note 2 ("new-style" JPEG, Compression 7): every tile is
    SOI, frame, scan, EOI, and the quantisation and Huffman tables they
    share sit once in the ``JPEGTables`` tag as SOI, DQT/DHT, EOI. The
    tables go in right after the tile's SOI; the entropy-coded data is
    untouched, so a DICOM frame made this way is the scanner's own JPEG.
    """
    if not tables:
        return tile
    if tables[:2] != _SOI or tables[-2:] != _EOI:
        raise ValueError("corrupt JPEGTables: not an SOI … EOI "
                         "tables-only stream")
    if tile[:2] != _SOI:
        raise ValueError("corrupt JPEG stream: missing SOI marker")
    return tile[:2] + tables[2:-2] + tile[2:]


def photometric(jpg: bytes) -> str:
    """The DICOM Photometric Interpretation of a JPEG frame: YBR_FULL_422
    where its chroma is subsampled (PS3.5 §8.2.1), YBR_FULL otherwise."""
    return "YBR_FULL_422" if _parse_stream(jpg)[4].subsampled \
        else "YBR_FULL"


def _parse_batch(jpgs: list[bytes]
                 ) -> tuple[int, int, Coding, list[np.ndarray]]:
    """Parse a batch of one level's frames → (H, W, coding, unstuffed
    scans); every frame must share the geometry and the coding."""
    parsed = [_parse_stream(j) for j in jpgs]
    H, W, _, _, coding = parsed[0]
    if any((h, w) != (H, W) for h, w, _, _, _ in parsed):
        raise ValueError(
            "corrupt JPEG stream: mixed tile geometries in one batch "
            f"({sorted({(h, w) for h, w, _, _, _ in parsed})})")
    if any(c != coding for *_, c in parsed):
        raise ValueError("corrupt JPEG stream: mixed tables or sampling in "
                         "one batch")
    scans = [_unstuff(np.frombuffer(jpg, np.uint8, end - start, start))
             for jpg, (_, _, start, end, _) in zip(jpgs, parsed)]
    return H, W, coding, scans


def decode_components(jpgs: list[bytes]) -> list[np.ndarray]:
    """N baseline tiles sharing one coding → per component (Y, Cb, Cr) an
    (N, h, w) int32 array of quantized coefficients, blocks in place (the
    chroma planes of a subsampled stream are smaller).

    The host entropy stage of the batched decode path (exact: only the
    transform stage is lossy). 4:4:4 streams are integrated and scattered
    on the host; a subsampled stream's planes come from the device
    (``entropy_jax.coef_planes``). Raises ``ValueError("corrupt JPEG …")``
    on truncated/garbage input.
    """
    jpgs = list(jpgs)
    if not jpgs:
        return [np.zeros((0, 0, 0), np.int32)] * 3
    with tracing.span("decode.parse", frames=len(jpgs)):
        H, W, coding, scans = _parse_batch(jpgs)
        engine, packed = _pack_scans(scans, H, W, coding=coding)
    with tracing.span("decode.entropy", engine=engine):
        zz = _run_packed(engine, packed, H, W, coding)
    with tracing.span("decode.scatter"):
        if not coding.subsampled:
            return list(_coef_planes(zz, H, W).transpose(1, 0, 2, 3))
        from repro.wsi.entropy_jax import coef_planes
        y, c = coef_planes(zz.reshape(-1), n=len(jpgs), H=H, W=W,
                           coding=coding)
        c = np.asarray(c)
        return [np.asarray(y), c[:, 0], c[:, 1]]


def decode_coef_batch(jpgs: list[bytes]) -> np.ndarray:
    """N baseline 4:4:4 tiles → (N, 3, H, W) int32 quantized coefficients.

    The exact inverse of ``encode_coef_batch``
    (``decode_coef_batch(encode_coef_batch(c))`` is coefficient-exact;
    only the transform stage is lossy). All tiles of a batch must share one
    geometry, as a pyramid level's frames do; subsampled streams have
    planes of two sizes (``decode_components``). Raises
    ``ValueError("corrupt JPEG …")`` on truncated/garbage input.
    """
    comps = decode_components(jpgs)
    if comps[0].shape != comps[1].shape:
        raise ValueError("subsampled JPEG stream: its planes differ in size "
                         "(decode_components)")
    return np.stack(comps, axis=1)


def _rgb(coef, coding: Coding) -> np.ndarray:
    """(N, 3, H, W) coefficient planes of a 4:4:4 stream → (N, H, W, 3)
    uint8 RGB: the fused ``jpeg_inverse`` where both chroma components
    share one table, ``jpeg_inverse420`` otherwise."""
    q = coding.qtables()
    if coding.q[1] == coding.q[2]:
        rgb = jpeg_inverse(coef, q[0], q[1])
    else:
        rgb = jpeg_inverse420(coef[:, 0], coef[:, 1:], q)
    return np.ascontiguousarray(
        np.asarray(rgb).astype(np.uint8, copy=False).transpose(0, 2, 3, 1))


def _rgb_subsampled(y, c, coding: Coding) -> np.ndarray:
    """Y (N, H, W) and chroma (N, 2, h, w) coefficient planes → (N, H, W,
    3) uint8 RGB through ``jpeg_inverse420`` (the stream's tables, chroma
    upsampled)."""
    rgb = jpeg_inverse420(y, c, coding.qtables())
    return np.ascontiguousarray(
        np.asarray(rgb).astype(np.uint8).transpose(0, 2, 3, 1))


def decode_tiles_batch(jpgs: list[bytes]) -> np.ndarray:
    """N baseline tiles sharing one coding → (N, H, W, 3) uint8 RGB.

    The whole-level batched decode path: one vectorized entropy-decode
    pass, then a single fused inverse dispatch. Output is pixel-identical
    to ``[decode_tile(j) for j in jpgs]`` — both paths share the one
    inverse transform, so identity reduces to the (exact, integer)
    coefficient streams matching. A subsampled stream's coefficients stay
    on the device from the entropy decoder to the inverse.
    """
    jpgs = list(jpgs)
    if not jpgs:
        return np.zeros((0, 0, 0, 3), np.uint8)
    with tracing.span("decode.parse", frames=len(jpgs)):
        H, W, coding, scans = _parse_batch(jpgs)
        engine, packed = _pack_scans(scans, H, W, coding=coding)
    if not coding.subsampled:
        with tracing.span("decode.entropy", engine=engine):
            zz = _run_packed(engine, packed, H, W, coding)
        with tracing.span("decode.scatter"):
            coef = _coef_planes(zz, H, W)
        with tracing.span("decode.inverse"):
            return _rgb(coef, coding)
    from repro.wsi.entropy_jax import coef_planes, decode_packed
    with tracing.span("decode.entropy", engine=engine):
        zzf = decode_packed(packed, H, W, coding) if engine == "jax" \
            else _run_packed(engine, packed, H, W, coding).reshape(-1)
    with tracing.span("decode.inverse"):
        y, c = coef_planes(zzf, n=len(jpgs), H=H, W=W, coding=coding)
        return _rgb_subsampled(y, c, coding)


def decode_frames(frames: list[bytes], *, transfer_syntax: str,
                  rows: int, cols: int) -> np.ndarray:
    """WADO frame bytes of one WSM instance → (n, rows, cols, 3) uint8 RGB.

    The single transfer-syntax dispatch shared by every store consumer
    (the export service, the ML-inference subscriber): JPEG-baseline
    frames — YBR_FULL (4:4:4) or YBR_FULL_422 (subsampled chroma, a
    scanner's own tiles), which the stream itself tells apart — go through
    the batched decode path when there is more than one (the lockstep
    decoder's win grows with the batch — see BENCH_export.json's
    ``batch_scaling``; small pulls sit near parity, whole levels win
    outright), native explicit-VR-LE frames are reshaped directly.
    Geometry mismatches and unknown syntaxes raise ``ValueError``.
    """
    frames = list(frames)
    n = len(frames)
    if rows <= 0 or cols <= 0:
        raise ValueError(f"bad frame geometry {rows}x{cols}")
    if n == 0:
        return np.zeros((0, rows, cols, 3), np.uint8)
    if transfer_syntax == TS_JPEG_BASELINE:
        rgb = decode_tiles_batch(frames) if n > 1 \
            else decode_tile(frames[0])[None]
        if rgb.shape[1:3] != (rows, cols):
            raise ValueError(
                f"frames decode to {rgb.shape[1]}x{rgb.shape[2]}, "
                f"expected {rows}x{cols}")
        return rgb
    if transfer_syntax == TS_EXPLICIT_LE:
        if any(len(f) != rows * cols * 3 for f in frames):
            raise ValueError(
                f"native frame size mismatch (expected {rows * cols * 3} "
                "bytes)")
        return np.stack([np.frombuffer(f, np.uint8).reshape(rows, cols, 3)
                         for f in frames])
    raise ValueError(
        f"unsupported transfer syntax {transfer_syntax} (JPEG baseline "
        "and explicit-VR-LE native are decodable)")


# --------------------------------------------------------------------------
# JFIF container
# --------------------------------------------------------------------------
def _marker(buf: bytearray, code: int, payload: bytes = b""):
    buf += struct.pack(">BB", 0xFF, code)
    if payload:
        buf += struct.pack(">H", len(payload) + 2) + payload


def _dqt_payload(tid: int, table: np.ndarray) -> bytes:
    return bytes([tid]) + bytes(
        int(v) for v in table.reshape(64)[_ZIGZAG]
    )


def _dht_payload(cls: int, tid: int, bits, vals) -> bytes:
    return bytes([cls << 4 | tid]) + bytes(bits) + bytes(vals)


def _jfif_header(H: int, W: int) -> bytearray:
    """SOI…SOS for a 4:4:4 baseline scan with the standard Annex-K tables."""
    buf = bytearray()
    _marker(buf, 0xD8)  # SOI
    _marker(buf, 0xE0, b"JFIF\x00\x01\x01\x00\x00\x01\x00\x01\x00\x00")
    _marker(buf, 0xDB, _dqt_payload(0, JPEG_LUMA_Q))
    _marker(buf, 0xDB, _dqt_payload(1, JPEG_CHROMA_Q))
    sof = struct.pack(">BHHB", 8, H, W, 3)
    for cid, tq in ((1, 0), (2, 1), (3, 1)):
        sof += bytes([cid, 0x11, tq])  # h=v=1 (4:4:4)
    _marker(buf, 0xC0, sof)
    _marker(buf, 0xC4, _dht_payload(0, 0, _DC_L_BITS, _DC_L_VALS))
    _marker(buf, 0xC4, _dht_payload(1, 0, _AC_L_BITS, _AC_L_VALS))
    _marker(buf, 0xC4, _dht_payload(0, 1, _DC_C_BITS, _DC_C_VALS))
    _marker(buf, 0xC4, _dht_payload(1, 1, _AC_C_BITS, _AC_C_VALS))
    sos = bytes([3, 1, 0x00, 2, 0x11, 3, 0x11, 0, 63, 0])
    _marker(buf, 0xDA, sos)
    return buf


def encode_tile(tile_rgb: np.ndarray) -> bytes:
    """RGB (H, W, 3) uint8 → baseline JFIF bytes (4:4:4).

    The per-tile path: 4 jitted dispatches + the Python Huffman loop. Kept
    as the A/B baseline for ``encode_tiles_batch`` (byte-identical output).
    """
    H, W, _ = tile_rgb.shape
    assert H % 8 == 0 and W % 8 == 0
    chw = np.transpose(tile_rgb, (2, 0, 1)).astype(np.float32)
    ycc = np.asarray(rgb2ycbcr(chw))  # kernels (level-shifted)
    qs = [JPEG_LUMA_Q, JPEG_CHROMA_Q, JPEG_CHROMA_Q]
    planes = [np.asarray(dct8x8_quant(ycc[i], qs[i])) for i in range(3)]

    buf = _jfif_header(H, W)
    bw = _BitWriter()
    _encode_blocks(bw, planes)
    buf += bw.flush()
    _marker(buf, 0xD9)  # EOI
    return bytes(buf)


#: device-resident batches with at least this many block-component units
#: (N × nu) are Huffman-coded on the device; smaller ones (the last levels'
#: 1-, 2- and 4-tile chunks) are copied back and coded by numpy, where a
#: compile per chunk shape would cost more than the coding
_DEVICE_MIN_UNITS = 1 << 15

#: pixels per device-coder dispatch (16 tiles of 256²): bounds the coder's
#: temporaries (≈ 0.1 GiB) and the shapes it compiles (one for every level
#: of a square slide), and per tile it ran faster on the chip than 64-tile
#: dispatches (0.16 against 0.25 ms); a larger batch is coded in
#: dispatches of this size, all enqueued before the first wait
_DEVICE_PX = 1 << 20


def _device_scans(coef: jax.Array) -> tuple[list[bytes], int, int]:
    """Entropy-code a device-resident batch on the device
    (``repro.wsi.entropy_encode_jax``): copy back each tile's packed scan
    and bit count, 0xFF-stuff it here. A tile the device coder flags (over
    its capacity, or a category outside the baseline tables) is copied
    back alone and coded by the numpy coder, which raises its own
    ``ValueError`` where the input is out of range.

    Returns (stuffed scans, tiles coded on the host, bytes copied back).
    """
    from repro.wsi.entropy_encode_jax import huffman_encode
    N, _, H, W = coef.shape
    step = max(1, _DEVICE_PX // (H * W))
    outs = [huffman_encode(coef if N <= step else coef[a:a + step])
            for a in range(0, max(N, 1), step)]
    slabs, bits, flags = (np.concatenate([np.asarray(o[i]) for o in outs])
                          for i in range(3))
    copied = slabs.nbytes + bits.nbytes + flags.nbytes
    ends = (bits.astype(np.int64) + 7) >> 3
    scans = [b"" if flag else _stuff(slab[:end])
             for slab, end, flag in zip(slabs, ends, flags)]
    for i in map(int, np.flatnonzero(flags)):
        tile = np.asarray(coef[i:i + 1])
        copied += tile.nbytes
        scans[i] = _entropy_encode_batch(tile)[0]
    return scans, int(flags.sum()), copied


def encode_coef_batch(coef) -> list[bytes]:
    """(N, 3, H, W) int quantized YCbCr DCT coefficients → N JFIF tiles.

    The entropy stage of the batched path. Where the coefficients live
    decides where they are coded: a ``jax.Array`` of at least
    ``_DEVICE_MIN_UNITS`` units is Huffman-coded on the device and only
    the packed scans come back (``_device_scans``); anything else — numpy
    input, or a device batch too small to be worth a compile — goes
    through the vectorized numpy coder, whose cost scales with emitted
    symbols. Both give the same bytes. The ``jpeg.encode`` span records
    ``device_tiles``, ``host_tiles`` and ``bytes_in`` (bytes copied from
    the device).
    """
    on_device = isinstance(coef, jax.Array)
    if not on_device:
        coef = np.asarray(coef)
    N, _, H, W = coef.shape
    if N == 0:
        return []
    with tracing.span("jpeg.encode", frames=N) as sp:
        if on_device and N * (H // 8) * (W // 8) * 3 >= _DEVICE_MIN_UNITS:
            scans, host, copied = _device_scans(coef)
        else:
            host_coef = np.asarray(coef)
            scans, host = _entropy_encode_batch(host_coef), N
            copied = host_coef.nbytes if on_device else 0
        if sp is not None:
            sp.attrs.update(device_tiles=N - host, host_tiles=host,
                            bytes_in=copied)
    header = bytes(_jfif_header(H, W))
    eoi = bytes((0xFF, 0xD9))
    return [header + scan + eoi for scan in scans]


def encode_tiles_batch(tiles_rgb: np.ndarray) -> list[bytes]:
    """RGB (N, H, W, 3) uint8 → N baseline JFIF byte strings (4:4:4).

    The whole-level batched path: all N tiles transform-coded in a single
    fused ``jpeg_transform`` dispatch, then the vectorized entropy coder.
    Output is byte-identical to ``[encode_tile(t) for t in tiles_rgb]``.
    """
    tiles = np.asarray(tiles_rgb)
    N, H, W, _ = tiles.shape
    assert H % 8 == 0 and W % 8 == 0
    chw = np.transpose(tiles, (0, 3, 1, 2)).astype(np.float32)
    coef = np.asarray(jpeg_transform(chw))
    return encode_coef_batch(coef)


def decode_tile(jpg: bytes) -> np.ndarray:
    """One baseline tile (``encode_tile``'s, or a scanner's once its tables
    are merged in) → RGB (H, W, 3) uint8.

    The per-tile decode path: a per-symbol Python Huffman loop, then the
    inverse transform the batched path uses on a batch of one — kept as
    the A/B baseline for ``decode_tiles_batch`` (pixel-identical output).
    Truncated/garbage input raises ``ValueError("corrupt JPEG …")``.
    """
    with tracing.span("decode.parse", frames=1):
        H, W, data_start, data_end, coding = _parse_stream(jpg)
    with tracing.span("decode.entropy", engine="python"):
        br = _BitReader(jpg[data_start:data_end])
        planes = [p[None] for p in _decode_blocks(br, H, W, coding)]
    with tracing.span("decode.inverse"):
        if coding.subsampled:
            return _rgb_subsampled(planes[0], np.stack(planes[1:], axis=1),
                                   coding)[0]
        return _rgb(np.stack(planes, axis=1), coding)[0]


def psnr(a: np.ndarray, b: np.ndarray) -> float:
    mse = np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2)
    return float(10 * np.log10(255.0**2 / max(mse, 1e-12)))
