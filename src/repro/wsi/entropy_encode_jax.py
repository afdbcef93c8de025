"""Jitted JPEG entropy *encoder*: Huffman-code a batch of tiles on the device.

The pyramid program leaves every level's quantised coefficients on the
device. Coding them there means the host copies back each tile's packed
scan (≈ 0.075 B/px on scanner pixels) into a 0.5 B/px slab instead of
12 B/px of int32 coefficients, and no longer walks every coefficient in
numpy. The host keeps what changes a scan's length: the 0xFF byte
stuffing and the JFIF/Part-10 wrap (``jpeg.encode_coef_batch``).

One dispatch (``huffman_encode``) codes a ``(n, 3, H, W)`` batch, each
tile independently (its own scan, DC predictors reset). On the TPU an
element-wise gather or scatter costs 5–20 ns an element against well
under 0.1 ns for a dense pass, so the coder is dense passes over every
coefficient slot, with no compaction of the symbols, no gather and no
loop over symbols:

1. **Slots.** A tile's coefficients as a (64, U) array: zigzag slot by
   block-component *unit* (``u = block·3 + comp``, the scan's order).
   Slot 0 holds the DC difference (predictor reset per tile).
2. **Symbols.** Every slot that emits bits — the DC, a nonzero AC, and
   slot 63, which is the last coefficient or the EOB — gets its run (an
   exclusive cummax of the unit's earlier nonzero slots), its category
   (``32 - clz|v|``), ZRLs, code and magnitude bits: one left-aligned bit
   string of ≤ 59 bits (≤ 3 ZRLs of ≤ 11 bits, a ≤ 16-bit code, ≤ 11
   magnitude bits) in two uint32 halves. Every other slot is 0 bits long.
   Codes come without a gather: a short code by matching the table's few
   short symbols, a 16-bit code by arithmetic on the symbol's rank
   (``_ac_code``); DC categories by matching (``_match``).
3. **Packing,** MSB first, in levels: each level groups consecutive bit
   strings, takes their offsets in the group from an exclusive cumsum of
   their lengths, shifts each into place and sums the group's pieces
   (bits are disjoint, so the sum is the OR). Slots → units (≤ 8 words),
   8 units → a group (≤ 32 words), 16 groups → a block of 128 units
   (≤ 512 words), a tile's blocks → its slab. Short groups sum by a
   masked reduction; long ones move each string by its word offset
   through a barrel of static shifts (``_merge``). The last byte is
   padded with 1-bits, as ``_BitWriter.flush`` does, and the words are
   split into big-endian bytes.

Everything is 32-bit. A tile with a unit, group or block over its words,
with more bits than its slab (0.5 B/px), or with a DC difference or AC
value outside the baseline tables' categories (DC ≤ 11, AC ≤ 10) is
flagged; the caller codes it with the numpy coder, which raises that
coder's own ``ValueError`` where it would. The numpy coder
(``jpeg._entropy_encode_batch``) is the oracle: scans are byte-identical
(tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.wsi import jpeg

__all__ = ["huffman_encode"]

#: packing levels: (items merged per group, words per group). A unit's
#: bit string is ≤ 8 words, 8 units' ≤ 32, 16 groups' ≤ 512. On scanner
#: pixels, at the busiest levels the device codes, units reach 146 bits
#: and groups 499, about half their room; blocks average under 4700 bits
#: of 16384.
_UNIT_WORDS = 8
_LEVELS = ((8, 32), (16, 512))


#: ``code << 5 | length`` of every DC category, luma then chroma
_DC_CODES = [[(sym, (c << 5) | ln) for sym, (c, ln)
              in sorted(jpeg._ENC[("dc", tid)].items())] for tid in (0, 1)]
_ZRL = [jpeg._ENC[("ac", tid)][0xF0] for tid in (0, 1)]
#: per AC table: its symbols with codes under 16 bits, (symbol,
#: ``code << 5 | length``), and the first 16-bit code; Annex K gives the
#: 16-bit codes consecutively in symbol order
_AC_SHORT = [
    ([(sym, (c << 5) | ln) for sym, (c, ln)
      in sorted(jpeg._ENC[("ac", tid)].items()) if ln < 16],
     min(c for c, ln in jpeg._ENC[("ac", tid)].values() if ln == 16))
    for tid in (0, 1)]


def _match(idx, pairs):
    """The value paired with ``idx`` in ``pairs`` ((key, value) constants),
    0 where none is: a chain of selects, no gather."""
    out = jnp.zeros_like(idx)
    for key, value in pairs:
        out = jnp.where(idx == key, value, out)
    return out


def _ac_code(sym, chroma):
    """``code << 5 | length`` of AC symbol ``sym`` (run << 4 | size) in the
    luma or the chroma table, with no gather: a short code by matching the
    table's few short symbols; a 16-bit code as the first one plus the
    symbol's rank among the 16-bit ones — its rank among all valid
    symbols (EOB, then ten sizes a run, ZRL before run 15's) less the
    short symbols below it."""
    r, size = sym >> 4, sym & 15
    out = []
    for short, first in _AC_SHORT:
        code = _match(sym, short)
        below = sum((sym > s_sym).astype(jnp.int32) for s_sym, _ in short)
        rank = r * 10 + size + (r == 15) - below
        out.append(jnp.where(code > 0, code, ((first + rank) << 5) | 16))
    return jnp.where(chroma == 1, out[1], out[0])


def _shl(x, k):
    """``x << k`` for uint32 ``x``, 0 where ``k`` ≥ 32."""
    return jnp.where(k >= 32, jnp.uint32(0),
                     x << jnp.clip(k, 0, 31).astype(jnp.uint32))


def _shr(x, k):
    """``x >> k`` (logical) for uint32 ``x``, 0 where ``k`` ≥ 32."""
    return jnp.where(k >= 32, jnp.uint32(0),
                     x >> jnp.clip(k, 0, 31).astype(jnp.uint32))


def _place(val, n, p):
    """``n`` bits of ``val`` at bits ``p .. p+n`` (from the MSB) of a 64-bit
    string held as (high, low) uint32; ``p + n`` ≤ 59."""
    e = 64 - p - n  # left shift of val inside the 64 bits, 5..62
    h = jnp.where(e >= 32, _shl(val, e - 32), _shr(val, 32 - e))
    lo = jnp.where(e >= 32, jnp.uint32(0), _shl(val, e))
    return h, lo


def _mag(v, s):
    """JPEG magnitude bits of ``v`` in category ``s``."""
    return (jnp.where(v >= 0, v, v + (1 << s) - 1) & ((1 << s) - 1)) \
        .astype(jnp.uint32)


def _shifted(words, sh):
    """(K, ...) left-aligned words shifted right by ``sh`` (< 32) bits into
    K + 1 words."""
    zero = jnp.zeros_like(words[:1])
    prev = jnp.concatenate([zero, words])
    cur = jnp.concatenate([words, zero])
    return _shr(cur, sh) | _shl(prev, 32 - sh)


def _merge(words, lengths, k_out: int):
    """Concatenate the bit strings of each group: ``words`` (K, N, G)
    left-aligned, ``lengths`` (N, G) → (k_out, N) words, (N,) lengths, and
    whether a group overflowed its ``k_out`` words.

    Each string is shifted right by its bit offset in the group; then its
    words go down by its word offset. Into a short group (≤ 32 words)
    every output word sums the pieces that fall on it, a masked reduction
    that XLA fuses; into a long one each string moves through a barrel of
    static shifts, one select a bit of the offset, and the group's
    strings are summed."""
    o = jnp.cumsum(lengths, axis=-1) - lengths
    ws = o >> 5
    pieces = _shifted(words, o & 31)  # (K + 1, N, G)
    if k_out <= 32:
        k = jnp.arange(pieces.shape[0])[:, None, None]
        m = jnp.arange(k_out)[:, None, None, None]
        hit = (ws[None] + k)[None] == m  # (k_out, K + 1, N, G)
        out = jnp.where(hit, pieces[None], jnp.uint32(0)).sum(
            axis=(1, 3), dtype=jnp.uint32)
    else:
        v = jnp.pad(pieces, ((0, k_out - 1), (0, 0), (0, 0)))
        for b in range((k_out - 1).bit_length()):
            step = 1 << b
            moved = jnp.pad(v[:-step], ((step, 0), (0, 0), (0, 0)))
            v = jnp.where(((ws >> b) & 1).astype(bool), moved, v)
        out = v[:k_out].sum(axis=-1, dtype=jnp.uint32)
    total = lengths.sum(axis=-1)
    return out, total, jnp.any(total > 32 * k_out)


def _group(words, lengths, g: int):
    """(K, N) → (K, N/g, g), zero-length items padding N to a multiple."""
    pad = -lengths.shape[0] % g
    words = jnp.pad(words, ((0, 0), (0, pad)))
    lengths = jnp.pad(lengths, (0, pad))
    return (words.reshape(words.shape[0], -1, g),
            lengths.reshape(-1, g))


def _slot_bits(x):
    """One tile's slots: (2, 64, U) left-aligned bit strings, (64, U)
    lengths, and whether a value is out of the baseline tables' range.
    ``x``: (3, H, W) int32 coefficients, blocks in place."""
    H, W = x.shape[1:]
    X = x.reshape(3, H // 8, 8, W // 8, 8).transpose(2, 4, 1, 3, 0) \
        .reshape(64, -1)  # natural position × unit
    Z = jnp.concatenate([X[p:p + 1] for p in jpeg._ZIGZAG])
    U = Z.shape[1]
    chroma = (jnp.arange(U) % 3 != 0).astype(jnp.int32)

    dc = Z[0].reshape(-1, 3)
    diff = (dc - jnp.concatenate([jnp.zeros((1, 3), dc.dtype), dc[:-1]])) \
        .reshape(U)
    s_dc = 32 - lax.clz(jnp.abs(diff))
    ent = jnp.where(chroma == 1, _match(s_dc, _DC_CODES[1]),
                    _match(s_dc, _DC_CODES[0]))
    n_dc = (ent & 31) + s_dc
    dc_bits = (((ent >> 5) << s_dc).astype(jnp.uint32) | _mag(diff, s_dc))
    h_dc = _shl(dc_bits, 32 - n_dc)  # ≤ 22 bits: all in the high word

    A = Z[1:]  # zigzag slots 1..63
    z = jnp.arange(1, 64, dtype=jnp.int32)[:, None]
    nonzero = A != 0
    emits = nonzero | (z == 63)
    prev = lax.cummax(jnp.where(nonzero, z, 0), axis=0)
    run = z - jnp.concatenate([jnp.zeros((1, U), jnp.int32), prev[:-1]]) - 1
    is_eob = (z == 63) & ~nonzero
    s = 32 - lax.clz(jnp.abs(A))
    sym = jnp.where(is_eob, 0, ((run & 15) << 4) | s)
    nzrl = jnp.where(is_eob, 0, run >> 4)
    ent = _ac_code(sym, chroma)
    clen = ent & 31
    main = ((ent >> 5) << s).astype(jnp.uint32) | _mag(A, s)
    zl = jnp.where(chroma == 1, _ZRL[1][1], _ZRL[0][1])
    zc = jnp.where(chroma == 1, _ZRL[1][0], _ZRL[0][0]).astype(jnp.uint32)
    h, l = _place(main, clen + s, nzrl * zl)
    for i in range(3):
        hk, lk = _place(jnp.where(nzrl > i, zc, jnp.uint32(0)), zl, i * zl)
        h, l = h | hk, l | lk
    n_ac = jnp.where(emits, nzrl * zl + clen + s, 0)
    zero = jnp.uint32(0)

    words = jnp.stack([
        jnp.concatenate([h_dc[None], jnp.where(emits, h, zero)]),
        jnp.concatenate([jnp.zeros((1, U), jnp.uint32),
                         jnp.where(emits, l, zero)])])
    lengths = jnp.concatenate([n_dc[None], n_ac])
    bad = (jnp.max(jnp.abs(diff)) > 2047) | (jnp.max(jnp.abs(A)) > 1023)
    return words, lengths, bad


def _tile_scan(x):
    """One tile → (slab bytes, bit count, flag)."""
    H, W = x.shape[1:]
    cap = H * W // 8  # slab words: 0.5 B/px
    words, lengths, flag = _slot_bits(x)

    # slots → units: the unit's slots are the group (axis 1 of 64)
    units, lengths, over = _merge(words.transpose(0, 2, 1), lengths.T,
                                  _UNIT_WORDS)
    flag |= over
    for g, k_out in _LEVELS:
        units, lengths, over = _merge(*_group(units, lengths, g), k_out)
        flag |= over

    # blocks → the slab: one more level, the tile's blocks its one group
    slab, bits, over = _merge(*_group(units, lengths, lengths.shape[0]),
                              cap)
    slab, bits = slab[:, 0], bits[0]
    # flush: 1-bits up to the byte boundary
    pad = (-bits) & 7
    fill = _shl((jnp.uint32(1) << pad.astype(jnp.uint32)) - 1,
                32 - (bits & 31) - pad)
    slab = slab | jnp.where((jnp.arange(cap) == bits >> 5) & (pad > 0),
                            fill, jnp.uint32(0))
    out = jnp.stack([(slab >> sft) & 0xFF for sft in (24, 16, 8, 0)],
                    axis=-1).astype(jnp.uint8).reshape(-1)
    return out, bits, flag | over


@jax.jit
def huffman_encode(coef):
    """(n, 3, H, W) int32 quantised coefficients (blocks in place) →
    ``(slabs, bits, flags)``: (n, H·W/2) uint8 packed scan bytes, unstuffed,
    flush-padded; (n,) int32 bit counts before the pad; (n,) bool, true
    where the tile must be coded on the host instead."""
    with jax.named_scope("huffman_encode"):
        return jax.vmap(_tile_scan)(coef.astype(jnp.int32))
