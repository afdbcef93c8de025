"""Jitted lockstep JPEG entropy decoder — the small-batch cliff fix.

The numpy lockstep decoder in ``repro.wsi.jpeg`` pays interpreter and
numpy-dispatch cost once per symbol *position* across the batch (~50–90µs
per step). A 16-tile level of tissue tiles runs ~10k lockstep steps, so the
"vectorized" path costs ~800ms of pure interpreter overhead — slower than
the per-tile Python loop it is supposed to amortize (BENCH_export.json
recorded 0.82x at 16 tiles). The overhead is per *step*, so no batch-size
bucketing of the transform kernels can remove it.

This module compiles the identical lockstep automaton into a single
``jax.lax.while_loop`` dispatch: one compiled step costs a few µs of
gathers/elementwise work instead of an interpreter sweep, so the batched
decode path stays ahead of the per-tile loop at **every** batch size — the
``batch_scaling`` acceptance gate in ``benchmarks/export_bench.py``.

Contract with the numpy engine (``jpeg._run_packed``, which
remains the differential oracle and still serves tiny batches where a
compile would dominate):

* coefficient-exact equality on every decodable stream — the automaton is
  a transliteration, step for step, of the numpy loop;
* identical ``ValueError("corrupt JPEG …")`` strings raised at identical
  failure points. The compiled loop cannot raise mid-flight, so each lane
  carries an error flag; the loop exits on the first flagged step, and the
  host replays the numpy engine's raise priority (invalid Huffman code
  before AC overrun before truncation — all surviving flags are from the
  same step, so the replay is exact).

The MCU's pattern of blocks and the tables each is coded with come from
the stream (``jpeg.Coding``): static per coding, they are part of the
compile key, and the LUTs are the stream's own. On the ingest path the
coefficients stay on the device (``decode_packed``, then
``coef_planes``: DC integration and de-zigzag by a 64×64 permutation as a
matmul); only the error flags and the loop's trip count come back to the
host.

Everything runs in 32 bits (no x64). The scan buffer reaches the loop as
big-endian 32-bit words, and each step reads one 32-bit window at the bit
cursor, joined from the two words it straddles by logical shifts: its top
16 bits index the Huffman LUT, whose int32 entries hold the symbol and
the code length, and the ≤ 11 magnitude bits follow the ≤ 16-bit code
inside the same window (ln + s ≤ 27). A step thus reads two words and one
LUT entry, and writes one coefficient; the magnitude's masks are shifts.
Bit cursors stay well under 2^31 for any realistic level (callers keep
batches below ``2^27`` buffer bytes).
"""
from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import tracing

__all__ = ["pack_scans", "decode_packed", "run_packed", "coef_planes"]

_ERR_INVALID, _ERR_RUN, _ERR_TRUNC = 1, 2, 3

#: per-tile zero bytes after each scan — same layout (and same reason) as
#: the numpy engine's guard: a lane reads only while its cursor is at or
#: before its stream's end, and the two words it reads then end ≤ 64 bits
#: past the cursor
_GUARD = 8


def _pick(m, rows: tuple[int, ...]):
    """``rows[m]`` for a static pattern, as selects (no gather)."""
    out = jnp.full_like(m, rows[-1])
    for i, r in enumerate(rows[:-1]):
        if r != rows[-1]:
            out = jnp.where(m == i, r, out)
    return out


@partial(jax.jit, static_argnames=("nu", "dc_rows", "ac_rows"))
def _lockstep(words, pos0, ends, u0, lut, *, nu: int,
              dc_rows: tuple[int, ...], ac_rows: tuple[int, ...]):
    """Run all lanes to completion (or first error) → the error flags, the
    flat coefficients and the number of steps taken. Shapes and the MCU's
    table pattern (``dc_rows``/``ac_rows``: the LUT row of each block of an
    MCU) are the compile key: callers pad the lane count and buffer length
    to powers of two so every level of a slide reuses a handful of cached
    executables."""
    n = pos0.shape[0]
    upm = len(dc_rows)
    total = n * nu * 64
    base = jnp.arange(n, dtype=jnp.int32) * (nu * 64)

    def cond(st):
        pos, u, k, err, zzf, steps = st
        return jnp.any(u < nu) & ~jnp.any(err > 0)

    def body(st):
        pos, u, k, err, zzf, steps = st
        active = u < nu

        # the 32 bits from the cursor, out of the two words it straddles
        # (the second word's shift is split in two so that at phase 0 no
        # shift reaches 32 bits)
        wi = pos >> 5
        sh = (pos & 31).astype(jnp.uint32)
        win = (words[wi] << sh) | ((words[wi + 1] >> 1) >> (31 - sh))
        is_dc = k == 0
        m = u % upm
        tbl = jnp.where(is_dc, _pick(m, dc_rows), _pick(m, ac_rows))
        ent = lut[tbl * 65536 + (win >> 16).astype(jnp.int32)]
        sym, ln = ent & 0xFF, ent >> 8

        # the s ≤ 11 magnitude bits right after the code, in the same
        # window; s = 0 (and an invalid code's ln = 0) masks them all off
        s = jnp.where(is_dc, sym, sym & 0xF)
        ext = (1 << s) - 1
        bits = (win >> (32 - ln - s).astype(jnp.uint32)).astype(jnp.int32) \
            & ext
        v = jnp.where(bits >= (1 << jnp.maximum(s - 1, 0)), bits, bits - ext)
        pos = jnp.where(active, pos + ln + s, pos)

        is_eob = ~is_dc & (sym == 0x00)
        is_zrl = ~is_dc & (sym == 0xF0)
        is_coef = ~(is_dc | is_eob | is_zrl)
        knew = k + (sym >> 4)
        err = jnp.where(active & (ln == 0), _ERR_INVALID,
                        jnp.where(active & is_coef & (knew > 63),
                                  _ERR_RUN, err))

        # one scatter: DC differential at slot 0, AC values at slot knew;
        # non-writing lanes aim past the buffer and are dropped
        write = active & (is_dc | is_coef) & (err == 0)
        tgt = jnp.where(write, base + u * 64 + jnp.where(is_dc, 0, knew),
                        total)
        zzf = zzf.at[tgt].set(v, mode="drop")

        k = jnp.where(is_dc, 1,
                      jnp.where(is_zrl, k + 16,
                                jnp.where(is_coef, knew + 1, k)))
        adv = active & (is_eob | (k >= 64))
        u = u + adv
        k = jnp.where(adv, 0, k)
        err = jnp.where((u < nu) & (err == 0) & (pos > ends),
                        _ERR_TRUNC, err)
        return pos, u, k, err, zzf, steps + 1

    state = (pos0, u0, jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
             jnp.zeros(total, jnp.int32), jnp.int32(0))
    with jax.named_scope("lockstep_decode"):
        pos, u, k, err, zzf, steps = jax.lax.while_loop(cond, body, state)
    return err, zzf, steps


@lru_cache(maxsize=32)
def _device_tables(huff: tuple, device=None) -> jax.Array:
    """A stream's Huffman LUTs flattened for a single-gather lookup, each
    int32 entry ``symbol | code length << 8`` (length 0: no code),
    committed once per table set and device."""
    from repro.wsi import jpeg
    sym, ln = jpeg._luts(huff)
    packed = sym.astype(np.int32) | ln.astype(np.int32) << 8
    return jax.device_put(packed.reshape(-1), device)


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pack_scans(scans: list[np.ndarray], nu: int) -> tuple:
    """The host half: N unstuffed scans of ``nu`` blocks each → one
    guarded, power-of-two padded buffer of big-endian 32-bit words (the
    same bytes) with each lane's start bit, end bit and first unit.

    Lane count and buffer length are padded to powers of two so the jit
    cache stays small; pad lanes start exhausted (``u = nu``) and can
    neither write nor flag errors.
    """
    N = len(scans)
    npad = _pow2(N)

    sizes = np.array([scan.size for scan in scans], np.int64)
    offs = np.zeros(npad, np.int64)
    offs[:N] = np.cumsum(sizes + _GUARD) - sizes - _GUARD
    ends = np.zeros(npad, np.int64)
    ends[:N] = (offs[:N] + sizes) * 8
    blen = _pow2(max(int(sizes.sum()) + N * _GUARD, _GUARD))
    assert blen * 8 < 2**31, "scan buffer too large for int32 bit cursors"
    raw = np.zeros(blen, np.uint8)
    for off, scan in zip(offs, scans):
        raw[off:off + scan.size] = scan

    u0 = np.full(npad, nu, np.int32)
    u0[:N] = 0
    words = raw.view(">u4").astype(np.uint32)
    return words, (offs * 8).astype(np.int32), ends.astype(np.int32), u0, N


def decode_packed(packed: tuple, H: int, W: int, coding) -> jax.Array:
    """The device half: the lockstep loop over a packed buffer (host
    arrays, or ``pack_scans``' arrays already on a device). Only the error
    flags and the loop's trip count come back: a corrupt stream raises the
    numpy engine's ``ValueError``; the coefficients stay on the device as a
    flat (lanes · blocks · 64,) int32 array of zigzag coefficients, the DC
    slots holding differentials. The ambient span (``decode.entropy``)
    records ``steps`` (the loop's trips: the most symbols any lane decodes)
    and ``lanes`` (the loop's width, padded to a power of two)."""
    words, bit0, ends, u0, N = packed
    device = next(iter(words.devices())) if isinstance(words, jax.Array) \
        else None
    err, zzf, steps = _lockstep(words, bit0, ends, u0,
                                _device_tables(coding.huff, device),
                                nu=coding.units(H, W),
                                dc_rows=coding.dc_rows,
                                ac_rows=coding.ac_rows)
    err, steps = jax.device_get((err, steps))
    sp = tracing.current_span()
    if sp is not None:
        sp.attrs.update(steps=int(steps), lanes=err.size)
    if (err == _ERR_INVALID).any():
        raise ValueError("corrupt JPEG stream: invalid Huffman code")
    if (err == _ERR_RUN).any():
        raise ValueError("corrupt JPEG stream: AC run past end of block")
    if (err == _ERR_TRUNC).any():
        raise ValueError("corrupt JPEG stream: truncated scan data")
    return zzf


def run_packed(packed: tuple, H: int, W: int, coding) -> np.ndarray:
    """``decode_packed`` with the coefficients fetched back → (N, mcus,
    blocks per MCU, 64) int32 zigzag coefficients, DC differentials."""
    N, npad = packed[4], packed[3].shape[0]
    upm = len(coding.unit_comps)
    zzf = np.array(decode_packed(packed, H, W, coding))
    return zzf.reshape(npad, -1)[:N].reshape(N, -1, upm, 64)


def _dezigzag() -> np.ndarray:
    """P with ``zz @ P`` = the natural-order block of a zigzag one."""
    from repro.wsi.jpeg import _ZIGZAG
    p = np.zeros((64, 64), np.float32)
    p[np.arange(64), _ZIGZAG] = 1.0
    return p


@partial(jax.jit, static_argnames=("n", "H", "W", "coding"))
def coef_planes(zzf, *, n: int, H: int, W: int, coding):
    """The lockstep decoder's flat zigzag coefficients (DC differentials)
    of ``n`` H×W tiles → Y (n, H, W) and chroma (n, 2, h, w) int32
    coefficient planes, blocks in place, on the device.

    The DC differentials are integrated per component in bitstream order
    (the predictor resets at every tile: each is its own scan). The
    de-zigzag is one 64×64 permutation as a matmul at ``HIGHEST``: exact
    for |coefficient| < 2²⁴, and on this chip a gather or scatter of
    millions of elements costs far more than a matmul.
    """
    upm = len(coding.unit_comps)
    hm, vm = coding.hmax, coding.vmax
    mr, mc = H // (8 * vm), W // (8 * hm)
    zz = zzf.reshape(-1, mr * mc, upm, 64)[:n]
    dc, first = [], 0
    for h, v in coding.sampling:
        k = h * v
        part = zz[:, :, first:first + k, 0].reshape(n, -1)
        dc.append(jnp.cumsum(part, axis=1).reshape(n, mr * mc, k))
        first += k
    zz = zz.at[..., 0].set(jnp.concatenate(dc, axis=2))
    nat = jnp.matmul(zz.astype(jnp.float32), _dezigzag(),
                     precision=jax.lax.Precision.HIGHEST)
    planes, first = [], 0
    for h, v in coding.sampling:
        blk = nat[:, :, first:first + h * v].reshape(n, mr, mc, v, h, 8, 8)
        planes.append(blk.transpose(0, 1, 3, 5, 2, 4, 6)
                      .reshape(n, mr * v * 8, mc * h * 8).astype(jnp.int32))
        first += h * v
    return planes[0], jnp.stack(planes[1:], axis=1)
