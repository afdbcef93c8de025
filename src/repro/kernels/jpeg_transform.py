"""Whole-level fused JPEG transform (RGB→YCbCr→8×8 DCT→quant) Pallas kernel.

One ``pallas_call`` transform-codes an entire pyramid level: the input is a
``(N, 3, T, T)`` batch of RGB tiles and the output the ``(N, 3, T, T)`` int32
quantized YCbCr DCT coefficients — the whole device side of the JPEG encoder
in a single dispatch, versus the 4 per-tile dispatches of the unfused path
(``rgb2ycbcr`` + 3× ``dct8x8_quant``). For an L-tile level that is a 4L→1
dispatch reduction (see DESIGN.md, "Whole-level batched dispatch").

Grid: ``(N, T/8, T/128)``. Each step loads one (1, 3, 8, 128) VMEM block —
an 8×128 strip of all three channels of one tile (16 DCT blocks side by
side) — converts to level-shifted YCbCr on the VPU, runs each channel's
block DCT as two strip matmuls on the MXU (``ref.strip_transform``) and
fuses the divide-by-Q rounding. The quantization tables ride along as one
(3, 8, 128) operand (luma, chroma, chroma) and the DCT matrices as two
more, all mapped to block 0 so they stay resident in VMEM.

Bit-exactness contract: the per-channel math is expression-identical to the
unfused ``rgb2ycbcr`` / ``dct8x8_quant`` kernels (shared
``ref.ycbcr_polynomials`` and ``ref.strip_transform`` on the same (8, 128)
strips), so the fused path produces the same int32 coefficients — the
batched and per-tile JPEG byte streams match exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import (STRIP, qtable_strip, strip_dct_matrices,
                               strip_transform, ycbcr_polynomials)

__all__ = ["jpeg_transform_pallas"]

_BH, _BW = 8, STRIP


def _kernel(x_ref, q_ref, l_ref, r_ref, o_ref):
    left, right = l_ref[...], r_ref[...]
    planes = ycbcr_polynomials(x_ref[0, 0], x_ref[0, 1], x_ref[0, 2])
    for ci, plane in enumerate(planes):
        y = strip_transform(plane, left, right)
        o_ref[0, ci] = jnp.round(y / q_ref[ci]).astype(jnp.int32)


def jpeg_transform_pallas(tiles, qluma, qchroma, *, interpret: bool):
    """tiles: (N, 3, H, W) uint8/float RGB; q*: (8, 8) tables.

    H % 8 == 0, W % 128 == 0. Returns (N, 3, H, W) int32 quantized YCbCr
    DCT coefficients (blocks in place) in one ``pallas_call``.
    """
    N, C, H, W = tiles.shape
    assert C == 3 and H % _BH == 0 and W % _BW == 0, tiles.shape
    qwide = jnp.stack([qtable_strip(q) for q in (qluma, qchroma, qchroma)])
    left, right = strip_dct_matrices()
    return pl.pallas_call(
        _kernel,
        grid=(N, H // _BH, W // _BW),
        in_specs=[
            pl.BlockSpec((1, 3, _BH, _BW), lambda n, i, j: (n, 0, i, j)),
            pl.BlockSpec((3, _BH, _BW), lambda n, i, j: (0, 0, 0)),
            pl.BlockSpec((8, 8), lambda n, i, j: (0, 0)),
            pl.BlockSpec((_BW, _BW), lambda n, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 3, _BH, _BW), lambda n, i, j: (n, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((N, 3, H, W), jnp.int32),
        interpret=interpret,
        name="jpeg_transform",
    )(tiles.astype(jnp.float32), qwide, left, right)
