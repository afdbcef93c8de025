"""Whole-level fused inverse JPEG transform (dequant→iDCT→YCbCr→RGB) kernel.

The exact mirror of ``jpeg_transform.py``: one ``pallas_call`` inverts an
entire pyramid level — the input is an ``(N, 3, T, T)`` batch of int32
quantized YCbCr DCT coefficients (blocks in place, as the forward kernel
and the entropy decoder emit them) and the output the ``(N, 3, T, T)``
int32 RGB samples in [0, 255] — the whole device side of the JPEG decoder
in a single dispatch. This is the compute spine of the export subsystem
(DICOM study → tiled TIFF): decoding a stored level is one entropy-decode
pass on the host plus this one dispatch, versus 3 iDCT dispatches + a host
color conversion per tile on the per-tile path.

Grid: ``(N, T/8, T/128)``. Each step loads one (1, 3, 8, 128) VMEM block —
an 8×128 strip of all three coefficient channels of one tile (16 DCT
blocks side by side) — multiplies by the per-channel quantization tables
(riding along as a single resident (3, 8, 128) operand, exactly as in the
forward kernel), runs each channel's block iDCT as two strip matmuls on
the MXU (``Cᵀ · X · (I₁₆ ⊗ C)``), then applies the YCbCr→RGB polynomials
+ level unshift on the VPU and rounds/clips to [0, 255].

Bit-exactness contract: the inverse contraction is ``ref.strip_transform``
and the color polynomials ``ref.ycbcr_inverse_polynomials`` — a single
copy each, shared between this kernel body and the jnp oracle, so the
fused path produces the same RGB samples and the batched and per-tile JPEG
decode paths emit pixel-identical tiles.

The output is int32, not uint8: 8-bit outputs would need (32, 128)-tiled
blocks on real hardware, and the public wrapper (``ops.jpeg_inverse``)
casts to uint8 outside the kernel either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import (STRIP, qtable_strip, strip_dct_matrices,
                               strip_transform, ycbcr_inverse_polynomials)

__all__ = ["jpeg_inverse_pallas"]

_BH, _BW = 8, STRIP


def _kernel(c_ref, q_ref, l_ref, r_ref, o_ref):
    left, right = l_ref[...], r_ref[...]
    planes = [strip_transform(c_ref[0, ci].astype(jnp.float32) * q_ref[ci],
                              left, right)
              for ci in range(3)]
    for ci, chan in enumerate(ycbcr_inverse_polynomials(*planes)):
        o_ref[0, ci] = jnp.clip(jnp.round(chan), 0, 255).astype(jnp.int32)


def jpeg_inverse_pallas(coef, qluma, qchroma, *, interpret: bool):
    """coef: (N, 3, H, W) int32 quantized coefficients; q*: (8, 8) tables.

    H % 8 == 0, W % 128 == 0. Returns (N, 3, H, W) int32 RGB samples in
    [0, 255] (cast to uint8 by the ``ops.jpeg_inverse`` wrapper) in one
    ``pallas_call``.
    """
    N, C, H, W = coef.shape
    assert C == 3 and H % _BH == 0 and W % _BW == 0, coef.shape
    qwide = jnp.stack([qtable_strip(q) for q in (qluma, qchroma, qchroma)])
    left, right = strip_dct_matrices(inverse=True)
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
    )(coef.astype(jnp.int32), qwide, left, right)
