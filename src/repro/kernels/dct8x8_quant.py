"""Fused 8×8 blockwise DCT-II + quantization as a Pallas TPU kernel.

The JPEG transform stage, re-blocked for the MXU: each grid step loads an
(8, 128) VMEM block (= 16 DCT blocks side by side) and runs the separable
2-D DCT of every block as two plain matmuls on the strip,

    Y = C · X · (I₁₆ ⊗ Cᵀ)      (``ref.strip_transform``)

then fuses the divide-by-Q rounding. The quant table (Q repeated 16×) and
both DCT matrices ride along as operands mapped to block (0, 0), so they
stay resident in VMEM across the grid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import (STRIP, qtable_strip, strip_dct_matrices,
                               strip_transform)

__all__ = ["dct8x8_quant_pallas"]

_BH, _BW = 8, STRIP


def _kernel(x_ref, q_ref, l_ref, r_ref, o_ref):
    y = strip_transform(x_ref[...], l_ref[...], r_ref[...])
    o_ref[...] = jnp.round(y / q_ref[...]).astype(jnp.int32)


def dct8x8_quant_pallas(plane, qtable, *, interpret: bool):
    """plane: (H, W) float32 level-shifted; qtable: (8, 8).

    H % 8 == 0, W % 128 == 0. Returns (H, W) int32 quantized coefficients.
    """
    H, W = plane.shape
    assert H % _BH == 0 and W % _BW == 0, plane.shape
    left, right = strip_dct_matrices()
    const = lambda i, j: (0, 0)  # noqa: E731
    return pl.pallas_call(
        _kernel,
        grid=(H // _BH, W // _BW),
        in_specs=[
            pl.BlockSpec((_BH, _BW), lambda i, j: (i, j)),
            pl.BlockSpec((_BH, _BW), const),
            pl.BlockSpec((8, 8), const),
            pl.BlockSpec((_BW, _BW), const),
        ],
        out_specs=pl.BlockSpec((_BH, _BW), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((H, W), jnp.int32),
        interpret=interpret,
    )(plane.astype(jnp.float32), qtable_strip(qtable), left, right)
