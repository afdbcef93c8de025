"""Fused RGB→YCbCr + JPEG level shift as a Pallas TPU kernel.

Purely elementwise across the channel dim → VPU work. Blocks are
(3, 8, 128)-shaped VMEM tiles (8×128 = one VREG tile per channel); the grid
walks the (H/8, W/128) plane. The three output planes are produced in one
pass over the input — the fusion the CPU converter gets from SIMD loops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import ycbcr_polynomials

__all__ = ["rgb2ycbcr_pallas"]

_BH, _BW = 8, 128


def _kernel(x_ref, o_ref):
    y, cb, cr = ycbcr_polynomials(x_ref[0], x_ref[1], x_ref[2])
    o_ref[0, :, :] = y
    o_ref[1, :, :] = cb
    o_ref[2, :, :] = cr


def rgb2ycbcr_pallas(img, *, interpret: bool):
    """img: (3, H, W) uint8/float, H % 8 == 0, W % 128 == 0 → (3, H, W) f32."""
    C, H, W = img.shape
    assert C == 3 and H % _BH == 0 and W % _BW == 0, img.shape
    grid = (H // _BH, W // _BW)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((3, _BH, _BW), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((3, _BH, _BW), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((3, H, W), jnp.float32),
        interpret=interpret,
        name="rgb2ycbcr",
    )(img.astype(jnp.float32))
