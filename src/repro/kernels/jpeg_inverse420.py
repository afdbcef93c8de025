"""Inverse JPEG transform of tiles with subsampled chroma (dequant → iDCT →
chroma upsample → YCbCr→RGB), one tile per grid step.

A scanner's tiles are 4:2:0: an H×W luma plane and two chroma planes of
half the size each way (4:2:2 and 4:4:4 are the same kernel with an
identity along an axis). One ``pallas_call`` inverts a whole level: grid
``(N,)``, each step loads one tile's Y block (1, H, W) and chroma block
(1, 2, h, w) of quantized coefficients, multiplies by the stream's own
quantisation planes, inverts every 8×8 block with two block-diagonal
matmuls per plane on the MXU (``L·X·R``, ``ref.strip_transform``), upsamples
each chroma plane by the triangle filter as two more matmuls, then applies
the YCbCr→RGB polynomials on the VPU and rounds and clips. The constant
operands (quantisation planes, iDCT and upsampling matrices) are mapped to
block 0, so they stay resident in VMEM; a whole tile per step keeps the
upsampler's neighbours, across 8×8 block edges, inside the block.

Bit-exactness contract: the math is ``ref.inverse420_planes``, a single
copy shared between this kernel body and the jnp oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import inverse420_operands, inverse420_planes

__all__ = ["jpeg_inverse420_pallas"]


def _kernel(y_ref, c_ref, qy_ref, qc_ref, ly_ref, ry_ref, lc_ref, rc_ref,
            uv_ref, uh_ref, o_ref):
    rgb = inverse420_planes(
        y_ref[0], c_ref[0, 0], c_ref[0, 1], qy_ref[...], qc_ref[0],
        qc_ref[1], ly_ref[...], ry_ref[...], lc_ref[...], rc_ref[...],
        uv_ref[...], uh_ref[...])
    for ci, chan in enumerate(rgb):
        o_ref[0, ci] = chan


def jpeg_inverse420_pallas(y, c, q, *, interpret: bool):
    """y: (N, H, W), c: (N, 2, h, w) quantized coefficients; q: (3, 8, 8).

    Returns (N, 3, H, W) float32 RGB samples in [0, 255] (integers) in one
    ``pallas_call``.
    """
    N, H, W = y.shape
    h, w = c.shape[2:]
    ops = inverse420_operands(q, H, W, h, w)

    def const(shape):
        return pl.BlockSpec(shape, lambda n: (0,) * len(shape))

    return pl.pallas_call(
        _kernel,
        grid=(N,),
        in_specs=[pl.BlockSpec((1, H, W), lambda n: (n, 0, 0)),
                  pl.BlockSpec((1, 2, h, w), lambda n: (n, 0, 0, 0))]
        + [const(tuple(o.shape)) for o in ops],
        out_specs=pl.BlockSpec((1, 3, H, W), lambda n: (n, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 3, H, W), jnp.float32),
        interpret=interpret,
        name="jpeg_inverse420",
    )(y.astype(jnp.int32), c.astype(jnp.int32), *ops)
