"""Stride-2 2×2 box-filter pyramid downsample as a Pallas TPU kernel.

Builds every WSI pyramid level. Channel-planar layout: each grid step loads a
(1, 16, 256) input VMEM block and writes the (1, 8, 128) mean-pooled output
block (8×128 = one VREG tile), so both sides stay hardware-aligned. The
pooling is two matmuls with constant 0/1 and 0.25 matrices — row pairs
(``R·X``, R is 8×16), then column pairs (``·P``, P is 256×128) — because
the TPU's kernel compiler refuses strided value slices and strided loads
from a 256-lane block. Sums of uint8-valued samples are exact at full f32
precision, so the pyramid levels equal the oracle's bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.ref import HIGHEST

__all__ = ["downsample2x2_pallas"]

_BH, _BW = 8, 128  # output block


def _pool_matrices() -> tuple[np.ndarray, np.ndarray]:
    """R (8×16) sums row pairs; P (256×128) averages column pairs (0.25
    folds in the row pair's share of the mean; scaling by 1/4 is exact)."""
    rows = np.kron(np.eye(_BH, dtype=np.float32), np.ones((1, 2), np.float32))
    cols = np.kron(np.eye(_BW, dtype=np.float32),
                   np.full((2, 1), 0.25, np.float32))
    return rows, cols


def _kernel(x_ref, r_ref, p_ref, o_ref):
    rows = jnp.matmul(r_ref[...], x_ref[0], precision=HIGHEST,
                      preferred_element_type=jnp.float32)
    o_ref[0] = jnp.matmul(rows, p_ref[...], precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def downsample2x2_pallas(img, *, interpret: bool):
    """img: (C, H, W); H % 16 == 0, W % 256 == 0 → (C, H//2, W//2) float32."""
    C, H, W = img.shape
    assert H % (2 * _BH) == 0 and W % (2 * _BW) == 0, img.shape
    rows, cols = _pool_matrices()
    return pl.pallas_call(
        _kernel,
        grid=(C, H // (2 * _BH), W // (2 * _BW)),
        in_specs=[
            pl.BlockSpec((1, 2 * _BH, 2 * _BW), lambda c, i, j: (c, i, j)),
            pl.BlockSpec((_BH, 2 * _BH), lambda c, i, j: (0, 0)),
            pl.BlockSpec((2 * _BW, _BW), lambda c, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _BH, _BW), lambda c, i, j: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, H // 2, W // 2), jnp.float32),
        interpret=interpret,
        name="downsample2x2",
    )(img.astype(jnp.float32), rows, cols)
