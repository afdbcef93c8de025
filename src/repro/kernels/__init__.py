"""Pallas TPU kernels for the conversion hot spots (+ jnp oracles).

``<name>.py`` holds the ``pl.pallas_call`` + BlockSpec tiling, ``ops.py`` the
jit'd public wrappers, ``ref.py`` the pure-jnp ground truth.
"""
from repro.kernels.ops import (  # noqa: F401
    dct8x8_quant,
    downsample2x2,
    idct8x8_dequant,
    jpeg_inverse,
    jpeg_inverse420,
    jpeg_transform,
    rgb2ycbcr,
)
from repro.kernels.wkv_chunk import wkv_chunk_pallas  # noqa: F401
