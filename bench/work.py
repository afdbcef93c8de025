"""Operations and bytes that the algorithm needs, from shapes alone.

The counts are the algorithm's minimum, whatever implements it: pixels in
as uint8 (3 B/px), quantised coefficients as int16 (6 B/px for three
channels), a separable 8x8 DCT as two 8x8 matrix products per block.
A faster or narrower implementation of the same work therefore reads as a
gain here, not as a changed count.
"""
from __future__ import annotations

#: flops per pixel of one 8x8 separable (i)DCT on one channel: two 8x8x8
#: multiply-adds per 64 pixels
DCT_PER_PX = 2 * (2 * 8 * 8 * 8) / 64
#: forward colour conversion: Y (3 mul, 2 add, level shift), Cb, Cr (3 mul,
#: 2 add each)
COLOUR_PER_PX = 6 + 5 + 5
#: inverse colour conversion: level shift, R (1 mul 1 add), G (2, 2),
#: B (1, 1)
INV_COLOUR_PER_PX = 1 + 2 + 4 + 2
#: quantise (divide, round) or dequantise (multiply) per coefficient
QUANT_PER_COEF = 2
DEQUANT_PER_COEF = 1
#: round and clip per output sample
ROUND_CLIP = 3


def transform(px: float) -> tuple[float, float]:
    """Forward JPEG transform of ``px`` RGB pixels: (flops, bytes)."""
    flops = px * (COLOUR_PER_PX + 3 * (DCT_PER_PX + QUANT_PER_COEF))
    return flops, px * (3 + 6)


def downsample(px_in: float) -> tuple[float, float]:
    """2x2 box downsample of ``px_in`` RGB pixels: (flops, bytes)."""
    out = px_in / 4
    flops = 3 * (px_in - out) + 3 * out * (1 + ROUND_CLIP)  # adds, scale
    return flops, px_in * 3 + out * 3


def inverse(px: float) -> tuple[float, float]:
    """Inverse JPEG transform to ``px`` RGB pixels: (flops, bytes)."""
    flops = px * (3 * (DEQUANT_PER_COEF + DCT_PER_PX) + INV_COLOUR_PER_PX
                  + 3 * ROUND_CLIP)
    return flops, px * (6 + 3)


def pyramid(sides: list[int]) -> tuple[float, float]:
    """The fused pyramid of a square slide whose levels have these sides:
    every level transformed, every level but the last downsampled."""
    flops = nbytes = 0.0
    for i, s in enumerate(sides):
        f, b = transform(s * s)
        flops, nbytes = flops + f, nbytes + b
        if i + 1 < len(sides):
            f, b = downsample(s * s)
            flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which roof bounds it."""
    tc = flops / peaks["flops_per_s"]
    tm = nbytes / peaks["bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
