"""Operations and bytes of the inverse transform of tiles with 4:2:0
chroma, from shapes alone, in ``work.py``'s stance: the algorithm's
minimum, whatever implements it. Per pixel: 1.5 int16 quantised
coefficient samples in (one luma, two quarter-size chroma), 3 B of RGB
out; dequantisation and the separable 8x8 iDCT on the 1.5 samples; the
upsample of two chroma planes (each output sample of a pass: two
multiplies and an add, the vertical pass making half the samples of the
horizontal one); colour conversion; round and clip."""
from __future__ import annotations

from work import (DCT_PER_PX, DEQUANT_PER_COEF, INV_COLOUR_PER_PX,
                  ROUND_CLIP)

#: coefficient samples per pixel at 4:2:0
SAMPLES_PER_PX = 1 + 2 / 4
#: triangle upsampling of one chroma plane, per output pixel: a vertical
#: pass making half the samples and a horizontal one making all of them
UPSAMPLE_PER_PX = 3 * (0.5 + 1)


def inverse420(px: float) -> tuple[float, float]:
    """Inverse JPEG transform of 4:2:0 tiles to ``px`` RGB pixels:
    (flops, bytes)."""
    flops = px * (SAMPLES_PER_PX * (DEQUANT_PER_COEF + DCT_PER_PX)
                  + 2 * UPSAMPLE_PER_PX + INV_COLOUR_PER_PX + 3 * ROUND_CLIP)
    return flops, px * (SAMPLES_PER_PX * 2 + 3)
