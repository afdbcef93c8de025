"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rgb2ycbcr_ref", "downsample2x2_ref", "dct8x8_quant_ref",
    "idct8x8_dequant_ref", "jpeg_transform_ref", "jpeg_inverse_ref",
    "strip_transform", "strip_dct_matrices", "qtable_strip",
    "ycbcr_polynomials", "ycbcr_inverse_polynomials", "dct_matrix",
    "JPEG_LUMA_Q", "JPEG_CHROMA_Q", "STRIP",
]

# ITU-T81 Annex K quantization tables (quality 50)
JPEG_LUMA_Q = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99],
], np.float32)

JPEG_CHROMA_Q = np.array([
    [17, 18, 24, 47, 99, 99, 99, 99],
    [18, 21, 26, 66, 99, 99, 99, 99],
    [24, 26, 56, 99, 99, 99, 99, 99],
    [47, 66, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
], np.float32)

#: lanes per DCT strip: 16 8×8 blocks side by side, one (8, 128) f32 tile
STRIP = 128

# f32 matmuls on a TPU default to reduced-precision passes; every DCT and
# pooling contraction asks for full f32 so the chip agrees with the CPU
HIGHEST = jax.lax.Precision.HIGHEST


def dct_matrix() -> np.ndarray:
    """Orthonormal 8×8 DCT-II matrix C (DCT: C·X·Cᵀ)."""
    k = np.arange(8)
    C = np.cos((2 * k[None, :] + 1) * k[:, None] * np.pi / 16)
    C *= np.sqrt(2.0 / 8.0)
    C[0] *= 1.0 / np.sqrt(2.0)
    return C.astype(np.float32)


def strip_dct_matrices(inverse: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """(L, R) with ``L·X·R`` = the 8×8 (i)DCT of every block of a strip.

    X is an (8, 128) strip of 16 blocks side by side. The forward DCT is
    ``L = C`` and ``R = I₁₆ ⊗ Cᵀ`` (block diagonal, 128×128); the inverse
    is ``L = Cᵀ`` and ``R = I₁₆ ⊗ C``. Two plain 2-D matmuls, no reshape
    of the strip: the form the TPU's kernel compiler accepts. The matrices
    are built here with numpy and passed to the kernels as operands —
    XLA's float32 cosine differs from numpy's in the last ULP.
    """
    C = dct_matrix()
    left, right = (C.T, C) if inverse else (C, C.T)
    eye = np.eye(STRIP // 8, dtype=np.float32)
    return np.ascontiguousarray(left), np.kron(eye, right)


def qtable_strip(q) -> jnp.ndarray:
    """An (8, 8) quantization table repeated across one (8, 128) strip."""
    return jnp.tile(jnp.asarray(q, jnp.float32), (1, STRIP // 8))


def strip_transform(x, left, right):
    """(…, 8, 128) strips → ``left·X·right`` per strip.

    The single copy of the block-transform contraction, shared by the
    Pallas kernel bodies (``dct8x8_quant``, ``jpeg_transform``,
    ``jpeg_inverse``) and the oracles below. Both sides must run this one
    expression with the same operand shapes: a reassociated contraction
    drifts the last ULPs, which flips a round-at-half quantization or
    pixel round and breaks the batched/per-tile byte-identity contract.
    """
    t = jnp.matmul(left, x, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    return jnp.matmul(t, right, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _to_strips(x):
    """(…, H, W) → (…, H/8, ⌈W/128⌉, 8, 128); W is zero-padded (a zero
    block transforms to zeros and never mixes with its neighbours)."""
    *lead, H, W = x.shape
    assert H % 8 == 0 and W % 8 == 0, x.shape
    pad = -W % STRIP
    x = jnp.pad(x, [(0, 0)] * (len(lead) + 1) + [(0, pad)])
    x = x.reshape(*lead, H // 8, 8, (W + pad) // STRIP, STRIP)
    return jnp.swapaxes(x, -3, -2)


def _from_strips(s, W: int):
    """Inverse of :func:`_to_strips` (drops the lane padding)."""
    *lead, bh, bw, _, _ = s.shape
    return jnp.swapaxes(s, -3, -2).reshape(*lead, bh * 8, bw * STRIP)[..., :W]


def ycbcr_polynomials(r, g, b):
    """The single copy of the level-shifted JPEG YCbCr polynomials.

    Every consumer — the Pallas kernel bodies (``rgb2ycbcr_pallas``,
    ``jpeg_transform_pallas``) and this module's oracle — must call this
    instead of restating the expressions: the batched/per-tile byte-identity
    contract needs bit-identical floats, and a reassociated term in one
    copy can drift the last ULP and flip a round-at-half quantization.
    """
    y = 0.299 * r + 0.587 * g + 0.114 * b - 128.0
    cb = -0.168736 * r - 0.331264 * g + 0.5 * b
    cr = 0.5 * r - 0.418688 * g - 0.081312 * b
    return y, cb, cr


def ycbcr_inverse_polynomials(y, cb, cr):
    """The single copy of the inverse (level-unshifted) YCbCr→RGB polynomials.

    The exact mirror of :func:`ycbcr_polynomials` and under the same
    contract: the Pallas inverse kernel body and the jnp oracle must call
    this one copy, because the batched/per-tile **decoder** pixel-identity
    contract (``decode_tiles_batch`` ≡ ``decode_tile`` loop) needs
    bit-identical floats before the final round/clip to uint8.
    """
    y = y + 128.0
    r = y + 1.402 * cr
    g = y - 0.344136 * cb - 0.714136 * cr
    b = y + 1.772 * cb
    return r, g, b


def rgb2ycbcr_ref(img):
    """BT.601 full-range RGB→YCbCr with JPEG level shift on Y only after
    shift convention: returns float32 planes in [-128, 127].

    img: (3, H, W) uint8/float  →  (3, H, W) float32 (Y, Cb, Cr), level-shifted
    (Y−128, Cb−128→centered, Cr centered).
    """
    r, g, b = (img[i].astype(jnp.float32) for i in range(3))
    return jnp.stack(list(ycbcr_polynomials(r, g, b)))


def downsample2x2_ref(img):
    """2×2 box filter, stride 2. img: (C, H, W) → (C, H//2, W//2) float32.

    Row pairs first, then column pairs: the association order of the
    kernel's two pooling matmuls (its 0.25 weights scale exactly).
    """
    x = img.astype(jnp.float32)
    C, H, W = x.shape
    x = x[:, : H - H % 2, : W - W % 2]
    rows = x[:, 0::2] + x[:, 1::2]
    return 0.25 * (rows[:, :, 0::2] + rows[:, :, 1::2])


def dct8x8_quant_ref(plane, qtable):
    """Blockwise 8×8 DCT-II + quantization (round(X̂/Q)).

    plane: (…, H, W) float32 level-shifted; qtable: (8, 8).
    Returns int32 coefficients, same layout (blocks in place).
    """
    W = plane.shape[-1]
    left, right = strip_dct_matrices()
    y = strip_transform(_to_strips(plane.astype(jnp.float32)), left, right)
    q = jnp.round(y / qtable_strip(qtable)).astype(jnp.int32)
    return _from_strips(q, W)


def jpeg_transform_ref(tiles, qluma=None, qchroma=None):
    """Oracle for the fused whole-level JPEG transform kernel.

    tiles: (N, 3, H, W) RGB → (N, 3, H, W) int32 quantized YCbCr DCT
    coefficients (rgb2ycbcr_ref ∘ dct8x8_quant_ref per channel, batched).
    """
    qluma = JPEG_LUMA_Q if qluma is None else qluma
    qchroma = JPEG_CHROMA_Q if qchroma is None else qchroma
    ycc = jax.vmap(rgb2ycbcr_ref)(tiles)  # (N, 3, H, W) f32 level-shifted
    qs = (qluma, qchroma, qchroma)
    return jnp.stack([dct8x8_quant_ref(ycc[:, c], qs[c]) for c in range(3)],
                     axis=1)


def idct8x8_dequant_ref(coef, qtable):
    """Inverse of ``dct8x8_quant_ref``: (…, H, W) quantized coefficients →
    (…, H, W) f32 spatial samples (dequantize, then the strip iDCT)."""
    W = coef.shape[-1]
    left, right = strip_dct_matrices(inverse=True)
    x = _to_strips(coef.astype(jnp.float32)) * qtable_strip(qtable)
    return _from_strips(strip_transform(x, left, right), W)


def jpeg_inverse_ref(coef, qluma=None, qchroma=None):
    """Oracle for the fused whole-level inverse JPEG transform kernel.

    coef: (N, 3, H, W) int quantized YCbCr DCT coefficients (blocks in
    place) → (N, 3, H, W) uint8 RGB (dequant + strip iDCT per channel +
    ycbcr_inverse_polynomials + round/clip, batched) — the inverse of
    :func:`jpeg_transform_ref` up to quantization loss.
    """
    qluma = JPEG_LUMA_Q if qluma is None else qluma
    qchroma = JPEG_CHROMA_Q if qchroma is None else qchroma
    qs = (qluma, qchroma, qchroma)
    planes = [idct8x8_dequant_ref(coef[:, c], qs[c]) for c in range(3)]
    r, g, b = ycbcr_inverse_polynomials(*planes)
    rgb = jnp.stack([r, g, b], axis=1)
    return jnp.clip(jnp.round(rgb), 0, 255).astype(jnp.uint8)
