"""Public wrappers over the Pallas kernels: impl dispatch, batch sharding,
size-bucketed jit.

``impl`` selects the implementation per call; the platform
(``jax.default_backend()``) decides what it means:

- ``"auto"`` (default) — on a TPU, the Pallas kernel compiled natively;
  on the CPU, the pure-jnp oracle. Shapes the kernel's blocks do not tile
  run the kernel through an explicit pad-to-aligned + slice path, so on a
  TPU no ``auto`` call ends in the oracle.
- ``"ref"`` — the pure-jnp oracle, unconditionally.
- ``"pallas"`` — the Pallas kernel: native on a TPU, ``interpret=True``
  on the CPU (the kernel body executes op by op, validating the BlockSpec
  tiling). Unaligned shapes take the padded path, as under ``auto``.

Interpret mode is a correctness harness for the CPU, never an execution
path on the chip, and never what ``auto`` picks: it is orders of
magnitude slower than the oracle. Keeping every ``auto`` caller on one
implementation per platform also preserves the byte-identity contract
between the batched and per-tile JPEG paths (DESIGN.md, "Bit-exactness
contract"): expression-identical float math compiled through *different*
machinery (plain XLA vs the interpreter) can differ in the last ULP and
flip a round-at-half quantization. The choice is resolved before the jit
cache, so it keys the compiled executables.

**Mesh sharding + bucketing** (DESIGN.md, "Kernel roofline & sharding"):
the whole-level batched kernels ``jpeg_transform``/``jpeg_inverse`` carry
an (N, 3, T, T) batch whose leading dimension is embarrassingly parallel —
every tile's transform is independent. Calls from op-by-op (non-traced)
code pad N up to the next power of two (so the jit cache holds a handful
of bucketed executables instead of one per level geometry — the
small-batch recompile fix), lay the batch out over the ambient mesh's
``data`` axis with ``jax.sharding.NamedSharding``, and slice the result
back; calls from inside an enclosing trace (the fused pyramid chain in
``wsi/convert.py``) keep their static shapes. Either way the kernel runs
under ``shard_map`` over the mesh: a batch that splits evenly gives each
device its own slice of tiles, one that does not runs replicated. Pad tiles are all-zero and sliced away, and the
per-tile math is batch-size independent (asserted by tests), so sharded,
bucketed and single-device dispatches all produce bit-identical tiles.
The ambient mesh defaults to ``make_local_mesh()`` over every visible
device; ``use_mesh`` scopes an explicit one.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import ref
from repro.kernels.dct8x8_quant import dct8x8_quant_pallas
from repro.kernels.downsample2x2 import downsample2x2_pallas
from repro.kernels.jpeg_inverse import jpeg_inverse_pallas
from repro.kernels.jpeg_transform import jpeg_transform_pallas
from repro.kernels.rgb2ycbcr import rgb2ycbcr_pallas

__all__ = ["rgb2ycbcr", "downsample2x2", "dct8x8_quant", "idct8x8_dequant",
           "jpeg_transform", "jpeg_inverse", "default_mesh", "use_mesh",
           "data_sharding"]


def _aligned(n: int, m: int) -> bool:
    return n % m == 0


# --------------------------------------------------------------------------
# mesh context: which devices whole-level batches are laid out over
# --------------------------------------------------------------------------
_MESH_TLS = threading.local()


def default_mesh():
    """The ambient mesh for whole-level batch sharding.

    Defaults (per thread, built lazily so importing this module never
    touches jax device state) to ``make_local_mesh()`` — every visible
    device on a ``("data",)`` axis. On the single-device CPU container
    that is a 1-device mesh and sharding degenerates to replication;
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the
    multi-device tests) or on a real slice, level batches split N ways.
    """
    mesh = getattr(_MESH_TLS, "mesh", None)
    if mesh is None:
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh()
        _MESH_TLS.mesh = mesh
    return mesh


@contextmanager
def use_mesh(mesh):
    """Scope the ambient mesh (thread-local) for batched kernel dispatch."""
    prev = getattr(_MESH_TLS, "mesh", None)
    _MESH_TLS.mesh = mesh
    try:
        yield mesh
    finally:
        _MESH_TLS.mesh = prev


def data_sharding(n: int, mesh=None) -> NamedSharding:
    """Sharding for a leading batch of ``n``: split over ``data`` when it
    divides evenly, replicated otherwise (a level batch that does not
    divide must still produce identical bytes, just without the speedup)."""
    mesh = default_mesh() if mesh is None else mesh
    ndev = int(mesh.devices.size)
    spec = P("data") if ndev > 1 and n > 0 and n % ndev == 0 else P()
    return NamedSharding(mesh, spec)


def _bucket(n: int) -> int:
    """Smallest power of two ≥ n — the jit-cache key for level batch sizes,
    so arbitrary pyramid geometries reuse a handful of executables."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _batched_call(x, core):
    """Shared batch policy for the (N, 3, T, T) kernels.

    ``core(x, mesh)`` runs the kernel over the ambient mesh. Traced
    operands (the fused pyramid chain) keep their static shape; concrete
    operands are bucket-padded to the next power of two, laid out over the
    mesh's data axis, dispatched, and sliced back. Pad tiles are zeros;
    per-tile math is batch-independent (tested), so the sliced result is
    bit-identical to the unpadded call.
    """
    mesh = default_mesh()
    if isinstance(x, jax.core.Tracer):
        return core(x, mesh)
    x = jnp.asarray(x)
    n = x.shape[0]
    if n == 0:
        return core(x, mesh)
    nb = _bucket(n)
    if nb != n:
        x = jnp.concatenate(
            [x, jnp.zeros((nb - n,) + x.shape[1:], x.dtype)])
    x = jax.device_put(x, data_sharding(nb, mesh))
    out = core(x, mesh)
    return out[:n] if nb != n else out


def _on_mesh(run, mesh, spec, x, *consts):
    """``run(x, *consts)`` under ``shard_map``: ``x`` laid out by ``spec``
    over ``mesh``, so each device runs the kernel on its own slice; the
    constants are replicated. A Mosaic kernel in a program that spans
    several devices compiles only inside ``shard_map``."""
    return jax.shard_map(
        run, mesh=mesh, in_specs=(spec,) + (P(),) * len(consts),
        out_specs=spec, check_vma=False)(x, *consts)


def _mode(impl: str) -> str:
    """Resolve the public ``impl`` choice against the platform (module
    docstring): ``"ref"``, ``"native"`` or ``"interpret"``."""
    if impl not in ("auto", "ref", "pallas"):
        raise ValueError(f"impl must be 'auto', 'ref' or 'pallas': {impl!r}")
    if impl == "ref":
        return "ref"
    if jax.default_backend() == "tpu":
        return "native"
    return "ref" if impl == "auto" else "interpret"


def _dispatch(mode: str, aligned: bool, pallas_fn, ref_fn, padded_fn):
    """Run the oracle, or the kernel on aligned / padded operands."""
    if mode == "ref":
        return ref_fn()
    return (pallas_fn if aligned else padded_fn)(
        interpret=mode == "interpret")


def _pad_hw(x, mh: int, mw: int):
    """Zero-pad the two trailing axes up to (mh, mw) multiples."""
    H, W = x.shape[-2], x.shape[-1]
    ph, pw = -H % mh, -W % mw
    cfg = [(0, 0)] * (x.ndim - 2) + [(0, ph), (0, pw)]
    return jnp.pad(x, cfg)


def rgb2ycbcr(img, impl: str = "auto"):
    """(3, H, W) → (3, H, W) f32 level-shifted YCbCr."""
    return _rgb2ycbcr(img, _mode(impl))


@partial(jax.jit, static_argnames=("mode",))
def _rgb2ycbcr(img, mode: str):
    H, W = img.shape[1], img.shape[2]
    return _dispatch(
        mode, _aligned(H, 8) and _aligned(W, 128),
        partial(rgb2ycbcr_pallas, img),
        lambda: ref.rgb2ycbcr_ref(img),
        # elementwise → padding is invisible to the retained region
        lambda **kw: rgb2ycbcr_pallas(_pad_hw(img, 8, 128),
                                      **kw)[:, :H, :W])


def downsample2x2(img, impl: str = "auto"):
    """(C, H, W) → (C, H//2, W//2) f32 box-filtered, rows split over the
    ambient mesh's data axis."""
    return _downsample2x2(img, _mode(impl), default_mesh())


@partial(jax.jit, static_argnames=("mode", "mesh"))
def _downsample2x2(img, mode: str, mesh):
    def run(x):
        H, W = x.shape[1], x.shape[2]
        return _dispatch(
            mode, _aligned(H, 16) and _aligned(W, 256),
            partial(downsample2x2_pallas, x),
            lambda: ref.downsample2x2_ref(x),
            # 2×2 boxes are independent; the pad only fills boxes sliced away
            lambda **kw: downsample2x2_pallas(_pad_hw(x, 16, 256),
                                              **kw)[:, :H // 2, :W // 2])
    # row bands of even height keep every 2×2 box on one device
    even = img.shape[1] % (2 * mesh.devices.size) == 0
    return _on_mesh(run, mesh, P(None, "data") if even else P(), img)


def dct8x8_quant(plane, qtable, impl: str = "auto"):
    """(H, W) f32 → (H, W) i32 quantized DCT coefficients."""
    return _dct8x8_quant(plane, qtable, _mode(impl))


@partial(jax.jit, static_argnames=("mode",))
def _dct8x8_quant(plane, qtable, mode: str):
    H, W = plane.shape
    return _dispatch(
        mode, _aligned(H, 8) and _aligned(W, 128),
        partial(dct8x8_quant_pallas, plane, qtable),
        lambda: ref.dct8x8_quant_ref(plane, qtable),
        # 8×8 blocks are independent; padding adds all-zero blocks only
        lambda **kw: dct8x8_quant_pallas(_pad_hw(plane, 8, 128), qtable,
                                         **kw)[:H, :W])


@partial(jax.jit, static_argnames=("mode", "mesh"))
def _jpeg_transform_core(tiles, qluma, qchroma, mode: str, mesh):
    def run(x, ql, qc):
        H, W = x.shape[2], x.shape[3]
        return _dispatch(
            mode, _aligned(H, 8) and _aligned(W, 128),
            partial(jpeg_transform_pallas, x, ql, qc),
            lambda: ref.jpeg_transform_ref(x, ql, qc),
            lambda **kw: jpeg_transform_pallas(_pad_hw(x, 8, 128), ql, qc,
                                               **kw)[:, :, :H, :W])
    return _on_mesh(run, mesh, data_sharding(tiles.shape[0], mesh).spec,
                    tiles, qluma, qchroma)


@partial(jax.jit, static_argnames=("mode", "mesh"))
def _jpeg_inverse_core(coef, qluma, qchroma, mode: str, mesh):
    def run(x, ql, qc):
        H, W = x.shape[2], x.shape[3]
        return _dispatch(
            mode, _aligned(H, 8) and _aligned(W, 128),
            lambda **kw: jpeg_inverse_pallas(
                x, ql, qc, **kw).astype(jnp.uint8),
            lambda: ref.jpeg_inverse_ref(x, ql, qc),
            lambda **kw: jpeg_inverse_pallas(
                _pad_hw(x, 8, 128), ql, qc,
                **kw).astype(jnp.uint8)[:, :, :H, :W])
    return _on_mesh(run, mesh, data_sharding(coef.shape[0], mesh).spec,
                    coef, qluma, qchroma)


def jpeg_transform(tiles, qluma=None, qchroma=None, impl: str = "auto"):
    """(N, 3, T, T) RGB tiles → (N, 3, T, T) i32 quantized YCbCr DCT coefs.

    The whole-level batched dispatch: one kernel launch transform-codes
    every tile of a pyramid level (fused rgb2ycbcr + per-channel
    dct8x8_quant). The batch dimension is bucket-padded to a power of two
    and laid out over the ambient mesh's ``data`` axis (see module
    docstring) — bit-identical to the unsharded, unpadded call.
    """
    mode = _mode(impl)
    qluma = ref.JPEG_LUMA_Q if qluma is None else qluma
    qchroma = ref.JPEG_CHROMA_Q if qchroma is None else qchroma
    return _batched_call(
        tiles, lambda x, mesh: _jpeg_transform_core(x, qluma, qchroma,
                                                   mode, mesh))


def jpeg_inverse(coef, qluma=None, qchroma=None, impl: str = "auto"):
    """(N, 3, T, T) i32 quantized YCbCr DCT coefs → (N, 3, T, T) u8 RGB.

    The whole-level batched inverse dispatch: one kernel launch
    decode-transforms every tile of a stored pyramid level (fused dequant +
    per-channel iDCT + YCbCr→RGB + round/clip) — the device half of the
    export path's JPEG decoder. Bucketed and mesh-sharded exactly like
    :func:`jpeg_transform`.
    """
    mode = _mode(impl)
    qluma = ref.JPEG_LUMA_Q if qluma is None else qluma
    qchroma = ref.JPEG_CHROMA_Q if qchroma is None else qchroma
    return _batched_call(
        coef, lambda x, mesh: _jpeg_inverse_core(x, qluma, qchroma,
                                                mode, mesh))


@jax.jit
def idct8x8_dequant(coef, qtable):
    """Decoder-side inverse of ``dct8x8_quant`` (jnp only; a test reference)."""
    return ref.idct8x8_dequant_ref(coef, qtable)
