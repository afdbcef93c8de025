"""Kernel roofline benchmark: achieved-vs-peak terms per device count.

Three sections, written to ``BENCH_kernels.json``:

- **roofline** — for each batched conversion kernel (``jpeg_transform``,
  ``jpeg_inverse``, ``downsample2x2``) and each device count D, a fresh
  interpreter (``XLA_FLAGS=--xla_force_host_platform_device_count=D``)
  lowers the jitted kernel with its level batch laid out over a
  ``make_local_mesh()`` data axis, runs the loop-aware HLO analysis
  (``roofline.analyze_hlo``) on the SPMD-partitioned program, and the
  parent derives the three roofline terms against the TPU-v5e targets
  (``roofline.derive_terms``): compute vs memory vs collective bound,
  useful-FLOPs ratio (analytic kernel math ÷ compiled FLOPs), and the MFU
  bound. On this CPU container the HLO is the jnp oracle path — the same
  math the Pallas kernels implement — so the terms describe the *program*,
  not interpret-mode overhead. (``analyze_hlo`` counts dot FLOPs only, so
  ``useful_flops_ratio`` can exceed 1 on these elementwise-heavy kernels —
  the analytic model includes the color-transform and quant arithmetic the
  dot counter does not see.)
- **measured** — single-device wall time per kernel on the same batch, with
  achieved GFLOP/s (analytic FLOPs ÷ wall) and the achieved fraction of
  the memory-bound roofline time. CPU-proxy numbers; the gap to peak is
  the point of recording them.
- **batch_scaling** — per-tile µs of the fused transform/inverse dispatch
  at growing batch sizes. **Gates** (run in ``make smoke``): per-tile cost
  must stay flat across batch sizes (≤3× the cheapest point; a recompile
  cliff is ~100×), and odd batch sizes must ride already-compiled pow2
  buckets instead of tracing new kernel executables (asserted on the jit
  cache itself) — the size-bucketed jit means a 16-tile level never pays
  a compile a 256-tile level doesn't.
  (The decode-path twin — batched speedup >1x at every batch size — lives
  in ``export_bench.py``.)

The end-to-end tile-encode row opens the slide through the ``formats``
registry (``open_slide``), exercising the same container sniffing as the
pipeline.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import jpeg_inverse, jpeg_transform, ref
from repro.launch.cache import enable_compile_cache
from repro.roofline import derive_terms
from repro.roofline.terms import HW
from repro.wsi.jpeg import encode_tile
from repro.wsi.slide import SyntheticScanner
from repro.wsi.formats import open_slide

SRC = str(Path(__file__).resolve().parents[1] / "src")

TILE = 256
ROOFLINE_N = 64  # tiles per level batch in the roofline lowering

KERNELS = ("jpeg_transform", "jpeg_inverse", "downsample2x2")


def model_flops(kernel: str, n: int, tile: int) -> float:
    """Analytic useful math per kernel call (the roofline numerator).

    Counts only the kernel's defining arithmetic, not compiled overhead:

    - color transform: 3 outputs × (3 mul + 3 add) per pixel;
    - 8×8 DCT (or iDCT): two 8×8×8 matmuls per block = 2·(2·8³) flops per
      64 pixels = 64 flops/pixel, plus ~2 flops/pixel (de)quant + round,
      per channel;
    - 2×2 box filter: 3 add + 1 mul per output pixel per channel.
    """
    px = n * tile * tile
    if kernel in ("jpeg_transform", "jpeg_inverse"):
        return px * (18 + 3 * (64 + 2))
    if kernel == "downsample2x2":
        return 3 * (px / 4) * 4
    raise ValueError(kernel)


def _roofline_prog(device_count: int, n: int, tile: int) -> str:
    """Subprocess: lower each sharded kernel, print analyze_hlo JSON."""
    return textwrap.dedent("""
        import os, json
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=%d"
        import jax, jax.numpy as jnp, sys
        sys.path.insert(0, %r)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import jpeg_transform, jpeg_inverse, downsample2x2
        from repro.launch.mesh import make_local_mesh
        from repro.roofline import analyze_hlo

        mesh = make_local_mesh()
        n, tile = %d, %d
        out = {}
        batch = jax.ShapeDtypeStruct((n, 3, tile, tile), jnp.float32)
        coef = jax.ShapeDtypeStruct((n, 3, tile, tile), jnp.int32)
        sh = NamedSharding(mesh, P("data"))
        for name, fn, spec in [
            ("jpeg_transform", lambda x: jpeg_transform(x), (batch, sh)),
            ("jpeg_inverse", lambda x: jpeg_inverse(x), (coef, sh)),
            # no batch axis: a level plane, rows over the data axis
            ("downsample2x2",
             lambda x: downsample2x2(x),
             (jax.ShapeDtypeStruct((3, n * tile // 8, tile * 8),
                                   jnp.float32),
              NamedSharding(mesh, P(None, "data", None)))),
        ]:
            arg, sharding = spec
            c = jax.jit(fn, in_shardings=sharding).lower(arg).compile()
            r = analyze_hlo(c.as_text())
            out[name] = {"flops": r["flops"], "bytes": r["bytes"],
                         "collective_bytes": r["collective_bytes"],
                         "by_kind": r["by_kind"]}
        print("ROOFLINE-JSON " + json.dumps(out))
    """) % (device_count, SRC, n, tile)


def _roofline_section(device_counts: list[int]) -> dict:
    """Per kernel per device count: HLO totals → three-term roofline."""
    hw = HW()
    out: dict[str, dict[str, dict]] = {k: {} for k in KERNELS}
    for d in device_counts:
        prog = _roofline_prog(d, ROOFLINE_N, TILE)
        # the child analyses forced host devices: it must never reach for
        # the chip this process holds
        res = subprocess.run([sys.executable, "-c", prog],
                             capture_output=True, text=True, timeout=600,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        line = next((ln for ln in res.stdout.splitlines()
                     if ln.startswith("ROOFLINE-JSON ")), None)
        assert line is not None, \
            f"roofline subprocess (D={d}) failed:\n{res.stderr[-2000:]}"
        analyzed = json.loads(line[len("ROOFLINE-JSON "):])
        for kernel in KERNELS:
            a = analyzed[kernel]
            terms = derive_terms(
                flops_per_device=a["flops"],
                bytes_per_device=a["bytes"],
                collective_bytes_per_device=a["collective_bytes"],
                chips=d,
                model_flops_total=model_flops(kernel, ROOFLINE_N, TILE),
                hw=hw)
            terms["collective_by_kind"] = a["by_kind"]
            out[kernel][str(d)] = terms
    return out


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)  # warm/compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _measured_section(reps: int) -> dict:
    """Single-device wall time vs the analytic roofline (CPU proxy)."""
    rng = np.random.default_rng(0)
    batch = jnp.asarray(
        rng.integers(0, 255, size=(ROOFLINE_N, 3, TILE, TILE)), jnp.float32)
    coef = np.asarray(jpeg_transform(batch))
    hw = HW()
    out = {}
    for name, fn, arg in [("jpeg_transform", jpeg_transform, batch),
                          ("jpeg_inverse", jpeg_inverse,
                           jnp.asarray(coef))]:
        wall = _time(fn, arg, reps=reps)
        mf = model_flops(name, ROOFLINE_N, TILE)
        # the batch read + written once at f32/i32 = the memory floor
        floor_s = 2 * arg.nbytes / hw.hbm_bw
        out[name] = {
            "batch": list(arg.shape),
            "wall_s": wall,
            "achieved_gflops": mf / wall / 1e9,
            "peak_gflops": hw.peak_flops / 1e9,
            "achieved_vs_peak": mf / wall / hw.peak_flops,
            "memory_floor_s": floor_s,
            "achieved_vs_memory_bound": floor_s / wall,
        }
    return out


def _batch_scaling_section(ns: list[int], reps: int) -> list[dict]:
    """Per-tile dispatch cost vs batch size — the bucketed-jit gate."""
    rng = np.random.default_rng(1)
    full = jnp.asarray(rng.integers(0, 255, size=(max(ns), 3, TILE, TILE)),
                       jnp.float32)
    coef_full = jnp.asarray(np.asarray(jpeg_transform(full)))
    rows = []
    for n in ns:
        t_fwd = _time(jpeg_transform, full[:n], reps=reps)
        t_inv = _time(jpeg_inverse, coef_full[:n], reps=reps)
        rows.append({"n_tiles": n,
                     "transform_us_per_tile": t_fwd / n * 1e6,
                     "inverse_us_per_tile": t_inv / n * 1e6})
    for key in ("transform_us_per_tile", "inverse_us_per_tile"):
        floor = min(r[key] for r in rows)
        for r in rows:
            # the cliff gate: per-tile cost must stay flat across batch
            # sizes (≤3× the cheapest point — a recompile cliff is ~100×).
            # Host cache pressure on the largest batches costs ~2× on this
            # CPU proxy and stays inside the slack.
            assert r[key] <= floor * 3.0, (
                f"{key} cliff at n={r['n_tiles']}: {r[key]:.0f}us/tile vs "
                f"{floor:.0f}us/tile floor")

    # bucket-reuse gate: an odd batch size must ride an already-compiled
    # pow2 bucket, not trace a new kernel executable (the recompile cliff
    # the bucketed jit removes). Observed directly on the jit cache.
    from repro.kernels import ops
    jax.block_until_ready(jpeg_transform(full[:32]))  # warm the 32 bucket
    before = ops._jpeg_transform_core._cache_size()
    for n in (17, 19, 23, 32):
        jax.block_until_ready(jpeg_transform(full[:n]))
    after = ops._jpeg_transform_core._cache_size()
    assert after == before, (
        f"odd batch sizes traced new kernel executables: jit cache grew "
        f"{before}→{after}")
    return rows


def _micro_rows(reps: int) -> list[tuple[str, float, str]]:
    """The original per-kernel microbenchmark rows (CSV only)."""
    rng = np.random.default_rng(0)
    tile = jnp.asarray(rng.integers(0, 255, size=(3, TILE, TILE)),
                       jnp.float32)
    plane = jnp.asarray(rng.normal(0, 40, size=(TILE, TILE)), jnp.float32)
    q = jnp.asarray(ref.JPEG_LUMA_Q)
    rows = [
        ("rgb2ycbcr_ref_256",
         _time(jax.jit(ref.rgb2ycbcr_ref), tile, reps=reps) * 1e6,
         "3x256x256"),
        ("downsample_ref_256",
         _time(jax.jit(ref.downsample2x2_ref), tile, reps=reps) * 1e6,
         "3x256x256"),
        ("dct_quant_ref_256",
         _time(jax.jit(lambda p: ref.dct8x8_quant_ref(p, q)), plane,
               reps=reps) * 1e6,
         "256x256"),
    ]

    # end-to-end tile encode, slide opened through the format sniffer
    psv = SyntheticScanner(seed=0).scan(TILE, TILE, TILE)
    t = open_slide(psv).read_tile(0, 0)
    encode_tile(t)  # warm
    t0 = time.perf_counter()
    n = 4
    for _ in range(n):
        jpg = encode_tile(t)
    dt = (time.perf_counter() - t0) / n
    rows.append(("jpeg_encode_tile_256", dt * 1e6,
                 f"{0.256*0.256/dt:.2f}MPix/s ratio={len(jpg)/t.nbytes:.3f}"))
    return rows


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: fewer device counts / batch sizes, "
                         "same monotonicity gate")
    args = ap.parse_args(argv)
    enable_compile_cache()
    device_counts = [1, 4] if args.fast else [1, 4, 8]
    scaling_ns = [16, 64] if args.fast else [16, 64, 256]
    reps = 2 if args.fast else 3

    roofline = _roofline_section(device_counts)
    measured = _measured_section(reps)
    scaling = _batch_scaling_section(scaling_ns, reps)
    result = {
        "hw": HW().__dict__,
        "roofline_batch": {"n_tiles": ROOFLINE_N, "tile": TILE},
        "roofline": roofline,
        "measured": measured,
        "batch_scaling": scaling,
    }
    with open("BENCH_kernels.json", "w") as f:
        json.dump(result, f, indent=2)

    print("name,value,derived")
    for kernel in KERNELS:
        for d, t in roofline[kernel].items():
            print(f"roofline_{kernel}_d{d},{t['bound_s']*1e6:.1f}us,"
                  f"bound={t['dominant'].removesuffix('_s')} "
                  f"useful={t['useful_flops_ratio']:.2f} "
                  f"mfu_bound={t['mfu_bound']:.3f}")
    for name, m in measured.items():
        print(f"measured_{name},{m['wall_s']*1e3:.1f}ms,"
              f"{m['achieved_gflops']:.2f}GFLOP/s "
              f"vs_peak={m['achieved_vs_peak']:.2e} "
              f"vs_membound={m['achieved_vs_memory_bound']:.2e}")
    for s in scaling:
        print(f"batch_scaling_n{s['n_tiles']},"
              f"{s['transform_us_per_tile']:.0f}us/tile,"
              f"inverse={s['inverse_us_per_tile']:.0f}us/tile")
    for name, us, derived in _micro_rows(reps):
        print(f"{name},{us:.0f},{derived}")
    print("wrote BENCH_kernels.json")


if __name__ == "__main__":
    main()
