"""Mesh-sharded level batches + padded kernel dispatch.

Four layers:

- the platform dispatch policy: on the CPU ``auto`` is the oracle and
  ``pallas`` is interpret mode; with the backend steered to ``tpu``, both
  pick the native kernel, on padded operands where the shape is ragged;
- mesh context (``ops.default_mesh`` / ``use_mesh`` / ``data_sharding``)
  and the pow2 bucket padding of the batched kernel wrappers (byte-exact
  vs unpadded, N=0 passthrough);
- the explicit padded-alignment path: ``impl="pallas"`` on ragged
  (non-lane-aligned) shapes runs the kernel through pad-to-aligned +
  slice and must match the oracle exactly;
- multi-device subprocesses (``--xla_force_host_platform_device_count=4``,
  the pattern from test_sharding_roofline.py): sharded kernel dispatch is
  bit-exact vs a single-device mesh, and the full convert→store→export
  circle emits byte-identical artifacts under a 4-device data mesh —
  asserted both inside the subprocess (4-dev vs 1-dev mesh) and across
  processes (vs this interpreter's single-device run).
"""
import hashlib
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax.sharding import PartitionSpec as P

from repro.kernels import ops, ref
from repro.kernels.ops import (data_sharding, default_mesh, jpeg_inverse,
                               jpeg_transform, use_mesh)

SRC = str(Path(__file__).resolve().parents[1] / "src")
RNG = np.random.default_rng(7)

UIDS = json.dumps(["1.2.826.0.1.3680043.2.1", "1.2.826.0.1.3680043.2.2"])


# --------------------------------------------------------------------------
# dispatch policy: what each impl runs on each platform
# --------------------------------------------------------------------------
#: kernel → (call, aligned shape, ragged shape, input dtype)
KERNEL_CALLS = {
    "rgb2ycbcr": (ops.rgb2ycbcr, (3, 16, 256), (3, 20, 100), jnp.float32),
    "downsample2x2": (ops.downsample2x2, (3, 16, 256), (3, 20, 100),
                      jnp.float32),
    "dct8x8_quant": (lambda x, impl: ops.dct8x8_quant(
        x, ref.JPEG_LUMA_Q, impl=impl), (16, 256), (24, 72), jnp.float32),
    "jpeg_transform": (jpeg_transform, (2, 3, 16, 128), (2, 3, 24, 72),
                       jnp.float32),
    "jpeg_inverse": (jpeg_inverse, (2, 3, 16, 128), (2, 3, 24, 72),
                     jnp.int32),
}


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested jaxprs (jit, shard_map) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)


def _traced(name: str, impl: str, shape) -> list:
    call, _, _, dtype = KERNEL_CALLS[name]
    x = jnp.zeros(shape, dtype)
    return list(_eqns(jax.make_jaxpr(
        lambda a: call(a, impl=impl))(x).jaxpr))


def _kernel_calls(eqns) -> list:
    return [e for e in eqns if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("name", sorted(KERNEL_CALLS))
def test_cpu_auto_is_oracle_and_pallas_interprets(name):
    aligned = KERNEL_CALLS[name][1]
    assert jax.default_backend() == "cpu"
    assert ops._mode("auto") == "ref" and ops._mode("pallas") == "interpret"
    assert not _kernel_calls(_traced(name, "auto", aligned))
    calls = _kernel_calls(_traced(name, "pallas", aligned))
    assert calls and all(e.params["interpret"] for e in calls)


@pytest.mark.parametrize("name", sorted(KERNEL_CALLS))
@pytest.mark.parametrize("impl", ["auto", "pallas"])
@pytest.mark.parametrize("ragged", [False, True])
def test_tpu_runs_native_kernel(monkeypatch, name, impl, ragged):
    """On a TPU no call ends in the oracle or in interpret mode; shapes the
    blocks do not tile run the kernel on zero-padded operands."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, aligned, unaligned, _ = KERNEL_CALLS[name]
    shape = unaligned if ragged else aligned
    eqns = _traced(name, impl, shape)
    calls = _kernel_calls(eqns)
    assert len(calls) == 1
    assert not calls[0].params["interpret"]
    operand = calls[0].invars[0].aval.shape
    if ragged:
        assert any(e.primitive.name == "pad" for e in eqns)
        assert operand[-1] % 128 == 0 and operand[-1] > shape[-1]
    else:
        assert operand == shape


# --------------------------------------------------------------------------
# mesh context + bucket padding (single device, in-process)
# --------------------------------------------------------------------------
def test_default_mesh_has_data_axis():
    mesh = default_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == len(jax.devices())


def test_use_mesh_scopes_and_restores():
    outer = default_mesh()
    other = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    with use_mesh(other) as m:
        assert m is other
        assert default_mesh() is other
    assert default_mesh() is outer


def test_data_sharding_replicates_when_indivisible():
    mesh = default_mesh()
    ndev = mesh.devices.size
    # single device, zero batch, or a batch the mesh can't split evenly
    assert data_sharding(0).spec == P()
    if ndev == 1:
        assert data_sharding(8).spec == P()
    else:
        assert data_sharding(ndev).spec == P("data")
        assert data_sharding(ndev + 1).spec == P()


@pytest.mark.parametrize("n", [1, 3, 5, 7])
def test_bucket_padding_is_byte_exact(n):
    """Odd batch sizes ride a pow2 bucket; pad tiles must not leak."""
    tiles = jnp.asarray(RNG.integers(0, 256, size=(n, 3, 16, 128)),
                        jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(jpeg_transform(tiles)),
        np.asarray(ref.jpeg_transform_ref(
            tiles, jnp.asarray(ref.JPEG_LUMA_Q),
            jnp.asarray(ref.JPEG_CHROMA_Q))))
    coef = jpeg_transform(tiles)
    np.testing.assert_array_equal(
        np.asarray(jpeg_inverse(coef)),
        np.asarray(ref.jpeg_inverse_ref(coef)))


def test_zero_batch_passthrough():
    empty = jnp.zeros((0, 3, 256, 256), jnp.float32)
    assert jpeg_transform(empty).shape == (0, 3, 256, 256)
    assert jpeg_inverse(jnp.zeros((0, 3, 256, 256), jnp.int32)).shape \
        == (0, 3, 256, 256)


def test_bucket_reuses_jit_cache():
    """5 and 7 tiles both pad to the 8 bucket — no second trace."""
    x8 = jnp.asarray(RNG.integers(0, 256, size=(8, 3, 16, 128)), jnp.float32)
    jpeg_transform(x8)  # warm the 8 bucket
    before = ops._jpeg_transform_core._cache_size()
    jpeg_transform(x8[:5])
    jpeg_transform(x8[:7])
    assert ops._jpeg_transform_core._cache_size() == before


# --------------------------------------------------------------------------
# explicit padded-alignment path: pallas ≡ ref on ragged shapes
# --------------------------------------------------------------------------
def test_rgb2ycbcr_padded_pallas_matches_ref():
    img = jnp.asarray(RNG.integers(0, 256, size=(3, 20, 100)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.rgb2ycbcr(img, impl="pallas")),
        np.asarray(ref.rgb2ycbcr_ref(img)), atol=1e-3, rtol=1e-5)


def test_downsample_padded_pallas_matches_ref():
    img = jnp.asarray(RNG.normal(0, 50, size=(3, 20, 100)), jnp.float32)
    out = ops.downsample2x2(img, impl="pallas")
    assert out.shape == (3, 10, 50)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.downsample2x2_ref(img)),
        atol=1e-4, rtol=1e-5)


def test_dct_quant_padded_pallas_matches_ref():
    q = jnp.asarray(ref.JPEG_LUMA_Q)
    plane = jnp.asarray(RNG.normal(0, 40, size=(24, 72)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.dct8x8_quant(plane, q, impl="pallas")),
        np.asarray(ref.dct8x8_quant_ref(plane, q)))


def test_jpeg_transform_padded_pallas_matches_ref():
    tiles = jnp.asarray(RNG.integers(0, 256, size=(2, 3, 24, 72)),
                        jnp.float32)
    ql = jnp.asarray(ref.JPEG_LUMA_Q)
    qc = jnp.asarray(ref.JPEG_CHROMA_Q)
    np.testing.assert_array_equal(
        np.asarray(jpeg_transform(tiles, impl="pallas")),
        np.asarray(ref.jpeg_transform_ref(tiles, ql, qc)))


def test_jpeg_inverse_padded_pallas_matches_ref():
    coef = jnp.asarray(RNG.integers(-64, 64, size=(2, 3, 24, 72)),
                       jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(jpeg_inverse(coef, impl="pallas")),
        np.asarray(ref.jpeg_inverse_ref(coef)))


# --------------------------------------------------------------------------
# multi-device subprocesses
# --------------------------------------------------------------------------
def _run(prog: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=600)


def test_sharded_kernels_bit_exact_multidevice_subprocess():
    """4-way data-sharded jpeg_transform/jpeg_inverse ≡ 1-device mesh."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np, sys
        sys.path.insert(0, %r)
        from repro.kernels.ops import (default_mesh, jpeg_inverse,
                                       jpeg_transform, use_mesh)
        assert default_mesh().devices.size == 4
        rng = np.random.default_rng(0)
        tiles = jnp.asarray(rng.integers(0, 256, size=(8, 3, 16, 128)),
                            jnp.float32)
        coef4 = jpeg_transform(tiles)          # 4-way data mesh
        rgb4 = jpeg_inverse(coef4)
        mesh1 = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        with use_mesh(mesh1):                  # single-device mesh
            coef1 = jpeg_transform(tiles)
            rgb1 = jpeg_inverse(coef1)
        assert (np.asarray(coef4) == np.asarray(coef1)).all()
        assert (np.asarray(rgb4) == np.asarray(rgb1)).all()
        # odd batch: replicated (5 %% 4 != 0) but still exact
        coef_odd = jpeg_transform(tiles[:5])
        assert (np.asarray(coef_odd) == np.asarray(coef1)[:5]).all()
        print("SHARDED-KERNELS-OK")
    """) % SRC
    out = _run(prog)
    assert "SHARDED-KERNELS-OK" in out.stdout, out.stderr[-2000:]


def test_level0_upload_lands_in_row_bands_multidevice_subprocess():
    """Level 0 goes to the mesh one band of tile rows per device (or whole
    to every device when the rows do not divide), never all to device 0;
    the assembled array equals the scanner's pixels."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np, sys
        sys.path.insert(0, %r)
        from jax.sharding import PartitionSpec as P
        from repro.kernels.ops import default_mesh
        from repro.wsi.convert import _upload_level0
        from repro.wsi.formats import open_slide
        from repro.wsi.slide import SyntheticScanner

        mesh = default_mesh()
        for rows, spec in ((1024, P(None, "data")), (768, P())):
            rd = open_slide(SyntheticScanner(seed=3).scan(rows, 512, 256))
            dev = _upload_level0(rd, mesh)
            assert dev.sharding.spec == spec, dev.sharding
            shards = dev.addressable_shards
            assert {s.device for s in shards} == set(mesh.devices.flat)
            want = np.concatenate(
                [np.concatenate([rd.read_tile(r, c) for c in range(2)], 1)
                 for r in range(rows // 256)]).transpose(2, 0, 1)
            assert (np.asarray(dev) == want).all()
            band = rows // 4 if spec else rows
            assert all(s.data.shape == (3, band, 512) for s in shards)
        print("LEVEL0-BANDS-OK")
    """) % SRC
    out = _run(prog)
    assert "LEVEL0-BANDS-OK" in out.stdout, out.stderr[-2000:]


def _single_device_circle() -> tuple[str, str]:
    """This interpreter's (1 CPU device) study tar + export digests."""
    from repro.core import SimScheduler
    from repro.core.storage import ObjectStore
    from repro.wsi.convert import ConvertOptions, convert_wsi_to_dicom
    from repro.wsi.export import ExportService
    from repro.wsi.slide import SyntheticScanner
    from repro.wsi.store_service import DicomStoreService

    psv = SyntheticScanner(seed=11).scan(512, 512, 256)
    tar = convert_wsi_to_dicom(psv, {"slide_id": "mesh"},
                               options=ConvertOptions(
                                   manifest={"uids": UIDS}))
    sched = SimScheduler()
    store = ObjectStore(sched)
    svc = DicomStoreService(store.bucket("dicom"), sched)
    svc.store_study_archive("studies/mesh.tar", tar)
    (study,) = svc.search_studies()
    exporter = ExportService(svc, store.bucket("derived"))
    keys = exporter.export_study(study)
    tifs = b"".join(exporter.derived.get(k).data for k in sorted(keys))
    return (hashlib.sha256(tar).hexdigest(),
            hashlib.sha256(tifs).hexdigest())


def test_convert_store_export_circle_multidevice_subprocess():
    """The full circle under a 4-device data mesh emits byte-identical
    artifacts — compared against a 1-device mesh in the same subprocess
    AND against this interpreter's single-device run."""
    tar_sha, tif_sha = _single_device_circle()
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import hashlib, json, sys
        import numpy as np
        sys.path.insert(0, %r)
        import jax
        from repro.core import SimScheduler
        from repro.core.storage import ObjectStore
        from repro.wsi.convert import ConvertOptions, convert_wsi_to_dicom
        from repro.wsi.export import ExportService
        from repro.wsi.slide import SyntheticScanner
        from repro.wsi.store_service import DicomStoreService
        from repro.kernels.ops import default_mesh

        UIDS = %r
        assert default_mesh().devices.size == 4
        psv = SyntheticScanner(seed=11).scan(512, 512, 256)

        def circle(mesh):
            tar = convert_wsi_to_dicom(
                psv, {"slide_id": "mesh"},
                options=ConvertOptions(manifest={"uids": UIDS}, mesh=mesh))
            sched = SimScheduler()
            store = ObjectStore(sched)
            svc = DicomStoreService(store.bucket("dicom"), sched)
            svc.store_study_archive("studies/mesh.tar", tar)
            (study,) = svc.search_studies()
            exporter = ExportService(svc, store.bucket("derived"),
                                     mesh=mesh)
            keys = exporter.export_study(study)
            tifs = b"".join(exporter.derived.get(k).data
                            for k in sorted(keys))
            return (hashlib.sha256(tar).hexdigest(),
                    hashlib.sha256(tifs).hexdigest())

        four = circle(None)   # ambient mesh: all 4 devices
        mesh1 = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        one = circle(mesh1)
        assert four == one, (four, one)
        print("CIRCLE-SHA", four[0], four[1])
    """) % (SRC, UIDS)
    out = _run(prog)
    line = next((ln for ln in out.stdout.splitlines()
                 if ln.startswith("CIRCLE-SHA")), None)
    assert line is not None, out.stderr[-2000:]
    _, got_tar, got_tif = line.split()
    assert got_tar == tar_sha, "4-device study tar diverges from 1-device"
    assert got_tif == tif_sha, "4-device export TIFFs diverge from 1-device"
