"""Sharding policy + HLO roofline analysis (single- and multi-device)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


# --------------------------------------------------------------------------
# spec_for policy (pure logic — fake mesh via a stub)
# --------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np
        self.devices = _np.empty(tuple(sizes.values()))


def _spec(shape, logical, sizes):
    from repro import sharding as shd
    return tuple(shd.spec_for(shape, logical, _FakeMesh(sizes)))


def test_batch_claims_pod_and_data():
    assert _spec((256, 4096), ("batch", "seq"),
                 {"pod": 2, "data": 16, "model": 16}) \
        == (("pod", "data"), "model")


def test_heads_fallback_when_indivisible():
    # gemma: 8 q heads on a 16-way model axis → seq takes the model axis
    spec = _spec((32, 4096, 8, 256), ("batch", "seq", "heads", "head_dim"),
                 {"data": 16, "model": 16})
    assert spec == ("data", "model")  # batch→data, seq→model, heads/dim open


def test_indivisible_batch_stays_replicated():
    spec = _spec((2, 4096, 8, 256), ("batch", "seq", "heads", "head_dim"),
                 {"data": 16, "model": 16})
    assert spec == (None, "model")


def test_heads_claim_model_when_divisible():
    spec = _spec((32, 4096, 32, 128), ("batch", "seq", "heads", "head_dim"),
                 {"data": 16, "model": 16})
    assert spec[2] == "model"


def test_weights_get_2d_fsdp_tp():
    spec = _spec((4096, 16384), ("embed", "mlp"), {"data": 16, "model": 16})
    assert spec == ("data", "model")


def test_each_mesh_axis_claimed_once():
    spec = _spec((4096, 4096), ("embed", "embed"), {"data": 16, "model": 16})
    assert tuple(spec) in ((("data",), ()), ("data",), ("data", None))


def test_constrain_rank_mismatch_raises():
    from repro import sharding as shd
    from repro.launch.mesh import make_local_mesh
    with shd.set_mesh(make_local_mesh()):
        with pytest.raises(ValueError):
            shd.constrain(np.zeros((2, 2)), "batch")


# --------------------------------------------------------------------------
# HLO analysis
# --------------------------------------------------------------------------
def test_shape_bytes_parsing():
    from repro.roofline import shape_bytes
    assert shape_bytes("bf16[16,128]{1,0}") == 16 * 128 * 2
    assert shape_bytes("(f32[8,8]{1,0}, s32[4]{0})") == 8 * 8 * 4 + 16
    assert shape_bytes("pred[]") == 1


def test_dot_flops_counted_loop_aware():
    """A scanned matmul must count trip × per-iteration flops."""
    import jax.numpy as jnp
    from repro.roofline import analyze_hlo

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=12)
        return out

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    r = analyze_hlo(hlo)
    expect = 2 * 32 * 64 * 64 * 12
    assert r["flops"] >= expect * 0.99, (r["flops"], expect)
    assert r["flops"] <= expect * 1.5
    assert any(t == 12 for _, t in r["loops"])


def test_collectives_counted_in_multidevice_subprocess():
    """Spawn a fresh interpreter with 8 fake devices; verify all-reduce bytes."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import sys
        sys.path.insert(0, %r)
        from repro.roofline import analyze_hlo
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        xs = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        ws = jax.ShapeDtypeStruct((256, 128), jnp.float32)
        f = lambda x, w: jnp.sum(x @ w)
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                                     NamedSharding(mesh, P(None, None)))
                    ).lower(xs, ws).compile()
        r = analyze_hlo(c.as_text())
        assert r["collective_bytes"] > 0, r
        assert "all-reduce" in r["by_kind"], r
        print("COLLECTIVES-OK", r["by_kind"])
    """) % SRC
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300)
    assert "COLLECTIVES-OK" in out.stdout, out.stderr[-2000:]


def test_compressed_psum_multidevice_subprocess():
    """int8 psum under shard_map across 8 fake devices ≈ exact psum."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        import sys
        sys.path.insert(0, %r)
        from repro.comms.compress import compressed_psum
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 128)),
                        jnp.float32)
        from jax.experimental.shard_map import shard_map
        f = shard_map(lambda v: compressed_psum(v[0], "data"),
                      mesh=mesh, in_specs=P("data", None), out_specs=P())
        approx = f(x)
        exact = x.sum(0)
        err = float(jnp.abs(approx - exact).max())
        scale = float(jnp.abs(exact).max())
        assert err < 0.1 * scale + 0.2, (err, scale)
        print("PSUM-OK", err)
    """) % SRC
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300)
    assert "PSUM-OK" in out.stdout, out.stderr[-2000:]


def test_derive_terms_dominance():
    from repro.roofline import derive_terms
    r = derive_terms(flops_per_device=197e12, bytes_per_device=1e9,
                     collective_bytes_per_device=0, chips=256,
                     model_flops_total=197e12 * 256 * 0.5)
    assert r["dominant"] == "compute_s"
    assert abs(r["mfu_bound"] - 0.5) < 1e-6
    r2 = derive_terms(flops_per_device=1e9, bytes_per_device=819e9,
                      collective_bytes_per_device=0, chips=256,
                      model_flops_total=1e9)
    assert r2["dominant"] == "memory_s"
