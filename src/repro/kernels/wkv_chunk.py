"""Fused RWKV6 wkv chunk kernel (Pallas TPU).

The roofline analysis (EXPERIMENTS.md §Roofline) showed the pure-XLA chunked
wkv materializing its O(q²·K) intra-chunk decay products in HBM — on TPU the
whole chunk update fits VMEM. This kernel fuses one chunk's worth of the
Finch recurrence per grid step:

  grid = (B, H, S/Q) with the chunk axis sequential ("arbitrary"): the
  (K, V) recurrent state lives in a VMEM scratch that persists across the
  chunk axis; each step loads (Q, K) r/k/v/logw tiles, computes the
  boundary-factored intra-chunk + carried-state terms entirely in registers/
  VMEM, writes the (Q, K) output tile, and updates the state in place.

Math is identical to ``repro.models.rwkv6.wkv_chunked`` (same stability
construction: every cross-position decay is exp(Δ) with Δ ≤ 0); the oracle
is ``wkv_sequential``. Validated in interpret mode on CPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv_chunk_pallas"]


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *,
            sub: int, nc: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, :, 0, :].astype(jnp.float32)  # (Q, K)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    lw = lw_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0, :]  # (K,)
    state = state_ref[...]  # (K, V)

    Q, K = r.shape
    ns = Q // sub
    L = jnp.cumsum(lw, axis=0)  # inclusive log decay
    Lex = L - lw
    Lend = L[-1]

    # inter-chunk: carried state
    out = (r * jnp.exp(Lex)) @ state  # (Q, V)

    # cross-sub-block, boundary factored (all exponents <= 0)
    Lb = jnp.concatenate(
        [jnp.zeros((1, K), jnp.float32), L[sub - 1 :: sub][: ns - 1]], axis=0
    )  # (ns, K)
    rg = r.reshape(ns, sub, K)
    Lexg = Lex.reshape(ns, sub, K)
    r2 = rg * jnp.exp(jnp.minimum(Lexg - Lb[:, None], 0.0))
    k2 = k[None] * jnp.exp(jnp.minimum(Lb[:, None] - L[None], 0.0))  # (ns,Q,K)
    smask = jax.lax.broadcasted_iota(jnp.int32, (ns, Q), 1) < (
        jax.lax.broadcasted_iota(jnp.int32, (ns, Q), 0) * sub
    )
    att_x = jnp.einsum("jtk,jsk->jts", r2, k2,
                       preferred_element_type=jnp.float32)
    att_x = att_x * smask[:, None, :]
    out = out + jnp.einsum("jts,sv->jtv", att_x, v,
                           preferred_element_type=jnp.float32).reshape(Q, K)

    # diagonal sub-blocks: exact log-space difference
    kg = k.reshape(ns, sub, K)
    vg = v.reshape(ns, sub, K)
    Lg = L.reshape(ns, sub, K)
    ldiff = jnp.minimum(Lexg[:, :, None] - Lg[:, None], 0.0)  # (ns,t,s,K)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1))
    att_d = jnp.einsum("jtk,jsk,jtsk->jts", rg, kg,
                       jnp.where(tri[None, :, :, None], jnp.exp(ldiff), 0.0),
                       preferred_element_type=jnp.float32)
    out_d = jnp.einsum("jts,jsv->jtv", att_d, vg,
                       preferred_element_type=jnp.float32)
    out_u = (rg * u[None, None] * kg).sum(-1, keepdims=True) * vg
    out = out + (out_d + out_u).reshape(Q, K)

    # state update
    kdec = k * jnp.exp(jnp.minimum(Lend[None] - L, 0.0))
    state_ref[...] = state * jnp.exp(Lend)[:, None] + kdec.T @ v
    o_ref[0, :, 0, :] = out


def wkv_chunk_pallas(r, k, v, logw, u, *, chunk: int = 64, sub: int = 16,
                     interpret: bool = True):
    """Fused chunked wkv. r/k/v/logw: (B, S, H, K) fp32; u: (H, K).

    S % chunk == 0. Returns out (B, S, H, K) fp32 (zero initial state).
    """
    B, S, H, K = r.shape
    assert S % chunk == 0 and chunk % sub == 0, (S, chunk, sub)
    nc = S // chunk
    grid = (B, H, nc)
    spec = pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0))
    u_spec = pl.BlockSpec((1, K), lambda b, h, c: (h, 0))
    return pl.pallas_call(
        partial(_kernel, sub=sub, nc=nc),
        grid=grid,
        in_specs=[spec, spec, spec, spec, u_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
        name="wkv_chunk",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(r, k, v, logw, u)
