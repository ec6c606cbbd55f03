"""Chunked WKV6 kernel — RWKV-6 recurrence as parallel chunks.

The naive recurrence is a length-S scan (MXU-starved: rank-1 updates).
This kernel processes C=16 tokens per chunk with dense matmuls:

With L_t = sum_{r<=t} log w_r (per key channel, L_0-exclusive prefix) and
S0 the carry state entering the chunk:

  y_t   = (r_t . e^{L_{t-1}}) @ S0                      (inter-chunk)
        + sum_{s<t} [(r_t e^{L_{t-1}}) . (k_s e^{-L_s})] v_s   (intra)
        + (r_t . u . k_t) v_t                            (bonus diag)
  S_out = e^{L_C} . S0 + sum_s (k_s e^{L_C - L_s}) (x) v_s

Chunk size 16 bounds |L| <= 16*e^1 so the e^{-L_s} factor stays inside f32
range for RWKV-6's decay parameterization (log w in [-e, ~0)); all chunk
math is f32 in VMEM.  Grid = (B, H, num_chunks): the chunk axis is
innermost/sequential on TPU, the (K, K) state rides in VMEM scratch across
chunk steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 16


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
            y_ref, sT_ref, s_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)            # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)               # (1, K)
    s0 = s_scr[...]                                # (K, K)

    logw = jnp.log(jnp.maximum(w, 1e-38))          # (C, K), <= 0
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum); HIGHEST keeps it f32-exact on the MXU
    tril = (s_idx <= t_idx).astype(jnp.float32)
    L = jax.lax.dot_general(tril, logw, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    L_prev = L - logw                              # exclusive prefix (L_{t-1})

    q_in = r * jnp.exp(L_prev)                     # (C, K)
    k_out = k * jnp.exp(-L)                        # (C, K)
    # inter-chunk contribution
    y = jax.lax.dot_general(q_in, s0, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk: strictly-causal scores + bonus diagonal
    scores = jax.lax.dot_general(q_in, k_out, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(s_idx < t_idx, scores, 0.0)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)   # (C, 1)
    scores += jnp.where(s_idx == t_idx, diag, 0.0)
    y += jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state carry: S_out = e^{L_C} . S0 + sum_s k_s e^{L_C - L_s} (x) v_s
    L_C = L[-1:, :]                                # (1, K)
    k_carry = k * jnp.exp(L_C - L)                 # (C, K)
    s_scr[...] = (jnp.exp(L_C).T * s0
                  + jax.lax.dot_general(k_carry, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(ci == nc - 1)
    def _done():
        sT_ref[0, 0] = s_scr[...].astype(sT_ref.dtype)


def wkv6(r, k, v, w, u, s0, chunk: int = DEFAULT_CHUNK,
         interpret: bool = False):
    """r,k,v,w: (B,H,S,K); u: (H,K); s0: (B,H,K,K) ->
    (y (B,H,S,K), sT (B,H,K,K))."""
    B, H, S, K = r.shape
    assert S % chunk == 0, (S, chunk)
    grid = (B, H, S // chunk)
    kern = functools.partial(_kernel, chunk=chunk)
    y, sT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, K), lambda b, h, ci: (h, 0, 0)),
            pl.BlockSpec((1, 1, K, K), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, K, K), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, K), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, K), s0)
    return y, sT
