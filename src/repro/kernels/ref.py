"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function mirrors its kernel's semantics exactly, in plain jax.numpy —
tests sweep shapes/dtypes and assert_allclose kernels against these.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def chunked_prefill_attention_ref(
        q: jax.Array,            # (B, Sq, Hq, D)
        k: jax.Array,            # (B, Skv, Hkv, D)  — the KV cache
        v: jax.Array,            # (B, Skv, Hkv, D)
        offset: jax.Array,       # (B,) absolute position of q row 0
        lengths: jax.Array,      # (B,) absolute valid key length
        window: int = 0,
        softcap: float = 0.0,
        scale: Optional[float] = None) -> jax.Array:
    """Causal (chunked) prefill attention against a cache.

    Row t of q sits at absolute position offset+t; keys are cache slots
    0..Skv-1; a key is visible iff k_pos <= q_pos and k_pos < lengths
    (and within the sliding window if window > 0).  A row that sees no
    key (only padding rows can) is 0."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qq = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqkgd,blkd->bkgql", qq.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = offset[:, None] + jnp.arange(Sq)[None]           # (B, Sq)
    k_pos = jnp.arange(Skv)[None]                            # (1, Skv)
    mask = k_pos[:, None, :] <= q_pos[:, :, None]            # (B, Sq, Skv)
    mask &= k_pos[:, None, :] < lengths[:, None, None]
    if window:
        mask &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    # a row that sees no key at all (padding past the window) outputs 0
    p = jnp.where(mask.any(-1)[:, None, None, :, None],
                  jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bkgql,blkd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


def decode_attention_ref(
        q: jax.Array,            # (B, Hq, D) — the single new token
        k: jax.Array,            # (B, L, Hkv, D)
        v: jax.Array,            # (B, L, Hkv, D)
        cur_lens: jax.Array,     # (B,) cache tokens; new token at cur_lens
        window: int = 0,
        softcap: float = 0.0,
        scale: Optional[float] = None) -> jax.Array:
    """Flash-decode semantics: attend to k_pos <= cur_len (the new token's
    k/v has already been written at slot cur_len)."""
    B, Hq, D = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qq = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bkgd,blkd->bkgl", qq.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = jnp.arange(L)[None]
    mask = k_pos <= cur_lens[:, None]
    if window:
        mask &= k_pos > (cur_lens[:, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,blkd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, D).astype(q.dtype)


def wkv6_ref(r, k, v, w, u, s0):
    """RWKV6 recurrence oracle (same math as models.ops.rwkv_wkv).

    r,k,w: (B,H,S,K) f32; v: (B,H,S,K); u: (H,K); s0: (B,H,K,K).
    Returns (y (B,H,S,K), sT (B,H,K,K))."""
    B, H, S, K = r.shape

    def step(s, t_in):
        r_t, k_t, v_t, w_t = t_in                     # (B,H,K)
        kv = k_t[..., :, None] * v_t[..., None, :]    # (B,H,K,K)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv)
        s = w_t[..., :, None] * s + kv
        return s, y

    xs = tuple(a.transpose(2, 0, 1, 3) for a in (r, k, v, w))
    sT, ys = jax.lax.scan(step, s0.astype(jnp.float32), xs)
    return ys.transpose(1, 2, 0, 3), sT
