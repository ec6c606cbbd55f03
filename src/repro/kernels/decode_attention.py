"""Flash-decode attention — the memory-bound decode hot-spot.

Decode reads the whole live KV cache once per step: the roofline is HBM
bandwidth.  The cache is token-major with the KV heads merged into the lane
axis, ``(B, L, Hkv*D)``, so one block ``(BK, Hkv*D)`` holds every KV head of
BK consecutive positions and satisfies the TPU (8, 128) block rule for any
head count (Qwen2-0.5B: Hkv*D = 2*64 = 128).  Grid = (batch, L // BK); the
cache-block axis is innermost and sequential, so the online-softmax stats and
the output accumulator persist in VMEM scratch across it.

GQA without slicing heads out of the lane axis: the wrapper spreads the
query block-diagonally, ``q_bd[i, g*D:(g+1)*D] = q_i`` for the KV group g of
query head i and zero elsewhere.  Then ``q_bd @ K^T`` is exactly each query
head's score against its own KV head, and ``P @ V`` puts head i's output in
lanes g*D:(g+1)*D, which the wrapper gathers back.  The MXU does Hkv times
the needed FLOPs, which a bandwidth-bound step has to spare.

``cur_lens`` is scalar-prefetched: the K/V index maps clamp the block index
to the live range, so blocks past cur_len (or behind the window) are neither
fetched again nor computed (@pl.when) — per-step traffic is O(cur_len).

The cache may be the model's whole stack of layers, ``(NB, P, L, Hkv*D)``:
the layer index and each batch row's pool row (``slots``) are
scalar-prefetched too, and the index maps pick ``(layer, slots[b], block)``
from the buffer as it lies, so no layer or slot is copied out first.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
DEFAULT_BK = 512


def _live_blocks(cur, bk: int, window: int):
    """First and last cache block holding a position the query may see."""
    hi = cur // bk
    lo = jnp.maximum(cur - window + 1, 0) // bk if window else 0
    return lo, hi


def _kernel(len_ref, layer_ref, slot_ref, q_ref, k_ref, v_ref, o_ref,
            acc, m_s, l_s, *, scale: float, window: int, softcap: float,
            bk: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    cur = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    lo, hi = _live_blocks(cur, bk, window)

    @pl.when((ki >= lo) & (ki <= hi))
    def _compute():
        q = q_ref[0]                                       # (Hq, Hkv*D)
        k = k_ref[0, 0]                                    # (BK, Hkv*D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos <= cur
        if window:
            mask &= k_pos > (cur - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = l_s[...] * alpha + p.sum(axis=1, keepdims=True)
        m_s[...] = m_new
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _done():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)


def decode_attention(
        q: jax.Array,            # (B, Hq, D)
        k: jax.Array,            # (B, L, Hkv*D) or (NB, P, L, Hkv*D) cache
        v: jax.Array,
        cur_lens: jax.Array,     # (B,) int32; the new token sits at cur_len
        layer: Optional[jax.Array] = None,   # () int32 into NB; default 0
        slots: Optional[jax.Array] = None,   # (B,) int32 into P; default b
        window: int = 0,
        softcap: float = 0.0,
        scale: Optional[float] = None,
        block_k: int = DEFAULT_BK,
        interpret: bool = False) -> jax.Array:
    if k.ndim == 3:
        k, v = k[None], v[None]
    B, Hq, D = q.shape
    P, L, HD = k.shape[1:]
    Hkv = HD // D
    assert HD == Hkv * D and Hq % Hkv == 0 and L % block_k == 0, (
        q.shape, k.shape, block_k)
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    # block-diagonal query: head i occupies the lanes of its KV group
    group = (jnp.arange(Hq) // G)[:, None] == jnp.arange(Hkv)[None, :]
    q_bd = (q[:, :, None, :] * group[None, :, :, None].astype(q.dtype))
    q_bd = q_bd.reshape(B, Hq, HD).astype(k.dtype)

    layer = jnp.zeros((1,), jnp.int32) if layer is None else (
        jnp.reshape(layer, (1,)).astype(jnp.int32))
    # a padding row (slot past the pool) reads the last row; its output
    # is not used
    slots = jnp.arange(B, dtype=jnp.int32) if slots is None else (
        jnp.minimum(slots, P - 1).astype(jnp.int32))

    def kv_index(b, ki, lens, layer, slots):
        lo, hi = _live_blocks(lens[b], block_k, window)
        return layer[0], slots[b], jnp.clip(ki, lo, hi), 0

    def q_index(b, ki, lens, layer, slots):
        return b, 0, 0

    kern = functools.partial(_kernel, scale=scale, window=window,
                             softcap=softcap, bk=block_k)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, L // block_k),
            in_specs=[
                pl.BlockSpec((1, Hq, HD), q_index),
                pl.BlockSpec((1, 1, block_k, HD), kv_index),
                pl.BlockSpec((1, 1, block_k, HD), kv_index),
            ],
            out_specs=pl.BlockSpec((1, Hq, HD), q_index),
            scratch_shapes=[
                pltpu.VMEM((Hq, HD), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, HD), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(cur_lens.astype(jnp.int32), layer, slots, q_bd, k, v)
    # head i's output sits in the lanes of its own KV group
    out = out.reshape(B, Hkv, G, Hkv, D)
    out = jnp.stack([out[:, g, :, g] for g in range(Hkv)], axis=1)
    return out.reshape(B, Hq, D).astype(q.dtype)
