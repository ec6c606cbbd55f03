"""Chunked-prefill flash attention — the Convertible Decoder's hot kernel.

One kernel covers the whole prefill family:
  * whole-prompt prefill          (offset = 0, keys = the cache)
  * restricted chunked prefill    (offset = chunk start, keys = live cache)
  * sliding-window (local) layers (window > 0, e.g. gemma-2)
  * softcapped attention          (softcap > 0)

TPU mapping: flash-attention with a head-major query, q (B, Hq, Sq, D), read
against the KV cache in place, in its merged-head token-major layout
(B, Skv, Hkv*D) — the same layout the decode kernel reads, so no step copies
or transposes the cache.  Grid = (batch, kv-head block, q_block, kv_block).
A kv-head block holds the fewest KV heads whose lanes fill whole 128-lane
tiles (all of them where none do: Qwen2-0.5B's 2 x 64), so its K/V block
(BK, HB*D) and its query block (HB*G, BQ, D) satisfy the TPU (8, 128) block
rule for any head count.  Inside a block each KV head's G query heads are
stacked into one (G*BQ, D) matmul against that head's D lanes (grouped
query).  The kv_block axis is innermost and sequential, so the
online-softmax stats (m, l) and the output accumulator live in VMEM scratch
across kv steps.

The cache may be the model's whole stack of layers, (NB, P, Skv, Hkv*D):
the layer index and each batch row's pool row (`slots`) are scalar-prefetched
and the index maps read (layer, slots[b], block) where it lies.

Per-batch `offset` and `lengths` are scalar-prefetched.  Masking is computed
from broadcasted iotas against absolute positions, which is what lets the
SAME kernel serve both the prefiller instances and the convertible decoder's
restricted chunks.  A kv block that no row of the q block may see (past the
valid length, above the causal diagonal, behind the window) is neither
computed (@pl.when) nor fetched again (the index map clamps to the live
range), so a chunk's traffic is O(live cache), not O(Skv).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def heads_per_block(hkv: int, d: int) -> int:
    """Fewest KV heads whose D-wide lanes fill whole 128-lane tiles; all
    `hkv` of them where no divisor does (a block as wide as the array is
    always legal)."""
    for h in range(1, hkv + 1):
        if hkv % h == 0 and (h * d) % 128 == 0:
            return h
    return hkv


def _live_blocks(offset, length, qi, bq: int, bk: int, window: int):
    """First and last kv block that some row of q block `qi` may see."""
    q_lo = offset + qi * bq
    q_hi = q_lo + bq - 1
    hi = jnp.maximum(jnp.minimum(q_hi, length - 1), 0) // bk
    lo = jnp.maximum(q_lo - window + 1, 0) // bk if window else 0
    return lo, hi


def _kernel(off_ref, len_ref, layer_ref, slot_ref,  # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,                   # VMEM blocks
            o_ref,                                 # VMEM out block
            acc, m_s, l_s,                         # scratch
            *, scale: float, window: int, softcap: float,
            bq: int, bk: int, hb: int, group: int, d: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    offset = off_ref[b]
    length = len_ref[b]
    rows = group * bq                              # one KV head's q rows

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    lo, hi = _live_blocks(offset, length, qi, bq, bk, window)

    @pl.when((ki >= lo) & (ki <= hi))
    def _compute():
        # row r of a stacked (G*BQ, BK) score tile is q row r mod BQ
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        q_pos = offset + qi * bq + (r & (bq - 1))
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        mask = (k_pos <= q_pos) & (k_pos < length)
        if window:
            mask &= k_pos > (q_pos - window)
        for h in range(hb):                        # static: KV heads here
            q = q_ref[0, h * group:(h + 1) * group].reshape(rows, d)
            k = k_ref[0, 0, :, h * d:(h + 1) * d]          # (BK, D)
            v = v_ref[0, 0, :, h * d:(h + 1) * d]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)
            sl = slice(h * rows, (h + 1) * rows)
            m_prev = m_s[sl]                               # (G*BQ, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                         # (G*BQ, BK)
            l_s[sl] = l_s[sl] * alpha + p.sum(axis=1, keepdims=True)
            m_s[sl] = m_new
            acc[sl] = acc[sl] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _done():
        # a row that saw no key (padding past the window) still has m at
        # NEG_INF and the masked scores in acc: it outputs 0
        seen = m_s[...] > NEG_INF
        l = jnp.maximum(l_s[...], 1e-30)
        o = jnp.where(seen, acc[...] / l, 0.0)
        o_ref[0] = o.reshape(hb * group, bq, d).astype(o_ref.dtype)


def chunked_prefill_attention(
        q: jax.Array,            # (B, Hq, Sq, D) head-major
        k: jax.Array,            # (B, Skv, Hkv*D) or (NB, P, Skv, Hkv*D)
        v: jax.Array,
        offset: jax.Array,       # (B,) int32 absolute position of q row 0
        lengths: jax.Array,      # (B,) int32 absolute valid key length
        layer: Optional[jax.Array] = None,   # () int32 into NB; default 0
        slots: Optional[jax.Array] = None,   # (B,) int32 into P; default b
        window: int = 0,
        softcap: float = 0.0,
        scale: Optional[float] = None,
        block_q: int = DEFAULT_BQ,
        block_k: int = DEFAULT_BK,
        interpret: bool = False) -> jax.Array:
    if k.ndim == 3:
        k, v = k[None], v[None]
    B, Hq, Sq, D = q.shape
    P, Skv, HD = k.shape[1:]
    Hkv = HD // D
    assert (HD == Hkv * D and Hq % Hkv == 0 and Sq % block_q == 0
            and Skv % block_k == 0 and block_q & (block_q - 1) == 0), (
        q.shape, k.shape, block_q, block_k)
    scale = scale if scale is not None else D ** -0.5
    group = Hq // Hkv
    hb = heads_per_block(Hkv, D)

    layer = jnp.zeros((1,), jnp.int32) if layer is None else (
        jnp.reshape(layer, (1,)).astype(jnp.int32))
    slots = jnp.arange(B, dtype=jnp.int32) if slots is None else (
        jnp.minimum(slots, P - 1).astype(jnp.int32))

    def kv_index(b, h, qi, ki, off, lens, layer, slots):
        lo, hi = _live_blocks(off[b], lens[b], qi, block_q, block_k, window)
        return layer[0], slots[b], jnp.clip(ki, lo, hi), h

    kern = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        bq=block_q, bk=block_k, hb=hb, group=group, d=D)
    q_spec = pl.BlockSpec((1, hb * group, block_q, D),
                          lambda b, h, qi, ki, *_: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hb * D), kv_index)
    rows = hb * group * block_q
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, Hkv // hb, Sq // block_q, Skv // block_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, D), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="chunked_prefill_attention",
    )(offset.astype(jnp.int32), lengths.astype(jnp.int32), layer, slots,
      q, k, v)
