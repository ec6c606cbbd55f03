"""Jit'd public wrappers around the Pallas kernels, and the path choice.

The wrappers take the model's layouts: queries (B, S, H, D) and KV caches
merged-head (B, L, Hkv*D), or the model's stack of them (NB, P, L, Hkv*D)
with a layer index and pool rows, which the kernels read in place (a cache
is never padded, transposed or copied).  ``by_platform()`` is how the model
layer chooses, per lowering platform, between the kernels and its jnp path.
Interpret mode happens only where a caller passes ``interpret=True``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, TypeVar

import jax
import jax.numpy as jnp

from repro.kernels.chunked_prefill_attention import chunked_prefill_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.wkv6 import wkv6

T = TypeVar("T")


def by_platform(kernel: Callable[[bool], T], reference: Callable[[], T]) -> T:
    """``kernel(False)`` (the compiled Pallas kernels) where the computation
    is lowered for a TPU; ``reference()`` (the jnp path of ``models/ops.py``)
    on every other platform.

    The choice is made per lowering platform by ``lax.platform_dependent``,
    never from the host's default backend: a trace for a CPU device on a TPU
    host takes the jnp path, and a trace on a CPU host compiled for a
    described TPU takes the kernels.  Tests steer the choice by patching this
    function, e.g. with ``lambda kernel, reference: kernel(True)`` for the
    kernels in Pallas's interpreter."""
    return jax.lax.platform_dependent(tpu=lambda: kernel(False),
                                      default=reference)


def _block(n: int, block: int) -> int:
    """Power-of-two block for an axis of n: at most `block`, at least 16
    (the bf16 sublane tile), no larger than n needs.  The axis is padded."""
    return min(block, max(16, 1 << (n - 1).bit_length()))


def _cache_block(n: int, block: int) -> int:
    """Block for a cache axis of n positions, which is never padded: the
    largest multiple of 8 that divides n and is at most `block`, else n
    itself (a block as long as the axis is always legal)."""
    for b in range(min(block, n) // 8 * 8, 0, -8):
        if n % b == 0:
            return b
    return n


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def prefill_attention(q, k, v, offset, lengths, layer=None, slots=None,
                      window: int = 0, softcap: float = 0.0,
                      scale: Optional[float] = None, block_q: int = 128,
                      block_k: int = 128, interpret: bool = False):
    """(B,Sq,Hq,D) chunked/whole prefill against a (B,Skv,Hkv*D) cache, or
    against `layer` of a stacked (NB,P,Skv,Hkv*D) one at pool rows `slots`.

    Only the query (chunk-sized) is transposed and padded; the cache is read
    in place."""
    Sq = q.shape[1]
    bq = _block(Sq, block_q)
    out = chunked_prefill_attention(
        _pad_to(q.transpose(0, 2, 1, 3), bq, 2), k, v, offset, lengths,
        layer, slots, window=window, softcap=softcap, scale=scale,
        block_q=bq, block_k=_cache_block(k.shape[-2], block_k),
        interpret=interpret)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "block_k", "interpret"))
def decode_attention_op(q, k, v, cur_lens, layer=None, slots=None,
                        window: int = 0, softcap: float = 0.0,
                        scale: Optional[float] = None, block_k: int = 512,
                        interpret: bool = False):
    """(B,Hq,D) single-token decode against a (B,L,Hkv*D) cache, or against
    `layer` of a stacked (NB,P,L,Hkv*D) one at pool rows `slots`, read in
    place."""
    return decode_attention(q, k, v, cur_lens, layer, slots, window=window,
                            softcap=softcap, scale=scale,
                            block_k=_cache_block(k.shape[-2], block_k),
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_op(r, k, v, w, u, s0, chunk: int = 16, interpret: bool = False):
    """(B,S,H,K)-layout WKV6 (matches models.ops.rwkv_wkv call shapes).

    Pads S to the chunk multiple with w=1 (no decay), k=0 (no state write)
    so padding cannot disturb the carry."""
    B, S, H, K = r.shape
    pad = (-S) % chunk
    tr = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731  (B,H,S,K)
    rp, kp2, vp, wp = tr(r), tr(k), tr(v), tr(w)
    if pad:
        zeros = jnp.zeros((B, H, pad, K), r.dtype)
        ones = jnp.ones((B, H, pad, K), w.dtype)
        rp = jnp.concatenate([rp, zeros], axis=2)
        kp2 = jnp.concatenate([kp2, zeros], axis=2)
        vp = jnp.concatenate([vp, zeros], axis=2)
        wp = jnp.concatenate([wp, ones], axis=2)
    y, sT = wkv6(rp, kp2, vp, wp, u, s0, chunk=chunk, interpret=interpret)
    return y[:, :, :S].transpose(0, 2, 1, 3), sT
