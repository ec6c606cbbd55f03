"""Plain reference of a dense decoder with grouped-query attention.

The block of Llama, Qwen2 and Yi: RMSNorm, rotary embeddings (rotate-half),
grouped-query causal attention with optional q/k/v biases, a SwiGLU FFN, a
final RMSNorm and an output head (tied to the embedding or not).  Written
from the published description in straightforward ``jax.numpy``, with no
cache, no kernels and no batching tricks, and importing nothing of the
program.  It reads the benchmark's weights by their names, one layer at a
time, and computes every product at ``Precision.HIGHEST``.

``mode="f32"`` is the reference: float32 throughout.  ``mode="fp8"`` is the
control: each operand of every product (projections, attention scores,
attention times values, FFN, head) rounded to float8 e4m3 with a scale per
row or column, as an fp8 serving path would, and the products summed in
float32.

Weights (stacked over layers under ``blocks/p0``): ``embed`` (V, d),
``final_norm``, ``lm_head`` (d, V) unless tied, and per layer ``ln1``,
``wq``, ``wk``, ``wv``, ``wo``, ``bq``/``bk``/``bv`` where the model has
them, ``ln2``, ``w_gate``, ``w_up``, ``w_down``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest float8 e4m3fn


def fake_fp8(x, axis):
    """x rounded to float8 e4m3, scaled so each slice along `axis` uses the
    format's whole range."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _operand(x, axis, mode):
    return fake_fp8(x, axis) if mode == "fp8" else x


def _mm(a, w, mode):
    """a (..., k) @ w (k, n)."""
    w = w.astype(F32)
    return jnp.matmul(_operand(a, -1, mode), _operand(w, 0, mode),
                      precision=HI)


def _rmsnorm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(F32)


def _rope(x, pos, theta):
    """x (B, S, H, D); rotate-half rotary embedding at positions pos (B, S)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "mode"))
def layer(x, blocks, i, *, heads, kv_heads, eps, theta, mode):
    """Layer i of the stacked weights on x (B, S, d), causal from 0."""
    w = {k: v[i] for k, v in blocks["p0"].items()}
    B, S, d = x.shape
    dh = w["wq"].shape[-1] // heads
    g = heads // kv_heads
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = _rmsnorm(x, w["ln1"], eps)
    q, k, v = _mm(h, w["wq"], mode), _mm(h, w["wk"], mode), \
        _mm(h, w["wv"], mode)
    if "bq" in w:
        q, k, v = (q + w["bq"].astype(F32), k + w["bk"].astype(F32),
                   v + w["bv"].astype(F32))
    q = _rope(q.reshape(B, S, heads, dh), pos, theta).reshape(
        B, S, kv_heads, g, dh)
    k = _rope(k.reshape(B, S, kv_heads, dh), pos, theta)
    v = v.reshape(B, S, kv_heads, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", _operand(q, -1, mode),
                        _operand(k, -1, mode), precision=HI) * dh ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", _operand(p, -1, mode),
                   _operand(v, 1, mode), precision=HI)
    x = x + _mm(o.reshape(B, S, heads * dh), w["wo"], mode)
    h = _rmsnorm(x, w["ln2"], eps)
    f = jax.nn.silu(_mm(h, w["w_gate"], mode)) * _mm(h, w["w_up"], mode)
    return x + _mm(f, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "mode"))
def head_logits(x, idx, final_norm, head, *, eps, tied, mode):
    """Logits (B, P, V) at positions idx (B, P) of the last hidden x."""
    xs = jnp.take_along_axis(x, idx[..., None], axis=1)
    xs = _rmsnorm(xs, final_norm, eps)
    w = head.T if tied else head
    return _mm(xs, w, mode)


@jax.jit
def _embed(embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(F32)


def logits_at(spec: dict, params, tokens, idx, mode: str = "f32"):
    """Reference logits (B, P, V) float32 of rows `tokens` (B, S) at
    positions `idx` (B, P): position j's logits predict token j + 1."""
    heads = int(spec["num_attention_heads"])
    kw = dict(heads=heads, kv_heads=int(spec["num_key_value_heads"]),
              eps=float(spec["rms_norm_eps"]),
              theta=float(spec["rope_theta"]), mode=mode)
    x = _embed(params["embed"], tokens)
    blocks = params["blocks"]
    for i in range(int(spec["num_hidden_layers"])):
        x = layer(x, blocks, i, **kw)
    tied = bool(spec["tie_word_embeddings"])
    head = params["embed"] if tied else params["lm_head"]
    return head_logits(x, idx, params["final_norm"], head,
                       eps=kw["eps"], tied=tied, mode=mode)
