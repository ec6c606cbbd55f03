"""Composable decoder-only transformer over the layer zoo.

Depth is executed as ``first_k_dense`` unrolled prefix layers followed by a
``jax.lax.scan`` over ``num_blocks`` repeats of the block pattern (HLO stays
O(block) in size — required for 61-layer/1T-param abstract lowering).

Public API:
    init_params / abstract_params / param_axes        (re-exported)
    init_state / abstract_state / state_axes          (re-exported)
    forward_train(cfg, params, tokens, ...) -> (logits, aux_loss)
    prefill(cfg, params, state, tokens, lengths, ...) -> (last_logits, state)
    decode_step(cfg, params, state, last_tokens, cur_lens, ...) -> (logits, state)
"""
from __future__ import annotations

import math
from dataclasses import replace
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import ops
from repro.models.ops import ApplyCtx
from repro.models.params import (  # noqa: F401  (re-exports)
    abstract_params, abstract_state, count_params, init_params, init_state,
    param_axes, state_axes,
)
from repro.sharding import shard


def _apply_layer(cfg: ModelConfig, spec: LayerSpec, p, x, state,
                 ctx: ApplyCtx):
    """Residual layer = mixer + ffn. Returns (x, new_state, aux)."""
    window = cfg.sliding_window if spec.mixer == "local_attn" else 0
    lctx = replace(ctx, window=window)
    if spec.mixer in ("attn", "local_attn"):
        out, state = ops.apply_attn(cfg, p, x, state, lctx)
    elif spec.mixer == "cross_attn":
        out, state = ops.apply_cross_attn(cfg, p, x, state, lctx)
    elif spec.mixer == "mamba":
        out, state = ops.apply_mamba(cfg, p, x, state, lctx)
    elif spec.mixer == "rwkv":
        out, state = ops.apply_rwkv_tm(cfg, p, x, state, lctx)
    else:
        raise ValueError(spec.mixer)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn == "dense":
        out, aux = ops.apply_dense_ffn(cfg, p, x)
    elif spec.ffn == "moe":
        out, aux = ops.apply_moe_ffn(cfg, p, x)
    elif spec.ffn == "rwkv_cm":
        out, state = ops.apply_rwkv_cm(cfg, p, x, state, lctx)
    else:
        raise ValueError(spec.ffn)
    x = shard(x + out, "batch", None, "embed")
    return x, state, aux


def _embed(cfg: ModelConfig, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeds:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return shard(x.astype(jnp.dtype(cfg.dtype)), "batch", None, "embed")


def _unembed(cfg: ModelConfig, params, x):
    x = ops.rmsnorm(x, params["final_norm"], cfg.norm_plus_one)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    logits = ops.softcap(logits, cfg.logit_softcap)
    return shard(logits, "batch", None, "vocab")


def _backbone(cfg: ModelConfig, params, x, state, ctx: ApplyCtx):
    """Prefix layers + scanned blocks. Returns (x, new_state, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_prefix = {}
    for i in range(cfg.first_k_dense):
        st = None if state is None else state["prefix"][f"l{i}"]
        x, st, a = _apply_layer(cfg, LayerSpec(), params["prefix"][f"l{i}"],
                                x, st, ctx)
        new_prefix[f"l{i}"] = st
        aux = aux + a

    pattern = cfg.block_pattern

    if state is None:
        def body(carry, bp):
            h, acc = carry
            for i, spec in enumerate(pattern):
                h, _, a = _apply_layer(cfg, spec, bp[f"p{i}"], h, None, ctx)
                acc = acc + a
            return (h, acc), None

        if ctx.remat:
            # activation checkpointing: recompute each block in the bwd
            # pass instead of saving attention/FFN intermediates — required
            # for the full configs to fit HBM (see EXPERIMENTS.md §Perf)
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), _ = jax.lax.scan(body, (x, aux), params["blocks"])
        return x, None, aux

    # The stacked state rides in the carry, so each layer updates its rows
    # of it in place (`ops.write_state`) and the attention reads its layer
    # where it lies: no layer's cache is sliced out and stacked anew.
    def body_s(carry, xs):
        h, acc, blocks = carry
        bp, layer = xs
        lctx = replace(ctx, layer=layer)
        new_blocks = {}
        for i, spec in enumerate(pattern):
            h, new_blocks[f"p{i}"], a = _apply_layer(
                cfg, spec, bp[f"p{i}"], h, blocks[f"p{i}"], lctx)
            acc = acc + a
        return (h, acc, new_blocks), None

    (x, aux, blocks), _ = jax.lax.scan(
        body_s, (x, aux, state["blocks"]),
        (params["blocks"], jnp.arange(cfg.num_blocks, dtype=jnp.int32)))
    return x, {"prefix": new_prefix, "blocks": blocks}, aux


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def forward_train(cfg: ModelConfig, params, tokens,
                  image_embeds: Optional[jax.Array] = None,
                  lengths: Optional[jax.Array] = None,
                  remat: bool = False):
    """Full-sequence causal forward. Returns (logits (B,S,V) f32, aux)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ctx = ApplyCtx(mode="train", positions=positions, lengths=lengths,
                   image_embeds=image_embeds, remat=remat)
    x = _embed(cfg, params, tokens)
    x, _, aux = _backbone(cfg, params, x, None, ctx)
    return _unembed(cfg, params, x), aux


def prefill(cfg: ModelConfig, params, state, tokens, lengths,
            image_embeds: Optional[jax.Array] = None,
            start: Optional[jax.Array] = None,
            slots: Optional[jax.Array] = None):
    """Prompt processing; fills `state` at offset `start` (default 0).

    `lengths` is the ABSOLUTE valid length (start + valid tokens in this
    chunk) — chunked prefill passes consecutive windows with increasing
    `start`.  `slots` (B,) are the state rows the prompts fill (default
    0..B-1), so a chunk can go straight into a pooled state.  Returns
    (last_token_logits (B,V), new_state)."""
    B, S = tokens.shape
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    ctx = ApplyCtx(mode="prefill", positions=positions, lengths=lengths,
                   image_embeds=image_embeds, slots=slots)
    x = _embed(cfg, params, tokens)
    x, new_state, _ = _backbone(cfg, params, x, state, ctx)
    # unembed ONLY the last valid position: the (B,S,V) logits tensor for a
    # 32k prompt x 256k vocab would dwarf the rest of the step
    # (EXPERIMENTS.md §Perf, gemma2 prefill)
    idx = jnp.clip(lengths - start - 1, 0, S - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    return _unembed(cfg, params, x_last)[:, 0], new_state


def decode_step(cfg: ModelConfig, params, state, last_tokens, cur_lens,
                slots: Optional[jax.Array] = None):
    """One autoregressive step against the cache.

    last_tokens: (B,) int32; cur_lens: (B,) tokens already cached; slots:
    (B,) state rows (default 0..B-1), a row past the last being padding
    that writes nothing.  Returns (logits (B,V), new_state)."""
    positions = cur_lens.astype(jnp.int32)[:, None]        # (B,1)
    ctx = ApplyCtx(mode="decode", positions=positions, slots=slots)
    x = _embed(cfg, params, last_tokens[:, None])
    x, new_state, _ = _backbone(cfg, params, x, state, ctx)
    return _unembed(cfg, params, x)[:, 0], new_state


def greedy_generate(cfg: ModelConfig, params, tokens, lengths, max_new: int,
                    image_embeds: Optional[jax.Array] = None):
    """Reference generation loop (tests / examples)."""
    B, S = tokens.shape
    state = init_state(cfg, B, S + max_new)
    logits, state = prefill(cfg, params, state, tokens, lengths, image_embeds)
    out = []
    cur = lengths.astype(jnp.int32)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(max_new):
        out.append(tok)
        logits, state = decode_step(cfg, params, state, tok, cur)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        cur = cur + 1
    return jnp.stack(out, axis=1)
