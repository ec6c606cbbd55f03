"""Layer math for every mixer / FFN kind.

All mixers share one signature::

    apply_<kind>(cfg, p, x, state, ctx) -> (out, new_state)

with ``x: (B, S, d)`` (S=1 for decode), ``state`` a dict (or None in train
mode) and ``ctx`` carrying positions / lengths / mode.  FFNs return
``(out, aux_loss)``.  Accumulations are f32; activations run in cfg.dtype.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.params import _RWKV_LORA  # lora width shared with decls
from repro.sharding import current_mesh, current_rules, shard

NEG_INF = -2.0 ** 30


@dataclass
class ApplyCtx:
    mode: str                      # "train" | "prefill" | "decode"
    positions: jax.Array           # (B, S) int32 — absolute token positions
    lengths: Optional[jax.Array] = None    # (B,) valid prompt lengths
    image_embeds: Optional[jax.Array] = None
    window: int = 0                # sliding window for local_attn layers
    remat: bool = False            # checkpoint each scanned block (train)
    # Where the batch's state lives.  `layer` indexes a leaf stacked over
    # the scanned blocks (None: the leaf is this layer's own); `slots` (B,)
    # names the state row of each batch row (None: row b).  A slot past the
    # last row is padding: the row writes no state.
    layer: Optional[jax.Array] = None
    slots: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def rmsnorm(x, w, plus_one: bool = False, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    scale = (1.0 + w.astype(jnp.float32)) if plus_one else w.astype(jnp.float32)
    return (xf * scale).astype(dt)


def _rope_tables(positions, dim, theta):
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta):
    """x: (B, S, H, Dh) — llama-style rotate-half RoPE."""
    cos, sin = _rope_tables(positions, x.shape[-1], theta)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap else x


def _act(x, kind: str):
    return jax.nn.gelu(x) if kind == "gelu" else jax.nn.silu(x)


def _quant_kv(x):
    """(B,S,H,D) -> (int8 values, f32 per-(token,head) scales)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def read_state(leaf, ctx: ApplyCtx):
    """The batch's rows of this layer's state leaf: (B, ...)."""
    if ctx.layer is not None:
        leaf = jax.lax.dynamic_index_in_dim(leaf, ctx.layer, keepdims=False)
    if ctx.slots is not None:
        leaf = jnp.take(leaf, ctx.slots, axis=0, mode="clip")
    return leaf


def write_state(leaf, new, ctx: ApplyCtx, start=None):
    """`leaf` with the batch's new state written in place at this layer's
    rows: whole rows `new` (B, ...), or with `start` (B,) the S positions
    `new` (B, S, ...) of a sequence leaf from position start[b].  One
    scatter of B windows; a padding slot's window is dropped."""
    B = new.shape[0]
    cols = [jnp.arange(B, dtype=jnp.int32) if ctx.slots is None
            else ctx.slots.astype(jnp.int32)]
    if ctx.layer is not None:
        cols.insert(0, jnp.full((B,), ctx.layer, jnp.int32))
    n_whole = len(cols)                 # layer and slot: one index each
    if start is not None:
        cols.append(start.astype(jnp.int32))
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, new.ndim)),
        inserted_window_dims=tuple(range(n_whole)),
        scatter_dims_to_operand_dims=tuple(range(len(cols))))
    return jax.lax.scatter(
        leaf, jnp.stack(cols, axis=1), new.astype(leaf.dtype), dnums,
        unique_indices=True, mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _sdpa(q, k, v, mask, scale, cap: float = 0.0, merged: bool = True):
    """q: (B,S,Hq,Dh) k,v: (B,L,Hkv,Dv') mask: (B,1,1,S,L) bool.

    merged=True (training, no cache): GQA is computed with KV heads
    broadcast up to the merged Hq head dim: the (B,H,S,L) score/
    probability tensors then shard cleanly as ("batch", "heads") even when
    Hkv < model-axis size.  With the earlier grouped (B,Hkv,G,S,L) layout
    GSPMD hit 'involuntary full rematerialization' and all-gathered
    multi-TB probability tensors in the backward pass (EXPERIMENTS.md
    §Perf, kimi-k2 iteration 2).

    merged=False (prefill/decode against a sequence-sharded cache): the
    grouped form keeps the cache layout undisturbed — broadcasting KV
    heads there forces a cache re-shard gather per layer (measured 20x
    regression on qwen25 prefill, §Perf)."""
    B, S, Hq, Dh = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if not merged:
        qg = q.reshape(B, S, Hkv, G, Dh)
        scores = jnp.einsum("bskgd,blkd->bkgsl", qg, k,
                            preferred_element_type=jnp.float32) * scale
        scores = softcap(scores, cap)
        scores = jnp.where(mask.transpose(0, 2, 1, 3, 4) if mask.ndim == 5
                           else mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgsl,blkv->bskgv", probs.astype(v.dtype), v)
        return out.reshape(B, S, Hq, out.shape[-1])
    if G > 1:
        k = jnp.broadcast_to(k[:, :, :, None], (B, L, Hkv, G, Dh))
        k = k.reshape(B, L, Hq, Dh)
        vd = v.shape[-1]
        v = jnp.broadcast_to(v[:, :, :, None], (B, L, Hkv, G, vd))
        v = v.reshape(B, L, Hq, vd)
    scores = jnp.einsum("bshd,blhd->bhsl", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, cap)
    scores = jnp.where(mask[:, 0] if mask.ndim == 5 else mask,
                       scores, NEG_INF)
    scores = shard(scores, "batch", "heads", None, None)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = shard(probs, "batch", "heads", None, None)
    out = jnp.einsum("bhsl,blhv->bshv", probs.astype(v.dtype), v)
    return shard(out, "batch", None, "heads", None)


def _kernels_allowed(ctx: ApplyCtx) -> bool:
    """Whether this computation may take the Pallas kernels, which
    ``kops.by_platform`` then picks where it is lowered for a TPU.  Training
    keeps the jnp path (the kernels have no VJP), and so does every
    computation under a mesh, the repo's ``axis_rules`` or ``jax.set_mesh``:
    the kernels are not wrapped in ``shard_map``, so GSPMD could only
    replicate a ``pallas_call`` whole, cache included."""
    return (ctx.mode != "train" and current_mesh() is None
            and jax.sharding.get_abstract_mesh().empty)


def _heads_shardable(n_heads: int) -> bool:
    """True iff `n_heads` divides the model-axis extent the "heads" rule
    maps to — the precondition for the merged-head attention layout
    (e.g. qwen-2.5's 40 heads do NOT divide a 16-way axis; the merged
    layout would replicate multi-GB score tensors, §Perf)."""
    mesh = current_mesh()
    rules = current_rules()
    if mesh is None or rules is None:
        return True
    n = 1
    for a in rules.get("heads", ()):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n <= 1 or n_heads % n == 0


def _pallas_attn(cfg: ModelConfig, q, kc, vc, ctx: ApplyCtx, scale,
                 interpret: bool):
    """Attention through the Pallas kernels against the merged-head cache,
    which they read in place at the layer and slots of `ctx`.

    prefill -> chunked_prefill_attention (offset = chunk start);
    decode  -> flash-decode."""
    B = q.shape[0]
    if ctx.mode == "decode":
        return kops.decode_attention_op(
            q[:, 0], kc, vc, ctx.positions[:, 0], ctx.layer, ctx.slots,
            window=ctx.window, softcap=float(cfg.attn_softcap),
            scale=scale, interpret=interpret)[:, None]
    offset = ctx.positions[:, 0]
    if ctx.lengths is not None:
        lengths = ctx.lengths
    else:
        lengths = jnp.full((B,), kc.shape[-2], jnp.int32)
    return kops.prefill_attention(
        q, kc, vc, offset, lengths, ctx.layer, ctx.slots, window=ctx.window,
        softcap=float(cfg.attn_softcap), scale=scale, interpret=interpret)


def _cache_sdpa(cfg: ModelConfig, q, kc, vc, ctx: ApplyCtx, scale):
    """The jnp path (and the kernels' reference): attention against the
    batch's rows of the merged-head cache, grouped so the cache layout
    stays undisturbed."""
    kc, vc = read_state(kc, ctx), read_state(vc, ctx)
    B, L = kc.shape[:2]
    nkv, dh = cfg.num_kv_heads, cfg.head_dim_
    kc = shard(kc.reshape(B, L, nkv, dh), "batch", "ctx", "kv_heads", None)
    vc = shard(vc.reshape(B, L, nkv, dh), "batch", "ctx", "kv_heads", None)
    k_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    mask = _causal_mask(ctx, ctx.positions, k_pos, ctx.lengths, ctx.window)
    return _sdpa(q, kc, vc, mask, scale, cfg.attn_softcap, merged=False)


def _causal_mask(ctx: ApplyCtx, q_pos, k_pos, k_len=None, window: int = 0):
    """(B, 1, 1, S, L) boolean mask."""
    m = k_pos[:, None, :] <= q_pos[:, :, None]           # (B, S, L)
    if window:
        m &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    if k_len is not None:
        m &= k_pos[:, None, :] < k_len[:, None, None]
    return m[:, None, None]


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, local, softcap, bias) + KV cache
# ---------------------------------------------------------------------------

def apply_attn(cfg: ModelConfig, p, x, state, ctx: ApplyCtx):
    if cfg.kv_lora_rank:
        return _apply_mla(cfg, p, x, state, ctx)
    B, S, d = x.shape
    dh, nq, nkv = cfg.head_dim_, cfg.num_heads, cfg.num_kv_heads
    h = rmsnorm(x, p["ln1"], cfg.norm_plus_one)
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, nq, dh)
    k = k.reshape(B, S, nkv, dh)
    v = v.reshape(B, S, nkv, dh)
    q = shard(apply_rope(q, ctx.positions, cfg.rope_theta),
              "batch", None, "heads", None)
    k = apply_rope(k, ctx.positions, cfg.rope_theta)
    scale = cfg.query_scale or dh ** -0.5

    new_state = state
    if ctx.mode == "train":
        mask = _causal_mask(ctx, ctx.positions, ctx.positions,
                            ctx.lengths, ctx.window)
        out = _sdpa(q, shard(k, "batch", "ctx", "kv_heads", None),
                    shard(v, "batch", "ctx", "kv_heads", None), mask, scale,
                    cfg.attn_softcap, merged=_heads_shardable(nq))
    else:
        # write offset = absolute position of the first new token
        # (0 for whole-prompt prefill, chunk start for chunked prefill,
        #  cur_len for decode).  The cache is merged-head (B, L, Hkv*D):
        # token-major with no padded minor dims, the kernels' layout.
        start = ctx.positions[:, 0]
        merge = lambda a: a.reshape(*a.shape[:2], -1)  # noqa: E731
        at = ctx                        # where the attention reads kc, vc
        if cfg.kv_cache_dtype == "int8":
            # quantized KV cache: per-(token, head) absmax scales
            kq, ks = _quant_kv(k)
            vq, vs = _quant_kv(v)
            new_state = {
                **state,
                "k": write_state(state["k"], merge(kq), ctx, start),
                "v": write_state(state["v"], merge(vq), ctx, start),
                "k_scale": write_state(state["k_scale"], ks, ctx, start),
                "v_scale": write_state(state["v_scale"], vs, ctx, start)}

            def dq(name):
                c = read_state(new_state[name], ctx)
                sc = read_state(new_state[f"{name}_scale"], ctx)
                return merge(c.reshape(*c.shape[:2], nkv, dh).astype(x.dtype)
                             * sc[..., None].astype(x.dtype))
            kc, vc = dq("k"), dq("v")
            at = replace(ctx, layer=None, slots=None)   # the batch's rows
        else:
            # reshard the new K/V to the CACHE layout before the in-place
            # update: without this GSPMD falls back to "involuntary full
            # rematerialization" (a whole-cache f32 all-gather per layer,
            # 722 GB/chip on gemma2 prefill)
            kc = write_state(state["k"], shard(merge(k), "batch", "ctx",
                                               "kv_heads"), ctx, start)
            vc = write_state(state["v"], shard(merge(v), "batch", "ctx",
                                               "kv_heads"), ctx, start)
            new_state = {**state, "k": kc, "v": vc}
        reference = lambda: _cache_sdpa(  # noqa: E731
            cfg, q, kc, vc, at, scale)
        if _kernels_allowed(ctx):
            out = kops.by_platform(
                lambda interpret: _pallas_attn(cfg, q, kc, vc, at, scale,
                                               interpret), reference)
        else:
            out = reference()
    out = out.reshape(B, S, nq * dh) @ p["wo"]
    if cfg.post_norms:
        out = rmsnorm(out, p["ln1_post"], cfg.norm_plus_one)
    return out.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# MLA (DeepSeek latent attention) — naive expand for train/prefill,
# weight-absorbed scoring for decode (production path).
# ---------------------------------------------------------------------------

def _apply_mla(cfg: ModelConfig, p, x, state, ctx: ApplyCtx):
    B, S, d = x.shape
    nq = cfg.num_heads
    nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    h = rmsnorm(x, p["ln1"], cfg.norm_plus_one)
    q = (h @ p["wq"]).reshape(B, S, nq, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, ctx.positions, cfg.rope_theta)
    ckr = h @ p["w_dkv"]                                  # (B,S,lora+rope)
    c_kv = rmsnorm(ckr[..., :lora], p["kv_norm"])
    k_rope = apply_rope(ckr[..., None, lora:], ctx.positions,
                        cfg.rope_theta)[:, :, 0]          # (B,S,rope)
    scale = (nope + rope) ** -0.5

    new_state = state
    if ctx.mode == "train":
        cc, kr = c_kv, k_rope
        k_pos = ctx.positions
    else:
        start = ctx.positions[:, 0]
        new_state = {
            **state,
            "c_kv": write_state(state["c_kv"], c_kv, ctx, start),
            "k_rope": write_state(state["k_rope"], k_rope, ctx, start)}
        cc = read_state(new_state["c_kv"], ctx)
        kr = read_state(new_state["k_rope"], ctx)
        L = cc.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    mask = _causal_mask(ctx, ctx.positions, k_pos, ctx.lengths)[:, 0, 0]
    cc = shard(cc, "batch", "ctx", "kv_lora")

    w_uk = p["w_uk"].reshape(lora, nq, nope)
    if ctx.mode == "decode":
        # absorbed: score against the latent cache directly
        q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        scores = (jnp.einsum("bshr,blr->bhsl", q_abs,
                             cc.astype(jnp.float32))
                  + jnp.einsum("bshr,blr->bhsl", q_rope.astype(jnp.float32),
                               kr.astype(jnp.float32))) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhsl,blr->bshr", probs, cc.astype(jnp.float32))
        w_uv = p["w_uv"].reshape(lora, nq, vdim)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv.astype(jnp.float32))
        out = out.astype(x.dtype)
    else:
        k_nope = jnp.einsum("blr,rhn->blhn", cc, w_uk.astype(cc.dtype))
        v = jnp.einsum("blr,rhv->blhv", cc,
                       p["w_uv"].reshape(lora, nq, vdim).astype(cc.dtype))
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                      (*kr.shape[:2], nq, rope))], -1)
        q_full = jnp.concatenate([q_nope, q_rope], -1)
        out = _sdpa(q_full, k_full, v, mask[:, None, None], scale,
                    merged=(ctx.mode == "train" and _heads_shardable(nq)))
    out = out.reshape(B, S, nq * vdim) @ p["wo"]
    return out.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Cross attention (VLM image layers)
# ---------------------------------------------------------------------------

def apply_cross_attn(cfg: ModelConfig, p, x, state, ctx: ApplyCtx):
    B, S, d = x.shape
    dh, nq, nkv = cfg.head_dim_, cfg.num_heads, cfg.num_kv_heads
    h = rmsnorm(x, p["ln1"], cfg.norm_plus_one)
    q = (h @ p["wq"]).reshape(B, S, nq, dh)
    q = rmsnorm(q, p["q_norm"])
    new_state = state
    if ctx.mode == "decode":
        k, v = read_state(state["xk"], ctx), read_state(state["xv"], ctx)
    else:
        assert ctx.image_embeds is not None, "vlm prefill needs image_embeds"
        ie = ctx.image_embeds.astype(x.dtype)
        k = (ie @ p["wk"]).reshape(B, -1, nkv, dh)
        v = (ie @ p["wv"]).reshape(B, -1, nkv, dh)
        k = rmsnorm(k, p["k_norm"])
        if state is not None:
            new_state = {**state, "xk": write_state(state["xk"], k, ctx),
                         "xv": write_state(state["xv"], v, ctx)}
    mask = jnp.ones((B, 1, 1, S, k.shape[1]), bool)
    out = _sdpa(q, k, v, mask, dh ** -0.5)
    out = out.reshape(B, S, nq * dh) @ p["wo"]
    out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * out
    return out.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Dense gated FFN
# ---------------------------------------------------------------------------

def apply_dense_ffn(cfg: ModelConfig, p, x):
    h = rmsnorm(x, p["ln2"], cfg.norm_plus_one)
    g = _act(h @ p["w_gate"], cfg.act)
    u = h @ p["w_up"]
    out = shard(g * u, "batch", None, "ff") @ p["w_down"]
    if cfg.post_norms:
        out = rmsnorm(out, p["ln2_post"], cfg.norm_plus_one)
    return out.astype(x.dtype), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Mixture of Experts
#   * dense-masked path: every expert computed, mask-combined (CPU smoke /
#     tiny models / no mesh)
#   * expert-parallel path: shard_map over the "experts"->model mesh axis,
#     capacity-bounded scatter dispatch (GShard-style dropping), psum combine
# ---------------------------------------------------------------------------

def _router(cfg: ModelConfig, p, h):
    m = cfg.moe
    logits = (h.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # (..., E)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style): E * sum(frac_tokens * frac_prob)
    T = probs.shape[0] * probs.shape[1] if probs.ndim == 3 else probs.shape[0]
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))
    ce = jnp.zeros((m.num_experts,), jnp.float32)
    ce = ce.at[top_i.reshape(-1)].add(1.0) / max(T * m.top_k, 1)
    aux = m.router_aux_coef * m.num_experts * jnp.sum(me * ce)
    return top_p, top_i, aux


def _expert_ffn(cfg, we_gate, we_up, we_down, xe):
    """xe: (E, C, d) -> (E, C, d)."""
    g = _act(jnp.einsum("ecd,edf->ecf", xe, we_gate), cfg.act)
    u = jnp.einsum("ecd,edf->ecf", xe, we_up)
    return jnp.einsum("ecf,efd->ecd", g * u, we_down)


def _moe_dense_path(cfg: ModelConfig, p, h, top_p, top_i):
    m = cfg.moe
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    gates = jnp.zeros((B * S, m.num_experts), h.dtype)
    gates = gates.at[jnp.arange(B * S)[:, None],
                     top_i.reshape(B * S, -1)].set(
        top_p.reshape(B * S, -1).astype(h.dtype))
    g = _act(jnp.einsum("td,edf->tef", x, p["we_gate"]), cfg.act)
    u = jnp.einsum("td,edf->tef", x, p["we_up"])
    ye = jnp.einsum("tef,efd->ted", g * u, p["we_down"])
    y = jnp.einsum("ted,te->td", ye, gates)
    return y.reshape(B, S, d)


def _moe_ep_path(cfg: ModelConfig, p, h, mesh, ep_axes):
    """Expert-parallel MoE under shard_map.

    Two data layouts:

    * S > 1 (train/prefill, token-heavy): tokens sharded over the batch
      axes and replicated over the expert axis; weights gathered to each
      expert shard (FSDP semantics).
    * S == 1 (decode, token-light): WEIGHT-STATIONARY 2D EP — weights stay
      sharded (experts x model, d_ff x expert_ff-axes) and the tiny token
      batch is replicated to them instead; partial outputs psum over both
      weight axes.  This removes the per-token re-gather of FSDP'd expert
      weights that made giant-MoE decode collective-bound
      (EXPERIMENTS.md §Perf, kimi-k2 decode).

    Each expert shard dispatches only the (token, k) pairs routed to its
    local experts into a capacity-bounded (E_local, C, d) buffer, runs its
    experts, gathers back and psums partial outputs.
    """
    m = cfg.moe
    rules = current_rules() or {}
    stationary = h.shape[1] == 1
    batch_axes = () if stationary else tuple(
        a for a in rules.get("batch", ()) if a in mesh.axis_names)
    ff_axes = tuple(a for a in rules.get("expert_ff", ())
                    if a in mesh.axis_names and a not in ep_axes) \
        if stationary else ()
    if ff_axes and m.d_ff_expert % _axes_size(mesh, ff_axes) != 0:
        ff_axes = ()
    n_ep = 1
    for a in ep_axes:
        n_ep *= mesh.shape[a]
    e_local = m.num_experts // n_ep

    def local_moe(h_l, router, we_gate, we_up, we_down):
        B, S, d = h_l.shape
        T = B * S
        x = h_l.reshape(T, d)
        top_p, top_i, aux = _router(cfg, {"router": router}, h_l)
        top_p = top_p.reshape(T, m.top_k)
        top_i = top_i.reshape(T, m.top_k)
        cap = int(max(8, T * m.top_k / m.num_experts * m.capacity_factor))
        ep_rank = jax.lax.axis_index(
            ep_axes[0] if len(ep_axes) == 1 else ep_axes)
        lo = ep_rank * e_local
        flat_e = top_i.reshape(-1) - lo                     # (T*k,)
        local = (flat_e >= 0) & (flat_e < e_local)
        flat_e = jnp.where(local, flat_e, 0)
        onehot = (jax.nn.one_hot(flat_e, e_local, dtype=jnp.int32)
                  * local[:, None].astype(jnp.int32))       # (T*k, El)
        pos = jnp.cumsum(onehot, axis=0) - onehot            # pos within expert
        pos_e = (pos * onehot).sum(-1)                       # (T*k,)
        keep = local & (pos_e < cap)
        tok = jnp.repeat(jnp.arange(T), m.top_k)
        buf = jnp.zeros((e_local, cap, d), x.dtype)
        buf = buf.at[jnp.where(keep, flat_e, 0),
                     jnp.where(keep, pos_e, cap - 1)].add(
            x[tok] * keep[:, None].astype(x.dtype),
            mode="drop")
        y_e = _expert_ffn(cfg, we_gate, we_up, we_down, buf)
        y_pairs = y_e[flat_e, jnp.minimum(pos_e, cap - 1)]   # (T*k, d)
        w = (top_p.reshape(-1) * keep).astype(x.dtype)
        y = jnp.zeros_like(x).at[tok].add(y_pairs * w[:, None])
        y = jax.lax.psum(y, ep_axes + ff_axes)
        # aux varies per data shard; average over every named axis so the
        # out_spec P() (fully replicated) is semantically true.
        aux = jax.lax.pmean(aux, ep_axes + ff_axes + batch_axes)
        return y.reshape(B, S, d), aux

    bspec = batch_axes if batch_axes else None
    in_specs = (P(bspec),
                P(), P(ep_axes, None, ff_axes or None),
                P(ep_axes, None, ff_axes or None),
                P(ep_axes, ff_axes or None, None))
    out_specs = (P(bspec), P())
    return jax.shard_map(
        local_moe, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(h, p["router"], p["we_gate"], p["we_up"], p["we_down"])


def apply_moe_ffn(cfg: ModelConfig, p, x):
    m = cfg.moe
    h = rmsnorm(x, p["ln2"], cfg.norm_plus_one)
    mesh = current_mesh()
    rules = current_rules() or {}
    ep_axes = tuple(a for a in rules.get("experts", ())
                    if mesh is not None and a in mesh.axis_names)
    use_ep = (mesh is not None and ep_axes
              and m.num_experts % _axes_size(mesh, ep_axes) == 0
              and _axes_size(mesh, ep_axes) > 1)
    if use_ep:
        y, aux = _moe_ep_path(cfg, p, h, mesh, ep_axes)
    else:
        top_p, top_i, aux = _router(cfg, p, h)
        y = _moe_dense_path(cfg, p, h, top_p, top_i)
    if m.num_shared:
        g = _act(h @ p["ws_gate"], cfg.act)
        u = h @ p["ws_up"]
        y = y + (g * u) @ p["ws_down"]
    if cfg.post_norms:
        y = rmsnorm(y, p["ln2_post"], cfg.norm_plus_one)
    return y.astype(x.dtype), aux


def _axes_size(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — sequential scan + single-step
# ---------------------------------------------------------------------------

def _mamba_proj(cfg, p, h):
    mc = cfg.mamba
    di = mc.expand * cfg.d_model
    xz = h @ p["in_proj"]
    return xz[..., :di], xz[..., di:]


def _mamba_ssm_params(cfg, p, xc):
    """xc: (B, S, di) post-conv activations -> dt, Bm, Cm."""
    mc = cfg.mamba
    dtr = mc.dt_rank or -(-cfg.d_model // 16)
    x_dbl = xc @ p["x_proj"]
    dt = jax.nn.softplus(
        x_dbl[..., :dtr] @ p["dt_w"] + p["dt_b"].astype(jnp.float32))
    Bm = x_dbl[..., dtr:dtr + mc.d_state].astype(jnp.float32)
    Cm = x_dbl[..., dtr + mc.d_state:].astype(jnp.float32)
    return dt.astype(jnp.float32), Bm, Cm


def _mamba_conv_seq(p, x, conv_state):
    """Causal depthwise conv over time. x: (B,S,di); conv_state: (B,K-1,di)."""
    K = p["conv_w"].shape[0]
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * p["conv_w"][i] for i in range(K))
    new_state = xp[:, xp.shape[1] - (K - 1):]
    return out + p["conv_b"], new_state


def apply_mamba(cfg: ModelConfig, p, x, state, ctx: ApplyCtx):
    B, S, d = x.shape
    mc = cfg.mamba
    di = mc.expand * d
    h = rmsnorm(x, p["ln1"], cfg.norm_plus_one)
    xi, z = _mamba_proj(cfg, p, h)
    xi = shard(xi, "batch", None, "ff")
    conv0 = (read_state(state["conv"], ctx) if state is not None
             else jnp.zeros((B, mc.d_conv - 1, di), x.dtype))
    ssm0 = (read_state(state["ssm"], ctx).astype(jnp.float32)
            if state is not None
            else jnp.zeros((B, di, mc.d_state), jnp.float32))
    xc, conv1 = _mamba_conv_seq(p, xi, conv0)
    xc = _act(xc, "silu")
    dt, Bm, Cm = _mamba_ssm_params(cfg, p, xc)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (di, ds)
    xcf = xc.astype(jnp.float32)
    if ctx.lengths is not None:
        # padded prefill: freeze the state past each row's valid length
        m = (ctx.positions < ctx.lengths[:, None]).astype(jnp.float32)
        dt = dt * m[:, :, None]
        xcf = xcf * m[:, :, None]
        # conv state must reflect the last K-1 *valid* inputs
        # (local chunk coordinates: absolute length minus chunk start)
        loc = jnp.clip(ctx.lengths - ctx.positions[:, 0], 0, S)
        xp_full = jnp.concatenate([conv0.astype(xi.dtype), xi], axis=1)
        conv1 = jax.vmap(
            lambda xp, ln: jax.lax.dynamic_slice(
                xp, (ln, 0), (mc.d_conv - 1, di)))(xp_full, loc)

    def step(hprev, t_in):
        dt_t, B_t, C_t, x_t = t_in                         # (B,di),(B,ds),(B,ds),(B,di)
        da = jnp.exp(dt_t[:, :, None] * A[None])           # (B,di,ds)
        hn = da * hprev + dt_t[:, :, None] * B_t[:, None, :] * x_t[:, :, None]
        y = jnp.einsum("bds,bs->bd", hn, C_t)
        return hn, y

    hT, ys = jax.lax.scan(
        step, ssm0,
        (dt.transpose(1, 0, 2), Bm.transpose(1, 0, 2),
         Cm.transpose(1, 0, 2), xcf.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2) + xcf * p["D"].astype(jnp.float32)
    y = (y.astype(x.dtype) * _act(z, "silu")) @ p["out_proj"]
    new_state = state
    if state is not None:
        new_state = {"ssm": write_state(state["ssm"], hT, ctx),
                     "conv": write_state(state["conv"], conv1, ctx)}
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# RWKV-6 (Finch): data-dependent decay time-mix + channel-mix
# ---------------------------------------------------------------------------

def _token_shift(x, shift_state):
    """x: (B,S,d); shift_state: (B,d) = last token of previous chunk."""
    prev = jnp.concatenate([shift_state[:, None], x[:, :-1]], axis=1)
    return prev - x


def rwkv_wkv_chunked(r, k, v, w, u, s0, chunk: int = 16):
    """Chunked WKV6: C-token chunks as dense matmuls (MXU-friendly) with a
    cross-chunk state carry — the jnp twin of kernels/wkv6.py, used for
    training/prefill where the token-by-token scan is HBM-bound
    (EXPERIMENTS.md §Perf, rwkv6-3b iteration 1).

    r,k,v,w: (B,S,H,K) f32; u: (H,K); s0: (B,H,K,K) f32."""
    B, S, H, K = r.shape
    pad = (-S) % chunk
    if pad:
        zeros = jnp.zeros((B, pad, H, K), r.dtype)
        r = jnp.concatenate([r, zeros], 1)
        k = jnp.concatenate([k, zeros], 1)
        v = jnp.concatenate([v, zeros], 1)
        w = jnp.concatenate([w, jnp.ones((B, pad, H, K), w.dtype)], 1)
    NC = (S + pad) // chunk
    resh = lambda x: x.reshape(B, NC, chunk, H, K).transpose(1, 0, 2, 3, 4)  # noqa: E731
    rs, ks, vs, ws = resh(r), resh(k), resh(v), resh(w)
    t_idx = jnp.arange(chunk)[:, None]
    s_idx = jnp.arange(chunk)[None, :]

    def body(s, xs):
        rc, kc, vc, wc = xs                           # (B,C,H,K)
        logw = jnp.log(jnp.maximum(wc, 1e-38))
        L = jnp.cumsum(logw, axis=1)
        L_prev = L - logw
        q_in = rc * jnp.exp(L_prev)
        k_out = kc * jnp.exp(-L)
        y = jnp.einsum("bchk,bhkv->bchv", q_in, s)
        scores = jnp.einsum("bthk,bshk->bhts", q_in, k_out)
        scores = jnp.where((s_idx < t_idx)[None, None], scores, 0.0)
        diag = jnp.sum(rc * u[None, None] * kc, axis=-1)   # (B,C,H)
        y += jnp.einsum("bhts,bshv->bthv", scores, vc)
        y += diag.transpose(0, 1, 2)[..., None] * vc
        L_C = L[:, -1:]                               # (B,1,H,K)
        k_carry = kc * jnp.exp(L_C - L)
        s = (jnp.exp(L_C[:, 0])[..., None] * s
             + jnp.einsum("bchk,bchv->bhkv", k_carry, vc))
        return s, y

    sT, ys = jax.lax.scan(body, s0.astype(jnp.float32), (rs, ks, vs, ws))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S + pad, H, K)
    return y[:, :S], sT


def rwkv_wkv(r, k, v, w, u, s0):
    """WKV6 recurrence.

    r,k,w: (B,S,H,K) f32; v: (B,S,H,V) f32; u: (H,K); s0: (B,H,K,V).
    Returns y: (B,S,H,V), sT.
    """
    def step(s, t_in):
        r_t, k_t, v_t, w_t = t_in                       # (B,H,K)...(B,H,V)
        kv = k_t[..., :, None] * v_t[..., None, :]      # (B,H,K,V)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv)
        s = w_t[..., :, None] * s + kv
        return s, y

    sT, ys = jax.lax.scan(
        step, s0, (r.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
                   v.transpose(1, 0, 2, 3), w.transpose(1, 0, 2, 3)))
    return ys.transpose(1, 0, 2, 3), sT


def apply_rwkv_tm(cfg: ModelConfig, p, x, state, ctx: ApplyCtx):
    B, S, d = x.shape
    K = cfg.rwkv_head_dim
    H = d // K
    h = rmsnorm(x, p["ln1"], cfg.norm_plus_one)
    shift0 = (read_state(state["shift_t"], ctx) if state is not None
              else jnp.zeros((B, d), x.dtype))
    sx = _token_shift(h, shift0.astype(h.dtype))
    xxx = h + sx * p["mu_x"]
    lora = jnp.tanh(xxx @ p["lora_A"]).reshape(B, S, 5, _RWKV_LORA)
    mixes = jnp.einsum("bsln,lnd->bsld", lora, p["lora_B"])
    xw, xk, xv, xr, xg = [
        h + sx * (p[f"mu_{n}"] + mixes[:, :, i])
        for i, n in enumerate(("w", "k", "v", "r", "g"))]
    r = (xr @ p["wr"]).reshape(B, S, H, K).astype(jnp.float32)
    k = (xk @ p["wk"]).reshape(B, S, H, K).astype(jnp.float32)
    v = (xv @ p["wv"]).reshape(B, S, H, K).astype(jnp.float32)
    g = _act(xg @ p["wg"], "silu")
    wdec = (p["w0"].astype(jnp.float32)
            + (jnp.tanh(xw @ p["decay_A"]) @ p["decay_B"]).astype(jnp.float32))
    w = jnp.exp(-jnp.exp(wdec)).reshape(B, S, H, K)
    u = p["u"].astype(jnp.float32).reshape(H, K)
    if ctx.lengths is not None:
        # padded prefill: no decay, no writes past each row's valid length
        m = (ctx.positions < ctx.lengths[:, None])[:, :, None, None]
        w = jnp.where(m, w, 1.0)
        k = k * m
    s0 = (read_state(state["wkv"], ctx).astype(jnp.float32)
          if state is not None else jnp.zeros((B, H, K, K), jnp.float32))
    if S == 1:
        y, sT = rwkv_wkv(r, k, v, w, u, s0)
    elif _kernels_allowed(ctx):
        y, sT = kops.by_platform(
            lambda interpret: kops.wkv6_op(r, k, v, w, u, s0,
                                           interpret=interpret),
            lambda: rwkv_wkv_chunked(r, k, v, w, u, s0))
    else:
        y, sT = rwkv_wkv_chunked(r, k, v, w, u, s0)
    # per-head groupnorm
    mu = y.mean(-1, keepdims=True)
    var = y.var(-1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + 64e-5)
    y = y.reshape(B, S, d) * p["lnx_g"].astype(jnp.float32) \
        + p["lnx_b"].astype(jnp.float32)
    out = (y.astype(x.dtype) * g) @ p["wo"]
    new_state = state
    if state is not None:
        new_state = {**state, "wkv": write_state(state["wkv"], sT, ctx),
                     "shift_t": write_state(state["shift_t"],
                                            _last_valid(h, ctx), ctx)}
    return out.astype(x.dtype), new_state


def _last_valid(h, ctx: ApplyCtx):
    """Last *valid* token's activation (B, d), honoring padded prefill.

    Indices are local to the chunk: absolute length minus chunk start."""
    if ctx.lengths is None:
        return h[:, -1]
    idx = jnp.clip(ctx.lengths - ctx.positions[:, 0] - 1, 0, h.shape[1] - 1)
    return jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]


def apply_rwkv_cm(cfg: ModelConfig, p, x, state, ctx: ApplyCtx):
    B, S, d = x.shape
    h = rmsnorm(x, p["ln2"], cfg.norm_plus_one)
    shift0 = (read_state(state["shift_c"], ctx) if state is not None
              else jnp.zeros((B, d), x.dtype))
    sx = _token_shift(h, shift0.astype(h.dtype))
    xk = h + sx * p["mu_ck"]
    xr = h + sx * p["mu_cr"]
    kk = jnp.square(jax.nn.relu(xk @ p["wk_cm"]))
    kv = shard(kk, "batch", None, "ff") @ p["wv_cm"]
    out = jax.nn.sigmoid(xr @ p["wr_cm"]) * kv
    new_state = state
    if state is not None:
        new_state = {**state, "shift_c": write_state(
            state["shift_c"], _last_valid(h, ctx), ctx)}
    return out.astype(x.dtype), new_state
