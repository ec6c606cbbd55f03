"""Declarative parameter/state system.

Each layer kind declares its parameters once as a tree of ``Leaf`` records
(shape + logical sharding axes + init tag).  From that single declaration we
derive:

  * ``init_params``  — real arrays (seeded, per-path RNG folding)
  * ``abstract_params`` — ShapeDtypeStructs (dry-run: no allocation)
  * ``param_axes``   — logical-axis tuples per leaf (-> PartitionSpecs)

and the same for decode/prefill state.  Per-layer weights inside the repeated
block pattern are STACKED with a leading ``num_blocks`` dim so the forward
pass can ``jax.lax.scan`` over depth (keeps HLO O(1) in num_layers — required
for the 61-layer / 1T-param dry-runs).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig

Tree = dict


@dataclass(frozen=True)
class Leaf:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "fanin"          # fanin | zeros | ones | embed | const:<v> | alog | decay
    dtype: Optional[str] = None  # None -> cfg.param_dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


# ---------------------------------------------------------------------------
# Layer declarations
# ---------------------------------------------------------------------------

def _attn_leaves(cfg: ModelConfig, cross: bool = False) -> Tree:
    d, dh = cfg.d_model, cfg.head_dim_
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    t: Tree = {"ln1": Leaf((d,), (None,), "ones")}
    if cfg.kv_lora_rank and not cross:
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        t["wq"] = Leaf((d, nq * qk), ("embed", "heads"))
        t["w_dkv"] = Leaf((d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                          ("embed", "kv_lora"))
        t["kv_norm"] = Leaf((cfg.kv_lora_rank,), ("kv_lora",), "ones")
        t["w_uk"] = Leaf((cfg.kv_lora_rank, nq * cfg.qk_nope_dim),
                         ("kv_lora", "heads"))
        t["w_uv"] = Leaf((cfg.kv_lora_rank, nq * cfg.v_head_dim),
                         ("kv_lora", "heads"))
        t["wo"] = Leaf((nq * cfg.v_head_dim, d), ("heads", "embed"))
    else:
        t["wq"] = Leaf((d, nq * dh), ("embed", "heads"))
        t["wk"] = Leaf((d, nkv * dh), ("embed", "kv_heads"))
        t["wv"] = Leaf((d, nkv * dh), ("embed", "kv_heads"))
        t["wo"] = Leaf((nq * dh, d), ("heads", "embed"))
        if cfg.qkv_bias:
            t["bq"] = Leaf((nq * dh,), ("heads",), "zeros")
            t["bk"] = Leaf((nkv * dh,), ("kv_heads",), "zeros")
            t["bv"] = Leaf((nkv * dh,), ("kv_heads",), "zeros")
    if cross:
        t["gate"] = Leaf((), (), "zeros")
        t["q_norm"] = Leaf((dh,), (None,), "ones")
        t["k_norm"] = Leaf((dh,), (None,), "ones")
    if cfg.post_norms:
        t["ln1_post"] = Leaf((d,), (None,), "ones")
    return t


def _dense_ffn_leaves(cfg: ModelConfig) -> Tree:
    d, f = cfg.d_model, cfg.d_ff
    t: Tree = {
        "ln2": Leaf((d,), (None,), "ones"),
        "w_gate": Leaf((d, f), ("embed", "ff")),
        "w_up": Leaf((d, f), ("embed", "ff")),
        "w_down": Leaf((f, d), ("ff", "embed")),
    }
    if cfg.post_norms:
        t["ln2_post"] = Leaf((d,), (None,), "ones")
    return t


def _moe_ffn_leaves(cfg: ModelConfig) -> Tree:
    d, m = cfg.d_model, cfg.moe
    e, f = m.num_experts, m.d_ff_expert
    # expert weights FSDP over their d_ff dim ("expert_ff"), NOT d_model:
    # resharding then gathers one layer's experts at the shard_map boundary
    # instead of the whole scanned stack (EXPERIMENTS.md §Perf, kimi-k2).
    t: Tree = {
        "ln2": Leaf((d,), (None,), "ones"),
        "router": Leaf((d, e), ("embed", None)),
        "we_gate": Leaf((e, d, f), ("experts", None, "expert_ff")),
        "we_up": Leaf((e, d, f), ("experts", None, "expert_ff")),
        "we_down": Leaf((e, f, d), ("experts", "expert_ff", None)),
    }
    if m.num_shared:
        fs = m.num_shared * f
        t["ws_gate"] = Leaf((d, fs), ("embed", "ff"))
        t["ws_up"] = Leaf((d, fs), ("embed", "ff"))
        t["ws_down"] = Leaf((fs, d), ("ff", "embed"))
    if cfg.post_norms:
        t["ln2_post"] = Leaf((d,), (None,), "ones")
    return t


def _mamba_leaves(cfg: ModelConfig) -> Tree:
    d, mc = cfg.d_model, cfg.mamba
    di = mc.expand * d
    dtr = mc.dt_rank or -(-d // 16)
    return {
        "ln1": Leaf((d,), (None,), "ones"),
        "in_proj": Leaf((d, 2 * di), ("embed", "ff")),
        "conv_w": Leaf((mc.d_conv, di), (None, "ff")),
        "conv_b": Leaf((di,), ("ff",), "zeros"),
        "x_proj": Leaf((di, dtr + 2 * mc.d_state), ("ff", None)),
        "dt_w": Leaf((dtr, di), (None, "ff")),
        "dt_b": Leaf((di,), ("ff",), "const:-4.6", "float32"),
        "A_log": Leaf((di, mc.d_state), ("ff", None), "alog", "float32"),
        "D": Leaf((di,), ("ff",), "ones", "float32"),
        "out_proj": Leaf((di, d), ("ff", "embed")),
    }


_RWKV_LORA = 32
_RWKV_DECAY_LORA = 64


def _rwkv_tm_leaves(cfg: ModelConfig) -> Tree:
    d = cfg.d_model
    t: Tree = {"ln1": Leaf((d,), (None,), "ones")}
    for n in ("x", "w", "k", "v", "r", "g"):
        t[f"mu_{n}"] = Leaf((d,), (None,), "const:0.5")
    t["lora_A"] = Leaf((d, 5 * _RWKV_LORA), ("embed", None))
    t["lora_B"] = Leaf((5, _RWKV_LORA, d), (None, None, "embed"), "zeros")
    t["w0"] = Leaf((d,), (None,), "decay", "float32")
    t["decay_A"] = Leaf((d, _RWKV_DECAY_LORA), ("embed", None))
    t["decay_B"] = Leaf((_RWKV_DECAY_LORA, d), (None, "embed"), "zeros")
    t["u"] = Leaf((d,), (None,), "const:0.5", "float32")
    for n in ("wr", "wk", "wv", "wg"):
        t[n] = Leaf((d, d), ("embed", "heads"))
    t["wo"] = Leaf((d, d), ("heads", "embed"))
    t["lnx_g"] = Leaf((d,), (None,), "ones")
    t["lnx_b"] = Leaf((d,), (None,), "zeros")
    return t


def _rwkv_cm_leaves(cfg: ModelConfig) -> Tree:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln2": Leaf((d,), (None,), "ones"),
        "mu_ck": Leaf((d,), (None,), "const:0.5"),
        "mu_cr": Leaf((d,), (None,), "const:0.5"),
        "wk_cm": Leaf((d, f), ("embed", "ff")),
        "wv_cm": Leaf((f, d), ("ff", "embed")),
        "wr_cm": Leaf((d, d), ("embed", None)),
    }


def layer_leaves(cfg: ModelConfig, spec: LayerSpec) -> Tree:
    mixer = {
        "attn": lambda: _attn_leaves(cfg),
        "local_attn": lambda: _attn_leaves(cfg),
        "cross_attn": lambda: _attn_leaves(cfg, cross=True),
        "mamba": lambda: _mamba_leaves(cfg),
        "rwkv": lambda: _rwkv_tm_leaves(cfg),
    }[spec.mixer]()
    ffn = {
        "dense": lambda: _dense_ffn_leaves(cfg),
        "moe": lambda: _moe_ffn_leaves(cfg),
        "rwkv_cm": lambda: _rwkv_cm_leaves(cfg),
    }[spec.ffn]()
    return {**mixer, **ffn}


def model_leaves(cfg: ModelConfig) -> Tree:
    d, v = cfg.d_model, cfg.vocab_size
    t: Tree = {
        "embed": Leaf((v, d), ("vocab", "embed"), "embed"),
        "final_norm": Leaf((d,), (None,), "ones"),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = Leaf((d, v), ("embed", "vocab"))
    t["prefix"] = {
        f"l{i}": layer_leaves(cfg, LayerSpec())
        for i in range(cfg.first_k_dense)
    }
    # block leaves get a leading (num_blocks,) stacking dim
    block = {f"p{i}": layer_leaves(cfg, s)
             for i, s in enumerate(cfg.block_pattern)}
    t["blocks"] = jax.tree.map(
        lambda lf: Leaf((cfg.num_blocks, *lf.shape), (None, *lf.axes),
                        lf.init, lf.dtype),
        block, is_leaf=lambda x: isinstance(x, Leaf))
    return t


# ---------------------------------------------------------------------------
# Decode/prefill state declarations
# ---------------------------------------------------------------------------

def layer_state_leaves(cfg: ModelConfig, spec: LayerSpec, batch: int,
                       max_len: int) -> Tree:
    dh = cfg.head_dim_
    nkv = cfg.num_kv_heads
    cdt = cfg.dtype
    if spec.mixer in ("attn", "local_attn"):
        if cfg.kv_lora_rank:
            return {
                "c_kv": Leaf((batch, max_len, cfg.kv_lora_rank),
                             ("batch", "ctx", "kv_lora"), "zeros", cdt),
                "k_rope": Leaf((batch, max_len, cfg.qk_rope_dim),
                               ("batch", "ctx", None), "zeros", cdt),
            }
        kv_dt = cfg.kv_cache_dtype or cdt
        # merged-head (B, L, Hkv*D): token-major, so a (L, Hkv*D) tile has
        # no padded minor dim and the decode kernel reads it as stored
        t = {
            "k": Leaf((batch, max_len, nkv * dh),
                      ("batch", "ctx", "kv_heads"), "zeros", kv_dt),
            "v": Leaf((batch, max_len, nkv * dh),
                      ("batch", "ctx", "kv_heads"), "zeros", kv_dt),
        }
        if kv_dt == "int8":
            t["k_scale"] = Leaf((batch, max_len, nkv),
                                ("batch", "ctx", "kv_heads"), "zeros",
                                "float32")
            t["v_scale"] = Leaf((batch, max_len, nkv),
                                ("batch", "ctx", "kv_heads"), "zeros",
                                "float32")
        return t
    if spec.mixer == "cross_attn":
        n = cfg.num_vision_tokens
        return {
            "xk": Leaf((batch, n, nkv, dh),
                       ("batch", None, "kv_heads", None), "zeros", cdt),
            "xv": Leaf((batch, n, nkv, dh),
                       ("batch", None, "kv_heads", None), "zeros", cdt),
        }
    if spec.mixer == "mamba":
        mc = cfg.mamba
        di = mc.expand * cfg.d_model
        return {
            "ssm": Leaf((batch, di, mc.d_state),
                        ("batch", "ff", None), "zeros", "float32"),
            "conv": Leaf((batch, mc.d_conv - 1, di),
                         ("batch", None, "ff"), "zeros", cdt),
        }
    if spec.mixer == "rwkv":
        h = cfg.d_model // cfg.rwkv_head_dim
        return {
            "wkv": Leaf((batch, h, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                        ("batch", "heads", None, None), "zeros", "float32"),
            "shift_t": Leaf((batch, cfg.d_model),
                            ("batch", "embed"), "zeros", cdt),
            "shift_c": Leaf((batch, cfg.d_model),
                            ("batch", "embed"), "zeros", cdt),
        }
    raise ValueError(spec.mixer)


def state_leaves(cfg: ModelConfig, batch: int, max_len: int) -> Tree:
    t: Tree = {
        "prefix": {f"l{i}": layer_state_leaves(cfg, LayerSpec(), batch, max_len)
                   for i in range(cfg.first_k_dense)},
    }
    block = {f"p{i}": layer_state_leaves(cfg, s, batch, max_len)
             for i, s in enumerate(cfg.block_pattern)}
    t["blocks"] = jax.tree.map(
        lambda lf: Leaf((cfg.num_blocks, *lf.shape), (None, *lf.axes),
                        lf.init, lf.dtype),
        block, is_leaf=lambda x: isinstance(x, Leaf))
    return t


# ---------------------------------------------------------------------------
# Materializers
# ---------------------------------------------------------------------------

def _is_leaf(x):
    return isinstance(x, Leaf)


def _init_array(leaf: Leaf, key, dtype) -> jax.Array:
    shape = leaf.shape
    if leaf.init == "zeros":
        return jnp.zeros(shape, dtype)
    if leaf.init == "ones":
        return jnp.ones(shape, dtype)
    if leaf.init.startswith("const:"):
        return jnp.full(shape, float(leaf.init[6:]), dtype)
    if leaf.init == "alog":
        ds = shape[-1]
        a = jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, shape).astype(dtype)
    if leaf.init == "decay":
        d = shape[-1]
        w0 = -6.0 + 5.0 * (jnp.arange(d, dtype=jnp.float32) / max(d - 1, 1))
        return jnp.broadcast_to(w0, shape).astype(dtype)
    if leaf.init == "embed":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    # fanin
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _fold_path(key, path) -> jax.Array:
    import zlib
    h = 0
    for p in path:
        name = getattr(p, "key", getattr(p, "idx", str(p)))
        # zlib.crc32 is process-stable (python hash() is salted per run!)
        h = (h * 1000003 + zlib.crc32(str(name).encode())) % (2 ** 31 - 1)
    return jax.random.fold_in(key, h)


def materialize(tree: Tree, cfg: ModelConfig, key=None, abstract=False):
    """Leaf tree -> arrays (key given) or ShapeDtypeStructs (abstract)."""
    def mk(path, leaf: Leaf):
        dtype = jnp.dtype(leaf.dtype or cfg.param_dtype)
        if abstract:
            return jax.ShapeDtypeStruct(leaf.shape, dtype)
        return _init_array(leaf, _fold_path(key, path), dtype)

    return jax.tree_util.tree_map_with_path(mk, tree, is_leaf=_is_leaf)


def axes_of(tree: Tree):
    """Leaf tree -> logical-axis tuples (same structure)."""
    return jax.tree.map(lambda lf: lf.axes, tree, is_leaf=_is_leaf)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Tree:
    return materialize(model_leaves(cfg), cfg, key=key)


def abstract_params(cfg: ModelConfig) -> Tree:
    return materialize(model_leaves(cfg), cfg, abstract=True)


def param_axes(cfg: ModelConfig) -> Tree:
    return axes_of(model_leaves(cfg))


def init_state(cfg: ModelConfig, batch: int, max_len: int) -> Tree:
    return materialize(state_leaves(cfg, batch, max_len), cfg,
                       key=jax.random.PRNGKey(0))


def abstract_state(cfg: ModelConfig, batch: int, max_len: int) -> Tree:
    return materialize(state_leaves(cfg, batch, max_len), cfg, abstract=True)


def state_axes(cfg: ModelConfig, batch: int = 1, max_len: int = 8) -> Tree:
    return axes_of(state_leaves(cfg, batch, max_len))


def count_params(params: Tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
