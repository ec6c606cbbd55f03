"""Compile the main-path Pallas kernels, and a whole Qwen2-0.5B decode step,
for a described TPU v5e (no chip attached: the TPU compiler refuses here what
the chip would refuse, such as block shapes off the (8, 128) tiling).

The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

ARCHS = ["qwen2-0.5b", "llama-3.1-8b", "qwen25_32b"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001  (any failure to describe)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: what is
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# every arch at a power-of-two cache, plus a cache length (1,000) that no
# power of two past 8 divides: the kernels read a cache in place, unpadded
CACHES = [(arch, 4096) for arch in ARCHS] + [("qwen2-0.5b", 1000)]


@pytest.mark.parametrize("arch,L", CACHES)
def test_decode_attention_compiles(one_chip, arch, L):
    from repro.kernels.ops import decode_attention_op
    cfg = get_config(arch)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    B = 32
    _compile(decode_attention_op,
             _spec(one_chip, (B, hq, d)),
             _spec(one_chip, (B, L, hkv * d)),
             _spec(one_chip, (B, L, hkv * d)),
             _spec(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("arch,L", CACHES)
def test_chunked_prefill_attention_compiles(one_chip, arch, L):
    from repro.kernels.ops import prefill_attention
    cfg = get_config(arch)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    S = 512
    _compile(prefill_attention,
             _spec(one_chip, (1, S, hq, d)),
             _spec(one_chip, (1, L, hkv * d)),
             _spec(one_chip, (1, L, hkv * d)),
             _spec(one_chip, (1,), jnp.int32),
             _spec(one_chip, (1,), jnp.int32))


@pytest.mark.parametrize("arch", ARCHS)
def test_wkv6_compiles(one_chip, arch):
    """WKV6 over as many 64-wide heads as the model's d_model holds."""
    from repro.kernels.ops import wkv6_op
    H, K = get_config(arch).d_model // 64, 64
    f32 = jnp.float32
    seq = _spec(one_chip, (1, 256, H, K), f32)
    _compile(wkv6_op, seq, seq, seq, seq,
             _spec(one_chip, (H, K), f32),
             _spec(one_chip, (1, H, K, K), f32))


def test_qwen2_decode_step_compiles_on_kernels(one_chip):
    """The engine's decode program for Qwen2-0.5B (24 layers, 32 slots x
    4096 positions), traced on this host and lowered for the described
    chip, takes the kernel path by itself, fits one v5e's 16 GB, and updates
    the donated cache in place: the state is aliased to the output and no
    temporary holds as much as one layer of it."""
    from repro.models import abstract_params, abstract_state
    from repro.serving.engine import jitted, programs
    cfg = get_config("qwen2-0.5b")
    B, L = 32, 4096
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    state = place(abstract_state(cfg, B, L))
    compiled = jitted(programs(cfg)[1]).lower(
        place(abstract_params(cfg)), state,
        _spec(one_chip, (B,), jnp.int32),
        _spec(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    cache = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= cache
    assert mem.temp_size_in_bytes < cache / cfg.num_layers


def test_decode_step_under_a_mesh_keeps_jnp_path(topo, one_chip):
    """Under a mesh the model keeps its jnp attention even when lowered for
    a TPU: the kernels are not wrapped in shard_map."""
    from jax.sharding import Mesh
    from repro.models import abstract_params, abstract_state, decode_step
    from repro.sharding import DEFAULT_RULES, axis_rules
    cfg = get_config("qwen2-0.5b", smoke=True)
    B, L = 4, 256
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))
    with jax.set_mesh(mesh), axis_rules(DEFAULT_RULES, mesh):
        lowered = jax.jit(
            lambda p, s, t, c: decode_step(cfg, p, s, t, c)).lower(
            place(abstract_params(cfg)), place(abstract_state(cfg, B, L)),
            _spec(one_chip, (B,), jnp.int32),
            _spec(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" not in lowered.compile().as_text()
