"""The serving programs update the pooled state in place.

Compiled on the CPU, the engine's decode and mixed programs alias the state
they are given (it is donated) and hold no cache-sized temporary: the
stacked state rides in the layer scan's carry, each layer writes its rows
with one scatter and reads only its own layer.  The mixed program writes its
prompt chunk straight into the chunk's slot of the pool, and gives what a
decode of the pool beside a prefill of that slot, read out beforehand and
written back afterwards, gives.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import (abstract_params, abstract_state, decode_step,
                          init_params, init_state, prefill)
from repro.serving.engine import _read_slot, _write_slot, jitted, programs


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("arch", ["llama31_8b", "deepseek_v2_lite_16b"],
                         ids=["dense_gqa", "mla"])
def test_decode_and_mixed_programs_update_the_pool_in_place(arch):
    """16 slots x 512 positions over 8 layers: each program aliases the
    whole state, and its temporaries stay under two layers' state (a
    quarter of it), where a copied cache would need all of it."""
    slots, max_len, chunk = 16, 512, 32
    cfg = get_config(arch, smoke=True).replace(num_layers=8)
    state = abstract_state(cfg, slots, max_len)
    cache = _nbytes(state)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    _, decode, mixed = programs(cfg)
    runs = {"decode": (decode, (i32(slots), i32(slots))),
            "mixed": (mixed, (i32(slots), i32(slots), i32(1),
                              i32(1, chunk), i32(1), i32(1)))}
    for name, (program, args) in runs.items():
        mem = jitted(program).lower(abstract_params(cfg), state,
                                    *args).compile().memory_analysis()
        assert mem.alias_size_in_bytes >= cache, (name, mem)
        assert mem.temp_size_in_bytes < 2 * cache / cfg.num_layers, (
            name, mem.temp_size_in_bytes, cache)


@pytest.mark.parametrize("arch", ["llama31_8b", "rwkv6_3b",
                                  "deepseek_v2_lite_16b", "jamba_v0_1_52b"])
def test_mixed_program_matches_decode_beside_a_slot_prefill(arch):
    """Three slots decode while slot 2 takes its second prompt chunk: the
    fused program's logits and pool equal the decode of the pool with the
    chunk prefilled on slot 2's state, read out before the step.  Slot 2's
    own decode row (padding) leaves its state alone, recurrent leaves
    included."""
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, L, C, slot = 4, 32, 8, 2
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 12)), jnp.int32)
    lens = jnp.array([12, 9, C, 11], jnp.int32)    # slot 2: one chunk in
    _, pool = prefill(cfg, params, init_state(cfg, B, L), toks, lens)
    last = jnp.asarray(rng.randint(0, cfg.vocab_size, (B,)), jnp.int32)
    cur = lens.at[slot].set(0)                     # as the engine keeps it
    chunk = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, C)), jnp.int32)
    start, chunk_len = jnp.array([C], jnp.int32), jnp.array([C + 5],
                                                            jnp.int32)

    @jax.jit
    def two_programs(pool):
        one = _read_slot(pool, slot)
        logits, pool = decode_step(cfg, params, pool, last, cur)
        clog, one = prefill(cfg, params, one, chunk, chunk_len, start=start)
        return logits, _write_slot(pool, one, slot), clog

    want = jax.device_get(two_programs(pool))
    got = jax.device_get(jitted(programs(cfg)[2])(
        params, pool, last, cur, jnp.array([slot], jnp.int32), chunk,
        chunk_len, start))
    # the same arithmetic in another fusion: float32 rounding apart
    close = dict(rtol=1e-5, atol=1e-5)
    rows = np.arange(B) != slot
    np.testing.assert_allclose(got[0][rows], want[0][rows], **close)
    np.testing.assert_allclose(got[2], want[2], **close)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **close)
