"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.ops import (decode_attention_op, prefill_attention,
                               wkv6_op)

RNG = np.random.RandomState(42)


def _rnd(*shape, dtype=np.float32):
    return jnp.asarray(RNG.randn(*shape).astype(dtype))


def _merged(x):
    """(B, L, Hkv, D) -> the cache's merged-head (B, L, Hkv*D) layout."""
    return x.reshape(*x.shape[:2], -1)


# ---------------------------------------------------------------------------
# chunked prefill attention
# ---------------------------------------------------------------------------

SWEEP = [
    # B, Sq, Skv, Hq, Hkv, D, window, softcap
    (1, 8, 8, 1, 1, 16, 0, 0.0),
    (2, 24, 40, 4, 2, 64, 0, 0.0),
    (2, 24, 40, 4, 2, 64, 16, 0.0),
    (2, 24, 40, 4, 2, 64, 0, 30.0),
    (1, 128, 128, 8, 8, 32, 0, 0.0),     # MHA
    (3, 17, 33, 6, 1, 64, 0, 0.0),       # MQA, ragged sizes
    (1, 256, 384, 2, 2, 128, 64, 50.0),  # gemma2-style local+softcap
]


@pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,D,window,cap", SWEEP)
def test_chunked_prefill_attention(B, Sq, Skv, Hq, Hkv, D, window, cap):
    q = _rnd(B, Sq, Hq, D)
    k = _rnd(B, Skv, Hkv, D)
    v = _rnd(B, Skv, Hkv, D)
    off = jnp.asarray(RNG.randint(0, Skv - Sq + 1, size=(B,)), jnp.int32)
    lens = jnp.asarray(RNG.randint(1, Skv + 1, size=(B,)), jnp.int32)
    out = prefill_attention(q, _merged(k), _merged(v), off, lens,
                            window=window, softcap=cap, interpret=True)
    want = ref.chunked_prefill_attention_ref(q, k, v, off, lens,
                                             window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_chunked_prefill_attention_bf16():
    B, Sq, Skv, Hq, Hkv, D = 2, 16, 32, 4, 2, 64
    q = _rnd(B, Sq, Hq, D).astype(jnp.bfloat16)
    k = _rnd(B, Skv, Hkv, D).astype(jnp.bfloat16)
    v = _rnd(B, Skv, Hkv, D).astype(jnp.bfloat16)
    off = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), Skv, jnp.int32)
    out = prefill_attention(q, _merged(k), _merged(v), off, lens,
                            interpret=True)
    want = ref.chunked_prefill_attention_ref(q, k, v, off, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


@settings(max_examples=20, deadline=None)
@given(
    b=st.integers(1, 3),
    sq=st.integers(1, 24),
    extra=st.integers(0, 24),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 2, 3]),
    d=st.sampled_from([8, 32]),
)
def test_prefill_attention_property(b, sq, extra, hkv, g, d):
    """Property: kernel == oracle for arbitrary (chunk, cache) geometry."""
    rng = np.random.RandomState(b * 1000 + sq * 10 + extra)
    skv = sq + extra
    hq = hkv * g
    q = jnp.asarray(rng.randn(b, sq, hq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, skv, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, skv, hkv, d).astype(np.float32))
    off = jnp.asarray(rng.randint(0, extra + 1, size=(b,)), jnp.int32)
    lens = jnp.asarray(rng.randint(1, skv + 1, size=(b,)), jnp.int32)
    out = prefill_attention(q, _merged(k), _merged(v), off, lens,
                            interpret=True)
    want = ref.chunked_prefill_attention_ref(q, k, v, off, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,Hq,Hkv,D,window", [
    (1, 16, 1, 1, 16, 0),
    (2, 64, 8, 2, 64, 0),
    (2, 64, 8, 2, 64, 16),
    (4, 129, 4, 1, 128, 0),     # non-multiple cache length
    (1, 512, 16, 16, 64, 0),    # MHA long-ish
])
def test_decode_attention(B, L, Hq, Hkv, D, window):
    q = _rnd(B, Hq, D)
    k = _rnd(B, L, Hkv, D)
    v = _rnd(B, L, Hkv, D)
    cur = jnp.asarray(RNG.randint(0, L, size=(B,)), jnp.int32)
    out = decode_attention_op(q, _merged(k), _merged(v), cur,
                              window=window, block_k=32, interpret=True)
    want = ref.decode_attention_ref(q, k, v, cur, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layer", [0, 1, 3])
def test_decode_attention_reads_a_stacked_cache_in_place(layer):
    """The model's stacked (NB, P, L, Hkv*D) cache read at `layer`, with
    batch row b at pool row slots[b], equals the reference on that layer's
    rows; a padding slot past the pool leaves the other rows alone."""
    NB, P, B, L, Hq, Hkv, D = 4, 5, 3, 64, 8, 2, 64
    q = _rnd(B, Hq, D)
    k = _rnd(NB, P, L, Hkv, D)
    v = _rnd(NB, P, L, Hkv, D)
    cur = jnp.asarray(RNG.randint(0, L, size=(B,)), jnp.int32)
    slots = jnp.array([4, 0, 2], jnp.int32)
    stacked = lambda x: x.reshape(NB, P, L, Hkv * D)  # noqa: E731
    out = decode_attention_op(q, stacked(k), stacked(v), cur,
                              jnp.int32(layer), slots, block_k=16,
                              interpret=True)
    want = ref.decode_attention_ref(q, k[layer, slots], v[layer, slots], cur)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    padded = decode_attention_op(q, stacked(k), stacked(v), cur,
                                 jnp.int32(layer), slots.at[0].set(P),
                                 block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(padded[1:]), np.asarray(want[1:]),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
def test_chunked_prefill_attention_reads_a_stacked_cache_in_place(layer):
    """A chunk against `layer` of the stacked cache at pool row `slot`
    equals the reference on that layer's row."""
    NB, P, Sq, Skv, Hq, Hkv, D = 3, 4, 16, 48, 4, 2, 64
    q = _rnd(1, Sq, Hq, D)
    k = _rnd(NB, P, Skv, Hkv, D)
    v = _rnd(NB, P, Skv, Hkv, D)
    off, lens = jnp.array([16], jnp.int32), jnp.array([30], jnp.int32)
    slot = jnp.array([3], jnp.int32)
    stacked = lambda x: x.reshape(NB, P, Skv, Hkv * D)  # noqa: E731
    out = prefill_attention(q, stacked(k), stacked(v), off, lens,
                            jnp.int32(layer), slot, interpret=True)
    want = ref.chunked_prefill_attention_ref(q, k[layer, slot],
                                             v[layer, slot], off, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_block_skipping():
    """Blocks beyond cur_len are skipped: result must not depend on garbage
    in the dead region."""
    B, L, H, D = 1, 64, 2, 32
    q = _rnd(B, H, D)
    k = _rnd(B, L, H, D)
    v = _rnd(B, L, H, D)
    cur = jnp.array([10], jnp.int32)
    out1 = decode_attention_op(q, _merged(k), _merged(v), cur, block_k=16,
                               interpret=True)
    k2 = k.at[:, 20:].set(jnp.nan)
    v2 = v.at[:, 20:].set(jnp.nan)
    out2 = decode_attention_op(q, _merged(k2), _merged(v2), cur,
                               block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

def _wkv_inputs(B, S, H, K, seed=0):
    rng = np.random.RandomState(seed)
    r = rng.randn(B, S, H, K).astype(np.float32)
    k = rng.randn(B, S, H, K).astype(np.float32)
    v = rng.randn(B, S, H, K).astype(np.float32)
    w = np.exp(-np.exp(rng.randn(B, S, H, K).astype(np.float32) * 0.5 - 1))
    u = rng.randn(H, K).astype(np.float32)
    s0 = rng.randn(B, H, K, K).astype(np.float32)
    return map(jnp.asarray, (r, k, v, w, u, s0))


@pytest.mark.parametrize("B,S,H,K,chunk", [
    (1, 16, 1, 8, 16),
    (2, 37, 2, 16, 16),      # padded tail
    (1, 64, 4, 32, 32),
    (2, 16, 2, 64, 8),
])
def test_wkv6(B, S, H, K, chunk):
    r, k, v, w, u, s0 = _wkv_inputs(B, S, H, K, seed=B * 100 + S)
    y, sT = wkv6_op(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    tr = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    y_ref, sT_ref = ref.wkv6_ref(tr(r), tr(k), tr(v), tr(w), u, s0)
    np.testing.assert_allclose(np.asarray(tr(y)), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref),
                               atol=2e-4, rtol=2e-4)


def test_wkv6_state_carry_composes():
    """Running two halves with state carry == running the whole sequence
    (the chunked-prefill invariant for SSM layers)."""
    B, S, H, K = 1, 32, 2, 16
    r, k, v, w, u, s0 = _wkv_inputs(B, S, H, K, seed=7)
    y_full, sT_full = wkv6_op(r, k, v, w, u, s0, interpret=True)
    half = S // 2
    y1, s_mid = wkv6_op(r[:, :half], k[:, :half], v[:, :half], w[:, :half],
                        u, s0, interpret=True)
    y2, sT = wkv6_op(r[:, half:], k[:, half:], v[:, half:], w[:, half:],
                     u, s_mid, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_full),
                               atol=2e-4, rtol=2e-4)


def test_model_pallas_path_matches_default(monkeypatch):
    """End-to-end: prefill then decode through the cache on the kernel
    path (interpreted) reproduces the jnp model path."""
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.models import decode_step, init_params, init_state, prefill
    for arch in ["llama31_8b", "gemma2_9b", "rwkv6_3b"]:
        cfg = get_config(arch, smoke=True)
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                  cfg.vocab_size)
        lens = jnp.array([12, 9], jnp.int32)
        runs = []
        for interpreted in (False, True):
            monkeypatch.setattr(
                kops, "by_platform",
                lambda kernel, reference, on=interpreted:
                    kernel(True) if on else reference())
            st = init_state(cfg, 2, 32)
            lg, st = prefill(cfg, params, st, toks, lens)
            logits = [lg]
            tok, cur = jnp.argmax(lg, -1).astype(jnp.int32), lens
            for _ in range(3):
                lg, st = decode_step(cfg, params, st, tok, cur)
                logits.append(lg)
                tok, cur = jnp.argmax(lg, -1).astype(jnp.int32), cur + 1
            runs.append(jnp.stack(logits))
        np.testing.assert_allclose(np.asarray(runs[0]), np.asarray(runs[1]),
                                   atol=2e-4, rtol=2e-4)
