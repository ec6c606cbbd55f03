"""Hypothesis property tests on system invariants."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.convertible import burst_ratio_of_trace
from repro.core.predictor import OutputPredictor
from repro.core.velocity import (BUCKETS, bucket_of,
                                 convertible_prefill_velocity,
                                 reserved_memory)


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 20000), st.integers(1, 2000))
def test_bucket_of_total(in_len, out_len):
    """Every (in, out) maps to exactly one of the 9 buckets."""
    b = bucket_of(in_len, out_len)
    assert b in BUCKETS


@settings(max_examples=30, deadline=None)
@given(st.floats(0.0, 1.0))
def test_predictor_accuracy_converges(acc):
    pred = OutputPredictor(accuracy=acc, seed=1)
    for i in range(400):
        pred.predict_bucket(100 + i % 5000, 50 + i % 500)
    assert abs(pred.measured_accuracy - acc) < 0.1


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 4096), st.integers(0, 512),
       st.floats(0.01, 1.0))
def test_eq5_nonnegative_and_monotone(chunk, batch, slo):
    v = convertible_prefill_velocity(chunk, batch, slo)
    assert v >= 0.0
    assert convertible_prefill_velocity(chunk + 128, batch, slo) >= v


@settings(max_examples=30, deadline=None)
@given(st.floats(0, 1e6), st.floats(0, 1e6), st.floats(0, 10))
def test_eq6_scales_linearly(v, mem_t, slo):
    assert reserved_memory(v, mem_t, slo) == pytest.approx(v * mem_t * slo)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.floats(0, 100), st.floats(1, 1000)),
                min_size=1, max_size=200))
def test_burst_ratio_bounded(arrivals):
    r = burst_ratio_of_trace(arrivals)
    assert 0.0 <= r <= 1.0


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 64), st.integers(1, 64))
def test_data_pipeline_pure_function_of_index(idx, b, s):
    from repro.training import DataConfig, PackedDataset
    dc = DataConfig(vocab_size=128, seq_len=s, global_batch=b, seed=3)
    t1, l1 = PackedDataset(dc).batch(idx)
    t2, l2 = PackedDataset(dc).batch(idx)
    assert np.array_equal(t1, t2)
    assert t1.shape == (b, s) and l1.shape == (b, s)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 10), st.integers(1, 2),
       st.sampled_from([8, 16]))
def test_wkv6_zero_key_is_identity(b, s, h, k):
    """k=0 writes nothing: state must equal decayed initial state."""
    import jax.numpy as jnp
    from repro.kernels.ops import wkv6_op
    rng = np.random.RandomState(b * s)
    r = jnp.asarray(rng.randn(b, s, h, k).astype(np.float32))
    kk = jnp.zeros((b, s, h, k), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, k).astype(np.float32))
    w = jnp.full((b, s, h, k), 0.5, jnp.float32)
    u = jnp.asarray(rng.randn(h, k).astype(np.float32))
    s0 = jnp.asarray(rng.randn(b, h, k, k).astype(np.float32))
    y, sT = wkv6_op(r, kk, v, w, u, s0, interpret=True)
    want = np.asarray(s0) * (0.5 ** s)
    np.testing.assert_allclose(np.asarray(sT), want, atol=1e-4, rtol=1e-4)


def test_scheduler_never_oversubscribes_slots():
    """Engine invariant: active slots never exceed num_slots, queue drains."""
    import jax
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import Engine, Request
    cfg = get_config("qwen2_0_5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, num_slots=2, max_len=48)
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=(int(rng.randint(3, 20)),)
                                       ).astype(np.int32),
                    max_new_tokens=4)
            for i in range(7)]
    for r in reqs:
        eng.add_request(r)
        assert int(eng.active.sum()) <= 2
    steps = 0
    while eng.active.any() or eng.waiting or eng.pending_chunked:
        eng.step()
        assert int(eng.active.sum()) <= 2
        steps += 1
        assert steps < 500
    assert all(len(r.output) == 4 for r in reqs)
