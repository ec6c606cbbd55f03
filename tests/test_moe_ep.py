"""Expert-parallel MoE (shard_map) vs the dense-masked reference path.

The multi-device case runs in a subprocess (XLA device count is locked at
first init; smoke tests must keep seeing 1 device — see conftest).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import forward_train, init_params

_SUB = r"""
import os, dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import init_params, forward_train
from repro.sharding import axis_rules

cfg = get_config("kimi_k2_1t_a32b", smoke=True)
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
params = init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
ref, aux_ref = forward_train(cfg, params, toks)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = {"batch": ("data",), "experts": ("model",), "heads": ("model",),
         "kv_heads": ("model",), "ff": ("model",), "vocab": ("model",),
         "embed": (), "ctx": (), "kv_lora": (), "seq": (), "state": ()}
with axis_rules(rules, mesh):
    with jax.set_mesh(mesh):
        out, aux = jax.jit(lambda p, t: forward_train(cfg, p, t))(params, toks)
err = float(jnp.abs(out - ref).max())
assert err < 1e-4, f"EP path diverged: {err}"
print("EP_OK", err)
"""


@pytest.mark.slow
def test_moe_ep_matches_dense_multidevice():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", _SUB], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EP_OK" in r.stdout


def test_moe_capacity_drops_tokens_gracefully():
    """With a tiny capacity factor outputs stay finite (dropped tokens fall
    back to the shared expert / residual) — GShard semantics."""
    import dataclasses
    cfg = get_config("kimi_k2_1t_a32b", smoke=True)
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=0.1))
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    logits, aux = forward_train(cfg, params, toks)
    assert bool(jnp.isfinite(logits).all())


def test_router_aux_loss_increases_with_imbalance():
    from repro.models.ops import _router
    cfg = get_config("kimi_k2_1t_a32b", smoke=True)
    d, e = cfg.d_model, cfg.moe.num_experts
    key = jax.random.PRNGKey(0)
    # positive activations so the skewed column is dominant for EVERY token
    h = jnp.abs(jax.random.normal(key, (1, 32, d), jnp.float32))
    balanced = {"router": jnp.zeros((d, e), jnp.float32)
                + 1e-3 * jax.random.normal(key, (d, e))}
    skew = jnp.zeros((d, e), jnp.float32).at[:, 0].set(5.0)
    skewed = {"router": skew}
    _, _, aux_b = _router(cfg, balanced, h)
    _, _, aux_s = _router(cfg, skewed, h)
    assert float(aux_s) > float(aux_b)
