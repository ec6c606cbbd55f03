"""Sharding rules, param/state axis trees, and the HLO roofline parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config
from repro.launch.mesh import act_rules, needs_fsdp, param_rules
from repro.launch.roofline import (analyze_hlo, model_flops_estimate,
                                   parse_hlo, _shape_bytes)
from repro.models.params import (abstract_params, abstract_state, param_axes,
                                 state_axes)
from repro.sharding import DEFAULT_RULES, logical_to_pspec


def test_logical_to_pspec_basic():
    rules = {"batch": ("pod", "data"), "heads": ("model",), "embed": ()}
    assert logical_to_pspec(("batch", None, "heads"), rules) \
        == P(("pod", "data"), None, "model")
    assert logical_to_pspec(("embed",), rules) == P()


def test_logical_to_pspec_divisibility_drop():
    """4 KV heads cannot shard over a 16-way model axis -> replicated."""
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rules = {"kv_heads": ("model",)}

    class FakeMesh:
        shape = {"model": 16}
    spec = logical_to_pspec(("kv_heads",), rules, shape=(4,), mesh=FakeMesh())
    assert spec == P()
    spec = logical_to_pspec(("kv_heads",), rules, shape=(32,),
                            mesh=FakeMesh())
    assert spec == P("model")


def test_logical_to_pspec_no_axis_reuse():
    rules = {"batch": ("data",), "ctx": ("data", "model")}
    spec = logical_to_pspec(("batch", "ctx"), rules)
    # "data" already used by batch -> ctx keeps only "model"
    assert spec == P("data", "model")


@pytest.mark.parametrize("arch", ["llama31_8b", "kimi_k2_1t_a32b",
                                  "jamba_v0_1_52b", "rwkv6_3b"])
def test_param_axes_structure_matches_params(arch):
    cfg = get_config(arch, smoke=True)
    pa = abstract_params(cfg)
    ax = param_axes(cfg)
    ta = jax.tree.structure(pa)
    tb = jax.tree.structure(ax, is_leaf=lambda x: isinstance(x, tuple))
    assert ta == tb
    for leaf, axes in zip(jax.tree.leaves(pa),
                          jax.tree.leaves(ax, is_leaf=lambda x:
                                          isinstance(x, tuple))):
        assert len(leaf.shape) == len(axes)


def test_state_axes_structure_matches_state():
    cfg = get_config("jamba_v0_1_52b", smoke=True)
    st = abstract_state(cfg, 2, 8)
    ax = state_axes(cfg, 2, 8)
    assert jax.tree.structure(st) == jax.tree.structure(
        ax, is_leaf=lambda x: isinstance(x, tuple))


def test_needs_fsdp():
    assert needs_fsdp(get_config("kimi_k2_1t_a32b"),
                      INPUT_SHAPES["decode_32k"])
    assert not needs_fsdp(get_config("qwen2_0_5b"),
                          INPUT_SHAPES["decode_32k"])
    # small trains fit TP-only (12 B/param); frontier trains must FSDP
    assert not needs_fsdp(get_config("qwen2_0_5b"), INPUT_SHAPES["train_4k"])
    assert needs_fsdp(get_config("kimi_k2_1t_a32b"),
                      INPUT_SHAPES["train_4k"])
    assert needs_fsdp(get_config("qwen25_32b"), INPUT_SHAPES["train_4k"])


def test_long_context_rules_use_context_parallelism():
    r = act_rules(INPUT_SHAPES["long_500k"], multi_pod=False)
    assert r["batch"] == ()
    assert "data" in r["ctx"]


# ---------------------------------------------------------------------------
# HLO roofline parser
# ---------------------------------------------------------------------------

HLO_SAMPLE = """\
HloModule test, num_partitions=8

%body.1 (p: (s32[], f32[4,64])) -> (s32[], f32[4,64]) {
  %p = (s32[], f32[4,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4,64]{1,0} get-tuple-element(%p), index=1
  %ag = f32[4,256]{1,0} all-gather(%x), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  %w = f32[256,64]{1,0} constant({...})
  %dot = f32[4,64]{1,0} dot(%ag, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[4,64]{1,0}) tuple(%i, %dot)
}

%cond.1 (p2: (s32[], f32[4,64])) -> pred[] {
  %p2 = (s32[], f32[4,64]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[4,64]) -> f32[4,64] {
  %a = f32[4,64]{1,0} parameter(0)
  %c = s32[] constant(0)
  %tup = (s32[], f32[4,64]{1,0}) tuple(%c, %a)
  %wh = (s32[], f32[4,64]{1,0}) while(%tup), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[4,64]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_parse_hlo_trip_counts():
    mod = parse_hlo(HLO_SAMPLE)
    assert mod.entry == "main"
    mult = mod.multipliers()
    assert mult["body.1"] == 7.0


def test_analyze_hlo_flops_and_collectives():
    counts = analyze_hlo(HLO_SAMPLE)
    # dot: 2 * |out|(4*64) * K(256) per iteration x 7
    assert counts.flops == pytest.approx(2 * 4 * 64 * 256 * 7)
    # all-gather output 4*256*4B x 7 iterations
    assert counts.collective_bytes == pytest.approx(4 * 256 * 4 * 7)
    assert counts.collective_counts["all-gather"] == 7


def test_shape_bytes():
    assert _shape_bytes("f32[4,64]{1,0}") == 4 * 64 * 4
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
    assert _shape_bytes("pred[7]") == 7


def test_model_flops_estimate():
    cfg = get_config("llama31_8b")
    tr = model_flops_estimate(cfg, INPUT_SHAPES["train_4k"])
    de = model_flops_estimate(cfg, INPUT_SHAPES["decode_32k"])
    n = cfg.param_counts()["active"]
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert de == pytest.approx(2 * n * 128)


def test_moe_active_vs_total_params():
    cfg = get_config("kimi_k2_1t_a32b")
    pc = cfg.param_counts()
    assert pc["total"] > 0.9e12            # the 1T class
    assert pc["active"] < 0.05 * pc["total"]   # top-8 of 384


def test_dryrun_results_file_complete():
    """The sweep artifact must cover every (arch x shape x mesh) pair with
    either ok or a documented skip (deliverable e)."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..",
                        "results_dryrun.jsonl")
    if not os.path.exists(path):
        pytest.skip("dry-run sweep not yet executed")
    recs = {}
    for line in open(path):
        r = json.loads(line)
        recs[(r["arch"], r["shape"], r["mesh"])] = r["status"]
    from repro.configs import ARCH_IDS
    missing, errors = [], []
    for arch in ARCH_IDS:
        for shape in INPUT_SHAPES:
            for mesh in ("16x16", "2x16x16"):
                s = recs.get((arch, shape, mesh))
                if s is None:
                    missing.append((arch, shape, mesh))
                elif s == "error":
                    errors.append((arch, shape, mesh))
    assert not missing, missing
    assert not errors, errors
