import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every other import: jax locks the device count at first
# init, and the production meshes below need 512 host placeholder devices.

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, SPMD-partitions and compiles, and extract the roofline
terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results.jsonl

No arrays are ever allocated: parameters, optimizer state, caches and
inputs are ShapeDtypeStructs; .lower().compile() exercises the full XLA
SPMD pipeline (sharding propagation, collective insertion, memory
assignment) without touching device memory.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, INPUT_SHAPES, InputShape, ModelConfig,
                           get_config, input_specs, long_context_variant)
from repro.launch.mesh import (act_rules, batch_axes,
                               make_production_mesh, needs_fsdp, param_rules)
from repro.launch.roofline import (Roofline, analyze_hlo,
                                   model_flops_estimate)
from repro.models import decode_step, prefill
from repro.models.params import (abstract_params, abstract_state, param_axes,
                                 state_axes)
from repro.sharding import axis_rules, pspec_tree_from_logical
from repro.training import AdamWConfig, adamw_init, make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P


def _named(tree_axes, tree_abs, mesh, rules):
    specs = pspec_tree_from_logical(tree_axes, rules, tree_abs, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _input_shardings(specs: dict, mesh, b_axes) -> dict:
    out = {}
    for k, v in specs.items():
        if v.ndim == 0 or v.shape[0] % max(_axsize(mesh, b_axes), 1) != 0:
            out[k] = NamedSharding(mesh, P())
        else:
            out[k] = NamedSharding(mesh, P(b_axes))
    return out


def _axsize(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def opt_config_for(cfg: ModelConfig) -> AdamWConfig:
    n = cfg.param_counts()["total"]
    if n > 2e11:
        # frontier-scale: bf16 + factored second moment (DESIGN.md)
        return AdamWConfig(moment_dtype="bfloat16", factored=True)
    return AdamWConfig()


def build_dryrun(arch: str, shape_name: str, multi_pod: bool,
                 fsdp: Optional[bool] = None,
                 rules_override: Optional[dict] = None,
                 remat: bool = True, kv8: bool = False):
    """Returns (fn, args_abstract, in_shardings, cfg, mesh) or None if the
    (arch, shape) pair is skipped (long_500k on pure full-attention)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    if kv8:
        cfg = cfg.replace(kv_cache_dtype="int8")
    if shape_name == "long_500k":
        cfg = long_context_variant(cfg)
        if cfg is None:
            return None
    mesh = make_production_mesh(multi_pod=multi_pod)
    b_axes = batch_axes(multi_pod)
    rules_a = act_rules(shape, multi_pod)
    rules_p = param_rules(cfg, shape, multi_pod, fsdp=fsdp)
    if rules_override:
        rules_a = {**rules_a, **rules_override.get("act", {})}
        rules_p = {**rules_p, **rules_override.get("param", {})}

    params_abs = abstract_params(cfg)
    params_sh = _named(param_axes(cfg), params_abs, mesh, rules_p)
    ins = input_specs(cfg, shape)
    ins_sh = _input_shardings(ins, mesh, b_axes)
    if shape.kind == "decode" and shape.global_batch == 1:
        ins_sh = {k: NamedSharding(mesh, P()) for k in ins}

    if shape.kind == "train":
        ocfg = opt_config_for(cfg)
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, ocfg), params_abs)
        # moments shard like their parameters (factored leaves: drop the
        # reduced axis from the param spec)
        opt_sh = _opt_shardings(params_sh, params_abs, opt_abs, mesh)
        step = make_train_step(cfg, ocfg, donate=False, remat=remat)

        def fn(params, opt, tokens, labels, image_embeds=None):
            if image_embeds is None:
                return step(params, opt, tokens, labels)
            return step(params, opt, tokens, labels, image_embeds)

        args = [params_abs, opt_abs, ins["tokens"], ins["labels"]]
        shards = [params_sh, opt_sh, ins_sh["tokens"], ins_sh["labels"]]
        if "image_embeds" in ins:
            args.append(ins["image_embeds"])
            shards.append(ins_sh["image_embeds"])
        return (fn, tuple(args), tuple(shards), cfg, mesh, rules_a, shape)

    # serving shapes need the cache/state
    max_len = shape.seq_len
    state_abs = abstract_state(cfg, shape.global_batch, max_len)
    state_sh = _named(state_axes(cfg, shape.global_batch, max_len),
                      state_abs, mesh, rules_a)
    if shape.kind == "prefill":
        def fn(params, state, tokens, lengths, image_embeds=None):
            return prefill(cfg, params, state, tokens, lengths,
                           image_embeds=image_embeds)

        args = [params_abs, state_abs, ins["tokens"], ins["lengths"]]
        shards = [params_sh, state_sh, ins_sh["tokens"], ins_sh["lengths"]]
        if "image_embeds" in ins:
            args.append(ins["image_embeds"])
            shards.append(ins_sh["image_embeds"])
        return (fn, tuple(args), tuple(shards), cfg, mesh, rules_a, shape)

    def fn(params, state, last_tokens, cur_lens):
        return decode_step(cfg, params, state, last_tokens, cur_lens)

    args = (params_abs, state_abs, ins["last_tokens"], ins["cur_lens"])
    shards = (params_sh, state_sh, ins_sh["last_tokens"],
              ins_sh["cur_lens"])
    return (fn, args, shards, cfg, mesh, rules_a, shape)


def _opt_shardings(params_sh, params_abs, opt_abs, mesh):
    """Moments shard like their params; factored (tuple) leaves drop the
    last / second-to-last spec entry respectively.  Specs are padded to
    the parameter rank first (canonical PartitionSpecs trim trailing
    Nones, which would break positional slicing)."""
    def _padded(psh, rank):
        spec = list(psh.spec) + [None] * (rank - len(psh.spec))
        return spec

    def v_like(psh, pabs, leaf):
        if isinstance(leaf, tuple):
            spec = _padded(psh, len(pabs.shape))
            row = P(*spec[:-1])
            col = P(*(spec[:-2] + spec[-1:]))
            return (NamedSharding(mesh, row), NamedSharding(mesh, col))
        return psh

    import repro.training.optimizer as _o
    return _o.AdamWState(
        step=NamedSharding(mesh, P()),
        m=jax.tree.map(lambda psh, _: psh, params_sh, opt_abs.m),
        v=jax.tree.map(v_like, params_sh, params_abs, opt_abs.v,
                       is_leaf=lambda x: isinstance(x, NamedSharding)))


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            fsdp: Optional[bool] = None, verbose: bool = True,
            rules_override: Optional[dict] = None,
            remat: bool = True, kv8: bool = False) -> Optional[dict]:
    built = build_dryrun(arch, shape_name, multi_pod, fsdp, rules_override,
                         remat=remat, kv8=kv8)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if built is None:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped",
               "reason": "long_500k requires sub-quadratic attention "
                         "(pure full-attention arch, see DESIGN.md)"}
        if verbose:
            print(json.dumps(rec))
        return rec
    fn, args, shards, cfg, mesh, rules_a, shape = built
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            with axis_rules(rules_a, mesh):
                lowered = jax.jit(fn, in_shardings=shards).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):     # older JAX: list of one dict
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        counts = analyze_hlo(hlo)
        chips = mesh.devices.size
        rf = Roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            flops_per_chip=counts.flops,
            hbm_bytes_per_chip=counts.hbm_bytes,
            collective_bytes_per_chip=counts.collective_bytes,
            model_flops=model_flops_estimate(cfg, shape),
            memory_stats={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            } if mem else None,
            collectives={k: {"bytes": v,
                             "count": counts.collective_counts[k]}
                         for k, v in counts.collectives.items()},
            cost_analysis_flops=float(cost.get("flops", 0.0)),
        )
        rec = {"status": "ok", "t_lower_s": round(t_lower, 2),
               "t_compile_s": round(t_compile, 2),
               "hlo_bytes": len(hlo), **rf.row()}
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    if verbose:
        slim = {k: v for k, v in rec.items() if k != "trace"}
        print(json.dumps(slim, default=str))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16 (256)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) pair")
    ap.add_argument("--fsdp", default=None, choices=["on", "off", None])
    ap.add_argument("--no-remat", action="store_true",
                    help="disable activation checkpointing (train shapes)")
    ap.add_argument("--kv8", action="store_true",
                    help="int8 KV cache (beyond-paper decode optimization)")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    fsdp = {"on": True, "off": False, None: None}[args.fsdp]

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_one(arch, shape, multi_pod=mp, fsdp=fsdp,
                              remat=not args.no_remat, kv8=args.kv8)
                records.append(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec, default=str) + "\n")
    n_err = sum(1 for r in records if r["status"] == "error")
    print(f"# {len(records)} runs, {n_err} errors", file=sys.stderr)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
