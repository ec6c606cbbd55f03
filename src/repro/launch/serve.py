"""Serving launcher: the TokenScale PD-disaggregated pipeline on real engines.

Builds a ``PDCluster`` (prefillers, decoders and Convertible Decoders over one
copy of the weights, KV handed over through ``kvtransfer``) under the
TokenScale autoscaling policy and the Alg. 1 router, replays a seeded request
mix through it, and prints one JSON summary line:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke

The published config is the default; ``--smoke`` selects the SMOKE preset.
The mix is a steady stream (prompts of 64-1,024 tokens, a few dozen new
tokens each, one arrival every ``GAP_S`` seconds) followed by a burst of long
prompts at one instant, which the burst detector hands to the Convertible
Decoder.  ``PDCluster.run_until_drained`` replays the arrivals: the
cluster's clock advances by the measured wall time of each step and skips
ahead while the cluster is idle.
"""
from __future__ import annotations

import argparse
import json
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import CHIPS, InstanceSpec, TokenScalePolicy, profile
from repro.launch.compile_cache import CompileLog, enable_compile_cache
from repro.models import init_params
from repro.serving import PDCluster, Request

GAP_S = 0.5          # between steady arrivals: a baseline the burst beats
MAX_INSTANCES = 4    # per role; bounds the caches the scaler may allocate
MAX_STEPS = 20_000   # cluster steps before a replay counts as stuck


@dataclass
class Mix:
    """Seeded request mix: (due time, request) pairs in arrival order."""
    arrivals: list[tuple[float, Request]]
    n_burst: int


def make_mix(cfg: ModelConfig, n: int, max_len: int, max_new: int,
             seed: int) -> Mix:
    """n requests: a steady stream, then a quarter of them as a burst of
    long prompts.  Every prompt leaves room for its new tokens in max_len."""
    rng = np.random.RandomState(seed)
    hi = min(1024, max_len - max_new - 1)
    lo = min(64, hi)
    n_burst = max(2, n // 4)
    n_steady = n - n_burst
    arrivals = []
    for i in range(n):
        burst = i >= n_steady
        L = int(rng.randint((3 * hi) // 4 if burst else lo, hi + 1))
        new = int(rng.randint(max(1, max_new // 2), max_new + 1))
        prompt = rng.randint(0, cfg.vocab_size, size=(L,)).astype(np.int32)
        due = GAP_S * (n_steady if burst else i)
        arrivals.append((due, Request(rid=i, prompt=prompt,
                                      max_new_tokens=new)))
    return Mix(arrivals, n_burst)


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="the config's SMOKE preset instead of the "
                         "published one")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=48,
                    help="largest max_new_tokens a request draws")
    ap.add_argument("--slots", type=int, default=16,
                    help="request slots per decoder")
    ap.add_argument("--max-len", type=int, default=2048,
                    help="cache positions per slot")
    ap.add_argument("--chunk-size", type=int, default=256,
                    help="Convertible Decoder prefill chunk")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def run(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    # one prefiller, one decoder and one Convertible Decoder under the
    # TokenScale policy, profiled for the published config on a v5e
    prof = profile(get_config(args.arch), InstanceSpec(CHIPS["v5e"], tp=1))
    cl = PDCluster(cfg, params, TokenScalePolicy(prof, convertible=1),
                   n_prefillers=1, n_decoders=1, n_convertible=1,
                   slots_per_decoder=args.slots, max_len=args.max_len,
                   chunk_size=args.chunk_size, max_instances=MAX_INSTANCES)
    mix = make_mix(cfg, args.requests, args.max_len, args.max_new, args.seed)
    ran = cl.run_until_drained(max_steps=MAX_STEPS, arrivals=mix.arrivals)
    reqs = [r for _, r in mix.arrivals]
    engines = [d.eng for d in cl.decoders + cl.convertibles]
    return {
        "arch": cfg.name,
        "requests": len(reqs),
        "burst_requests": mix.n_burst,
        "completed": sum(len(r.output) == r.max_new_tokens for r in reqs),
        "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
        "tokens_generated": sum(len(r.output) for r in reqs),
        "cluster_steps": ran["steps"],
        "decode_steps": sum(e.steps["decode"] for e in engines),
        "mixed_steps": sum(e.steps["mixed"] for e in engines),
        "kv_transfers": cl.transfers.n_transfers,
        "kv_transfer_bytes": cl.transfers.total_bytes,
        "peak_prefillers": ran["peak_prefillers"],
        "peak_decoders": ran["peak_decoders"],
        "convertibles": len(cl.convertibles),
    }


def main(argv=None):
    args = parser().parse_args(argv)
    enable_compile_cache()
    log = CompileLog()
    rec = run(args)
    print(json.dumps({**rec, "compile_s": log.total()}))


if __name__ == "__main__":
    main()
