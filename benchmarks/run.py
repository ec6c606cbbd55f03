"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig9 fig14 # a subset
    PYTHONPATH=src python -m benchmarks.run --engine=events fig9
                                                       # event-driven engine
    PYTHONPATH=src python -m benchmarks.run --engine=events --bench=tails
                                 # per-priority-class p99/p999 tail rows
    PYTHONPATH=src python -m benchmarks.run --spec=my_experiment.json
                                 # a declarative ExperimentSpec file

Each benchmark prints ``name,metric,value`` CSV rows (plus section
headers).  Simulation benches replay bursty traces through the real
TokenScale control plane on the analytic cluster model; the micro bench
times the real JAX engines on whatever backend JAX finds.  Its numbers are
host wall time: on a CPU they time the jnp path and say nothing about a
device.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import (CHIPS, ExperimentSpec, InstanceSpec, OutputPredictor,
                        TokenScalePolicy, plan_convertible, profile)
from repro.core.autoscaler import ComboPolicy
from repro.core.velocity import BUCKETS
from repro.sim import get_trace, step_trace
from repro.sim.runner import (compare_engines, compare_policies, get_engine,
                              hetero_demo_spec, make_policy, run_policy,
                              run_spec)

ROWS: list[str] = []

# simulation engine used by every sim-shaped bench; --engine=events switches
# the whole harness to the discrete-event simulator (DESIGN.md)
ENGINE = "fluid"

# flight-recorder output base path (--trace-out=PATH); when set, the obs
# bench and --spec runs attach a recorder and write JSONL (+ Chrome-trace
# JSON) traces next to it.  None = telemetry off everywhere (default).
TRACE_OUT = None


def _trace_paths(tag: str) -> tuple[str, str]:
    """Derive per-run trace paths from --trace-out: base-<tag>.jsonl plus
    the Perfetto-loadable base-<tag>.chrome.json."""
    base, ext = os.path.splitext(TRACE_OUT)
    return (f"{base}-{tag}{ext or '.jsonl'}", f"{base}-{tag}.chrome.json")


def emit(bench: str, metric: str, value):
    if isinstance(value, float):
        value = f"{value:.6g}"
    row = f"{bench},{metric},{value}"
    ROWS.append(row)
    print(row, flush=True)


# ---------------------------------------------------------------------------
# Fig. 2/3 — burstiness of the traces + overprovisioning sweep
# ---------------------------------------------------------------------------

def fig3_overprovisioning():
    """% of tokens/requests beyond an X-times-average provisioned system."""
    for trace_name in ["azure_conv", "azure_code", "burstgpt1", "burstgpt2"]:
        trace = get_trace(trace_name, duration_s=300.0, rps=10.0, seed=0)
        ts = np.array([r.t for r in trace])
        toks = np.array([float(r.in_len) for r in trace])
        grid_n = 301
        per_sec_req = np.zeros(grid_n)
        per_sec_tok = np.zeros(grid_n)
        idx = np.clip(ts.astype(int), 0, grid_n - 1)
        np.add.at(per_sec_req, idx, 1.0)
        np.add.at(per_sec_tok, idx, toks)
        for x in (1, 2, 3, 4):
            cap_r = per_sec_req.mean() * x
            cap_t = per_sec_tok.mean() * x
            br = np.maximum(per_sec_req - cap_r, 0).sum() / per_sec_req.sum()
            bt = np.maximum(per_sec_tok - cap_t, 0).sum() / per_sec_tok.sum()
            emit("fig3", f"{trace_name},overprov={x}x,req_burst_pct",
                 100 * br)
            emit("fig3", f"{trace_name},overprov={x}x,tok_burst_pct",
                 100 * bt)


# ---------------------------------------------------------------------------
# Table II — per-bucket decode Token Velocity (+ Fig. 7 characterization)
# ---------------------------------------------------------------------------

def table2_velocities():
    for model, tp in [("llama31_8b", 1), ("qwen25_32b", 4)]:
        cfg = get_config(model)
        prof = profile(cfg, InstanceSpec(CHIPS["a100"], tp=tp))
        for b in BUCKETS:
            emit("table2", f"{cfg.name},tp={tp},a100,{b},v_decode",
                 prof.v_decode[b])
        emit("table2", f"{cfg.name},tp={tp},a100,v_prefill", prof.v_prefill)
        emit("table2", f"{cfg.name},tp={tp},a100,v_network", prof.v_network)


def fig7_characterization():
    for chip in ["a100", "h100", "v5e"]:
        for model in ["llama31_8b", "qwen25_32b"]:
            cfg = get_config(model)
            prof = profile(cfg, InstanceSpec(CHIPS[chip], tp=1))
            vd = sorted(prof.v_decode.values())
            emit("fig7", f"{cfg.name},{chip},v_prefill", prof.v_prefill)
            emit("fig7", f"{cfg.name},{chip},v_network", prof.v_network)
            emit("fig7", f"{cfg.name},{chip},v_decode_min", vd[0])
            emit("fig7", f"{cfg.name},{chip},v_decode_max", vd[-1])


# ---------------------------------------------------------------------------
# Fig. 9 — end-to-end SLO attainment vs GPU usage
# ---------------------------------------------------------------------------

def fig9_end_to_end(model="llama31_8b", tp=1, tag="small",
                    duration=120.0, rps=10.0):
    for trace in ["azure_conv", "azure_code", "mixed"]:
        reps = compare_policies(trace, model=model, tp=tp,
                                duration=duration, rps=rps, seed=0,
                                engine=ENGINE)
        for name, r in reps.items():
            emit("fig9", f"{tag},{trace},{name},slo_pct",
                 100 * r.slo_attainment())
            emit("fig9", f"{tag},{trace},{name},ttft_pct",
                 100 * r.ttft_attainment())
            emit("fig9", f"{tag},{trace},{name},tpot_pct",
                 100 * r.tpot_attainment())
            emit("fig9", f"{tag},{trace},{name},avg_gpus", r.avg_gpus())


def fig9b_large_model():
    fig9_end_to_end(model="qwen25_32b", tp=4, tag="large",
                    duration=90.0, rps=6.0)


# ---------------------------------------------------------------------------
# Fig. 10 — burst adaptation timeline (10x RPS step at t=10 s)
# ---------------------------------------------------------------------------

def fig10_burst_adaptation():
    for pol in ["tokenscale", "distserve", "aibrix", "blitzscale"]:
        rep = _run_step_trace(pol)
        burst_ttfts = [r.ttft for r in rep.requests
                       if 10.0 <= r.src.t < 14.0 and r.t_first_token >= 0]
        post = [r.ttft for r in rep.requests
                if 16.0 <= r.src.t < 25.0 and r.t_first_token >= 0]
        emit("fig10", f"{pol},burst_ttft_p99_ms",
             1e3 * float(np.percentile(burst_ttfts, 99))
             if burst_ttfts else -1.0)
        emit("fig10", f"{pol},post_burst_ttft_p99_ms",
             1e3 * float(np.percentile(post, 99)) if post else -1.0)


def _run_step_trace(policy_name: str):
    cfg = get_config("llama31_8b")
    inst = InstanceSpec(CHIPS["a100"], tp=1)
    prof = profile(cfg, inst)
    # 20x step: the burst exceeds one prefiller's velocity while instance
    # startup (5 s) is longer than the burst itself — only a standing
    # rapid-response buffer (the Convertible Decoder) can absorb it
    trace = step_trace(30.0, base_rps=1.0, burst_rps=20.0, burst_start=10.0,
                       burst_len=4.0, seed=3)
    policy = make_policy(policy_name, prof, 1, trace=trace)
    conv = plan_convertible(cfg, inst, 32, 1200.0, 0.2, 8)
    n_conv = 1 if policy_name == "tokenscale" else 0
    cl = get_engine(ENGINE)(cfg, inst, prof, policy,
                           OutputPredictor(0.85, 3),
                           conv_cfg=conv, n_convertible=n_conv)
    return cl.run(trace, 30.0)


# ---------------------------------------------------------------------------
# Fig. 11 — provisioned vs required instances (Pearson correlation)
# ---------------------------------------------------------------------------

def fig11_provision_correlation():
    """Provisioned vs required instance counts under large load swings
    (5->25->10->35->8 RPS); Pearson r per policy.  Requirement series is
    the ground-truth velocity quotient with TRUE lengths; both series are
    5 s-smoothed (the provisioning loop runs at 1 s + hysteresis)."""
    from repro.core import (OutputPredictor, bucket_of, plan_convertible)
    from repro.sim.runner import make_policy
    from repro.sim.traces import varying_rate_trace
    cfg = get_config("llama31_8b")
    inst = InstanceSpec(CHIPS["a100"], tp=1)
    prof = profile(cfg, inst)
    segments = [(40.0, 5.0), (40.0, 25.0), (40.0, 10.0), (40.0, 35.0),
                (40.0, 8.0)]
    trace = varying_rate_trace(segments, seed=0)
    T = int(sum(d for d, _ in segments)) + 1
    req_p = np.zeros(T)
    req_d = np.zeros(T)
    for r in trace:
        i = min(int(r.t), T - 1)
        req_p[i] += r.in_len / prof.v_prefill
        b = bucket_of(r.in_len, r.out_len)
        req_d[i] += (r.in_len + r.out_len) / prof.v_decode[b]

    def smooth(x, w=5):
        return np.convolve(x, np.ones(w) / w, mode="same")

    conv = plan_convertible(cfg, inst, 32, 1200.0, 0.2, 8)
    for pol in ["tokenscale", "distserve", "aibrix", "blitzscale"]:
        policy = make_policy(pol, prof, 1, trace=trace)
        cl = get_engine(ENGINE)(cfg, inst, prof, policy,
                               OutputPredictor(0.85, 0), conv_cfg=conv,
                               n_convertible=1 if pol == "tokenscale" else 0)
        rep = cl.run(list(trace), float(T - 1))
        prov_p = np.zeros(T)
        prov_d = np.zeros(T)
        cnt = np.zeros(T) + 1e-9
        for snap in rep.timeline:
            i = min(int(snap["t"]), T - 1)
            prov_p[i] += snap["prefillers"]
            prov_d[i] += snap["decoders"] + snap["convertibles"]
            cnt[i] += 1
        prov_p /= cnt
        prov_d /= cnt
        n = T - 2
        rp = float(np.corrcoef(smooth(req_p)[:n], smooth(prov_p)[:n])[0, 1])
        rd = float(np.corrcoef(smooth(req_d)[:n], smooth(prov_d)[:n])[0, 1])
        emit("fig11", f"{pol},pearson_prefill", rp)
        emit("fig11", f"{pol},pearson_decode", rd)


# ---------------------------------------------------------------------------
# Fig. 12 — output-predictor accuracy sweep
# ---------------------------------------------------------------------------

def fig12_predictor_accuracy():
    for acc in [1.0, 0.85, 0.7, 0.5]:
        rep = run_policy("tokenscale", "mixed", duration=90.0, rps=8.0,
                         seed=2, predictor_accuracy=acc, engine=ENGINE)
        emit("fig12", f"acc={acc},slo_pct", 100 * rep.slo_attainment())
        emit("fig12", f"acc={acc},avg_gpus", rep.avg_gpus())


# ---------------------------------------------------------------------------
# Fig. 13 — number of Convertible Decoders
# ---------------------------------------------------------------------------

def fig13_convertible_count():
    for n in [0, 1, 2, 3]:
        rep = run_policy("tokenscale", "mixed", duration=90.0, rps=8.0,
                         seed=1, n_convertible=n, engine=ENGINE)
        emit("fig13", f"n_convertible={n},slo_pct",
             100 * rep.slo_attainment())
        emit("fig13", f"n_convertible={n},ttft_pct",
             100 * rep.ttft_attainment())


# ---------------------------------------------------------------------------
# Fig. 14 — ablation: B -> B+P -> B+P+D -> TokenScale
# ---------------------------------------------------------------------------

def fig14_ablation():
    cfg = get_config("llama31_8b")
    inst = InstanceSpec(CHIPS["a100"], tp=1)
    prof = profile(cfg, inst)
    trace = get_trace("mixed", duration_s=120.0, rps=10.0, seed=0)

    def ds():
        return make_policy("distserve", prof, 0, trace=trace)

    def ts():
        return TokenScalePolicy(prof, convertible=0)

    variants = {
        "B": (ds(), 0),
        "B+P": (ComboPolicy(ts(), ds(), "B+P"), 0),
        "B+P+D": (ComboPolicy(ts(), ts(), "B+P+D"), 0),
        "TokenScale": (TokenScalePolicy(prof, convertible=1), 1),
    }
    conv = plan_convertible(cfg, inst, 32, 1200.0, 0.2, 8)
    for name, (policy, n_conv) in variants.items():
        cl = get_engine(ENGINE)(cfg, inst, prof, policy,
                               OutputPredictor(0.85, 0),
                               conv_cfg=conv, n_convertible=n_conv)
        rep = cl.run(list(trace), 150.0)
        emit("fig14", f"{name},slo_pct", 100 * rep.slo_attainment())
        emit("fig14", f"{name},ttft_pct", 100 * rep.ttft_attainment())
        emit("fig14", f"{name},tpot_pct", 100 * rep.tpot_attainment())
        emit("fig14", f"{name},avg_gpus", rep.avg_gpus())


# ---------------------------------------------------------------------------
# Fig. 15 — generality on H100
# ---------------------------------------------------------------------------

def fig15_h100():
    for trace in ["azure_conv", "azure_code", "mixed"]:
        for pol in ["tokenscale", "distserve"]:
            rep = run_policy(pol, trace, chip="h100", duration=90.0,
                             rps=10.0, seed=0, engine=ENGINE)
            emit("fig15", f"h100,{trace},{pol},slo_pct",
                 100 * rep.slo_attainment())
            emit("fig15", f"h100,{trace},{pol},avg_gpus", rep.avg_gpus())


# ---------------------------------------------------------------------------
# Engine micro-benchmarks (host wall time; us_per_call)
# ---------------------------------------------------------------------------

def engine_microbench():
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import decode_step, init_params, init_state, prefill
    enable_compile_cache()
    cfg = get_config("llama31_8b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 64
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    lengths = jnp.full((B,), S, jnp.int32)
    st = init_state(cfg, B, S + 32)
    pf = jax.jit(lambda p, s, t, ln: prefill(cfg, p, s, t, ln))
    logits, st = pf(params, st, toks, lengths)     # compile
    t0 = time.perf_counter()
    for _ in range(10):
        logits, _ = pf(params, st, toks, lengths)
    jax.block_until_ready(logits)
    emit("micro", "prefill_us_per_call",
         1e6 * (time.perf_counter() - t0) / 10)

    dc = jax.jit(lambda p, s, t, ln: decode_step(cfg, p, s, t, ln))
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    dl, st2 = dc(params, st, nxt, lengths)
    t0 = time.perf_counter()
    for _ in range(20):
        dl, st2 = dc(params, st2, nxt, lengths + 1)
    jax.block_until_ready(dl)
    emit("micro", "decode_us_per_call",
         1e6 * (time.perf_counter() - t0) / 20)


def sim_throughput():
    t0 = time.perf_counter()
    rep = run_policy("tokenscale", "mixed", duration=60.0, rps=8.0,
                     seed=0, engine=ENGINE)
    dt = time.perf_counter() - t0
    emit("micro", "sim_requests_per_wall_s", len(rep.requests) / dt)


def kv8_velocity():
    """Beyond-paper: the int8 KV cache folded back into TokenScale's own
    math — per-bucket decode Token Velocity roughly doubles, so Eq. 3
    provisions ~half the decoders for the same arrival rates, and the
    end-to-end sim serves the same trace with fewer GPUs."""
    cfg16 = get_config("llama31_8b")
    cfg8 = cfg16.replace(kv_cache_dtype="int8")
    inst = InstanceSpec(CHIPS["a100"], tp=1)
    p16 = profile(cfg16, inst)
    p8 = profile(cfg8, inst)
    for b in ("S-S", "M-M", "L-L"):
        emit("kv8", f"{b},v_decode_bf16", p16.v_decode[b])
        emit("kv8", f"{b},v_decode_int8", p8.v_decode[b])
        emit("kv8", f"{b},speedup", p8.v_decode[b] / p16.v_decode[b])
    # Eq.3 decoder counts for an identical arrival pattern
    lam = {b: p16.v_decode[b] * 0.8 for b in ("S-S", "M-M", "L-L")}
    import math as _m
    n16 = sum(r / p16.v_decode[b] for b, r in lam.items())
    n8 = sum(r / p8.v_decode[b] for b, r in lam.items())
    emit("kv8", "eq3_decoders_bf16", _m.ceil(n16))
    emit("kv8", "eq3_decoders_int8", _m.ceil(n8))
    # end-to-end: same trace, int8 profile
    r16 = run_policy("tokenscale", "mixed", duration=90.0, rps=10.0,
                     seed=0, prof=p16, engine=ENGINE)
    r8 = run_policy("tokenscale", "mixed", duration=90.0, rps=10.0,
                    seed=0, prof=p8, engine=ENGINE)
    emit("kv8", "e2e_bf16_slo_pct", 100 * r16.slo_attainment())
    emit("kv8", "e2e_bf16_gpus", r16.avg_gpus())
    emit("kv8", "e2e_int8_slo_pct", 100 * r8.slo_attainment())
    emit("kv8", "e2e_int8_gpus", r8.avg_gpus())


def pd_runtime():
    """PD-disaggregated runtime on real engines: measured network-stage
    velocity (the paper's V_N, from actual KVC transfer bytes) for an
    attention arch vs an attention-free SSM."""
    from repro.core import TokenScalePolicy
    from repro.models import init_params
    from repro.serving import PDCluster, Request
    for arch in ["llama31_8b", "rwkv6_3b"]:
        cfg = get_config(arch, smoke=True)
        params = init_params(cfg, jax.random.PRNGKey(0))
        prof = profile(get_config(arch), InstanceSpec(CHIPS["v5e"], 1))
        cl = PDCluster(cfg, params, TokenScalePolicy(prof, convertible=0),
                       n_prefillers=1, n_decoders=1, n_convertible=0,
                       max_len=160)
        rng = np.random.RandomState(0)
        # longer prompts: KVC grows with length, SSM state does not — the
        # §III-C asymmetry needs prompts >> the fixed-state equivalent
        for i in range(6):
            cl.submit(Request(
                rid=i, prompt=rng.randint(0, cfg.vocab_size,
                                          size=(80,)).astype(np.int32),
                max_new_tokens=4))
        cl.run_until_drained()
        emit("pd", f"{arch},kvc_bytes_per_token",
             cl.transfers.bytes_per_token())
        emit("pd", f"{arch},measured_v_network_toks",
             cl.measured_network_velocity())
        emit("pd", f"{arch},transfers", cl.transfers.n_transfers)


def multipod_scaling():
    """Multi-pod (512-chip) vs single-pod (256-chip) roofline terms from
    the dry-run artifact: per-chip terms should ~halve for batch-sharded
    shapes if the 'pod' axis actually shards (deliverable e)."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..",
                        "results_dryrun.jsonl")
    if not os.path.exists(path):
        emit("multipod", "skipped", "results_dryrun.jsonl missing")
        return
    recs = {}
    for line in open(path):
        r = json.loads(line)
        recs[(r["arch"], r["shape"], r["mesh"])] = r
    for arch in ["llama31_8b", "kimi_k2_1t_a32b", "jamba_v0_1_52b",
                 "rwkv6_3b"]:
        for shape in ["train_4k", "decode_32k"]:
            a = recs.get((arch, shape, "16x16"))
            b = recs.get((arch, shape, "2x16x16"))
            if not a or not b or a["status"] != "ok" or b["status"] != "ok":
                continue
            for term in ["t_compute_s", "t_memory_s"]:
                if a[term] > 1e-9:
                    emit("multipod", f"{arch},{shape},{term}_ratio",
                         b[term] / a[term])


# ---------------------------------------------------------------------------
# Differential validation: fluid vs event engine on identical inputs
# ---------------------------------------------------------------------------

def diffval():
    """Agreement between the dt-stepped fluid simulator and the
    discrete-event simulator on throughput / mean TTFT / mean TPOT
    (the bench twin of tests/test_sim_differential.py)."""
    for trace in ["azure_conv", "mixed"]:
        for pol in ["tokenscale", "distserve"]:
            reps = compare_engines(pol, trace, duration=60.0, rps=8.0,
                                   seed=0)
            fl, ev = reps["fluid"], reps["events"]
            emit("diffval", f"{trace},{pol},thr_fluid", fl.throughput())
            emit("diffval", f"{trace},{pol},thr_events", ev.throughput())
            emit("diffval", f"{trace},{pol},ttft_ms_fluid",
                 1e3 * fl.mean("ttft"))
            emit("diffval", f"{trace},{pol},ttft_ms_events",
                 1e3 * ev.mean("ttft"))
            emit("diffval", f"{trace},{pol},tpot_ms_fluid",
                 1e3 * fl.mean("tpot"))
            emit("diffval", f"{trace},{pol},tpot_ms_events",
                 1e3 * ev.mean("tpot"))
            emit("diffval", f"{trace},{pol},ttft_p99_ms_events",
                 1e3 * ev.percentile("ttft", 99))


# ---------------------------------------------------------------------------
# Tails — Fig. 9/10-style p99/p999 at event fidelity, per priority class
# ---------------------------------------------------------------------------

#: the memory-tight fleet where HBM backpressure actually bites: qwen25-32B
#: at TP2 on A100-40G leaves ~6.5 GB of KV headroom (~27 resident requests
#: per decoder) and the 2-instance cap keeps bursts from being absorbed by
#: scale-out — exactly the contention regime preemption policies target.
TAILS_CFG = dict(model="qwen25_32b", tp=2, duration=30.0, rps=8.0, seed=0,
                 max_instances=2)
PREEMPTION_MODES = ["none", "evict-lowest", "pause-requeue"]


def tails():
    """Per-priority-class tail latencies (p99/p999 TTFT, p99 TPOT) and SLO
    attainment for every trace x policy x preemption variant, at event
    fidelity (run with --engine=events; the fluid engine smears exactly the
    tails this bench exists to expose, so it is skipped there — including
    in the no-argument run-everything invocation)."""
    from repro.sim.traces import DEFAULT_PRIORITY_MIX
    if ENGINE != "events":
        emit("tails", "skipped", "needs --engine=events")
        return
    for trace in ["azure_conv", "azure_code", "burstgpt1", "burstgpt2",
                  "mixed"]:
        for pol in ["tokenscale", "distserve", "aibrix", "blitzscale"]:
            for mode in PREEMPTION_MODES:
                rep = run_policy(pol, trace, engine=ENGINE, preemption=mode,
                                 priority_mix=DEFAULT_PRIORITY_MIX,
                                 **TAILS_CFG)
                for cls in rep.priority_classes():
                    pre = f"{trace},{pol},{mode},class{cls}"
                    emit("tails", f"{pre},ttft_p99_ms",
                         1e3 * rep.percentile("ttft", 99, priority=cls))
                    emit("tails", f"{pre},ttft_p999_ms",
                         1e3 * rep.percentile("ttft", 99.9, priority=cls))
                    emit("tails", f"{pre},tpot_p99_ms",
                         1e3 * rep.percentile("tpot", 99, priority=cls))
                    emit("tails", f"{pre},slo_pct",
                         100 * rep.slo_attainment(cls))
                emit("tails", f"{trace},{pol},{mode},preemptions",
                     len(rep.preemptions))


#: the kvtiers contention fleet: qwen25-32B TP2 on A100-40G (2-instance
#: cap) over azure_code — long prompts make a KV recomputation (~2.7K
#: tokens at prefill velocity, plus the prefill backlog the burst itself
#: created) far more expensive than a host-DRAM swap-in at PCIe bandwidth,
#: which is exactly the gap the tiered subsystem exists to expose.
KVTIERS_CFG = dict(model="qwen25_32b", tp=2, duration=30.0, rps=7.0,
                   seed=0, max_instances=2)
KVTIERS_TRACE = "azure_code"
KVTIERS_BLOCK = 16
KVTIERS_SESSIONS = 0.5

#: variant -> (preemption mode, prefix_cache); all run the paged
#: allocator so the comparison isolates the *policy*, not the accounting
KVTIERS_VARIANTS = {
    "none": ("none", False),
    "recompute": ("evict-lowest", False),
    "swap": ("pause-requeue", False),
    "swap+prefix": ("pause-requeue", True),
}


def run_kvtiers_variant(variant: str, duration: float = None,
                        engine: str = "events"):
    """One kvtiers bench cell (shared with the golden regenerator and the
    smoke row, so the fixture and the bench can never drift apart)."""
    from repro.sim.traces import DEFAULT_PRIORITY_MIX
    mode, prefix = KVTIERS_VARIANTS[variant]
    cfg = dict(KVTIERS_CFG)
    if duration is not None:
        cfg["duration"] = duration
    return run_policy("tokenscale", KVTIERS_TRACE, engine=engine,
                      preemption=mode, priority_mix=DEFAULT_PRIORITY_MIX,
                      session_prob=KVTIERS_SESSIONS,
                      block_size=KVTIERS_BLOCK, prefix_cache=prefix, **cfg)


def kvtiers():
    """Tiered-KV ablation on the memory-tight fleet over a session-style
    trace: none / recompute (evict-lowest) / swap (pause-requeue into the
    host-DRAM tier) / swap+prefix (adding copy-on-write prefix reuse).
    Swap must strictly improve the preempted-request p99 TTFT/TPOT over
    recompute, and prefix reuse must cut the prefill-token load (the
    acceptance rows; pinned by tests/golden/kvtiers_session.json).  Always
    runs the event engine — swap completions are exact events there, which
    is the fidelity this bench exists to measure."""
    for variant in KVTIERS_VARIANTS:
        rep = run_kvtiers_variant(variant)
        ks = rep.kv_summary()
        pre = f"{KVTIERS_TRACE},{variant}"
        emit("kvtiers", f"{pre},preemptions", len(rep.preemptions))
        emit("kvtiers", f"{pre},preempted_ttft_p99_ms",
             1e3 * ks["preempted_ttft_p99"])
        emit("kvtiers", f"{pre},preempted_tpot_p99_ms",
             1e3 * ks["preempted_tpot_p99"])
        emit("kvtiers", f"{pre},slo_pct", 100 * rep.slo_attainment())
        emit("kvtiers", f"{pre},prefill_tokens",
             sum(r.src.in_len - r.kv_hit_tokens for r in rep.requests))
        emit("kvtiers", f"{pre},prefix_hit_rate_pct",
             100 * ks["prefix_hit_rate"])
        emit("kvtiers", f"{pre},offload_mb", ks["offload_bytes"] / 1e6)
        emit("kvtiers", f"{pre},swap_outs", ks["swap_outs"])
        emit("kvtiers", f"{pre},swap_fallbacks", ks["swap_fallbacks"])
        emit("kvtiers", f"{pre},swap_stall_ms", 1e3 * ks["swap_stall_s"])
        emit("kvtiers", f"{pre},peak_blocks_frac", ks["peak_blocks_frac"])


#: the deflection fleet: llama31-8B on A100-TP1 driven hard enough that
#: the prefill path saturates during bursts (the 6-instance cap keeps
#: scale-out from absorbing them before the 5 s startup) — the regime
#: where Alg. 1 rounds 1-2 fail and round 2b (chunked deflection onto
#: regular decoders) is the only rapid-response path left.
DEFLECT_CFG = dict(model="llama31_8b", chip="a100", tp=1, duration=60.0,
                   rps=40.0, seed=0, max_instances=6)
DEFLECT_TRACES = ["burstgpt1", "burstgpt2"]
#: variant -> PoolSpec.prefill_chunking (0 = legacy wholesale conversion)
DEFLECT_VARIANTS = {"wholesale": 0, "chunked": 2048}


def run_deflect_variant(variant: str, trace: str = "burstgpt1",
                        duration: float = None, engine: str = "events"):
    """One deflect bench cell (shared with the golden regenerator and the
    smoke row, so the fixture and the bench can never drift apart)."""
    cfg = dict(DEFLECT_CFG)
    if duration is not None:
        cfg["duration"] = duration
    return run_policy("tokenscale", trace, engine=engine,
                      prefill_chunking=DEFLECT_VARIANTS[variant], **cfg)


def deflect():
    """Whole-instance conversion vs chunked prefill deflection on the
    burst traces, at event fidelity (chunk boundaries are exact events
    there — the fluid engine smears exactly the burst-tail TTFTs this
    bench compares).  The acceptance gradient: chunked deflection beats
    wholesale conversion on p99 TTFT on both traces while resident p99
    TPOT stays inside the Eq. 5 budget (pinned by
    tests/golden/deflect_burst.json)."""
    for trace in DEFLECT_TRACES:
        for variant in DEFLECT_VARIANTS:
            rep = run_deflect_variant(variant, trace)
            pre = f"{trace},{variant}"
            emit("deflect", f"{pre},requests", len(rep.requests))
            emit("deflect", f"{pre},ttft_p99_ms",
                 1e3 * rep.percentile("ttft", 99))
            emit("deflect", f"{pre},ttft_p999_ms",
                 1e3 * rep.percentile("ttft", 99.9))
            emit("deflect", f"{pre},tpot_p99_ms",
                 1e3 * rep.percentile("tpot", 99))
            emit("deflect", f"{pre},slo_pct", 100 * rep.slo_attainment())
            emit("deflect", f"{pre},avg_gpus", rep.avg_gpus())
            emit("deflect", f"{pre},deflected", rep.n_deflected)


#: the gateway fleet: the kvtiers contention fleet (qwen25-32B TP2 on
#: A100-40G, 2-instance cap) driven by a hot-system-prompt session trace —
#: 70% of arrivals share one of two Zipf-popular 1K-token system prompts
#: across sessions.  The legacy owner-steering path only sees *session*
#: affinity, so the cross-session prompt reuse is invisible to it; the
#: locality gateway's block-granular hashtrie routes those arrivals to
#: whichever decoder already holds the shared blocks (and replicates the
#: hot prefix when one holder funnels), which is exactly the gap this
#: bench measures.
GATEWAY_CFG = dict(model="qwen25_32b", tp=2, duration=30.0, rps=7.0,
                   seed=0, max_instances=2)
GATEWAY_TRACE = "azure_code"
GATEWAY_BLOCK = 16
GATEWAY_SESSIONS = 0.4
GATEWAY_SHARED = dict(shared_prefix_prob=0.7, shared_prefix_len=1024,
                      shared_prefix_count=2)
#: variant -> (PoolSpec.gateway, PoolSpec.kv_alloc); both run the paged
#: allocator + prefix cache so the comparison isolates *routing* (and the
#: allocate-on-generate paging the gateway enables), not the accounting
GATEWAY_VARIANTS = {"owner": (False, "reserve"),
                    "gateway": (True, "lazy")}


def run_gateway_variant(variant: str, duration: float = None,
                        engine: str = "events"):
    """One gateway bench cell (shared with the golden regenerator and the
    smoke row, so the fixture and the bench can never drift apart)."""
    gw, alloc = GATEWAY_VARIANTS[variant]
    cfg = dict(GATEWAY_CFG)
    if duration is not None:
        cfg["duration"] = duration
    return run_policy("tokenscale", GATEWAY_TRACE, engine=engine,
                      preemption="pause-requeue",
                      session_prob=GATEWAY_SESSIONS,
                      block_size=GATEWAY_BLOCK, prefix_cache=True,
                      gateway=gw, kv_alloc=alloc, **GATEWAY_SHARED, **cfg)


def gateway():
    """KV-locality gateway ablation on the hot-system-prompt session
    trace: legacy owner-steering (session-affinity only, reserve-ahead KV)
    vs the prefix-hashtrie gateway (cross-session locality routing +
    hot-prefix replication + allocate-on-generate paging).  The acceptance
    gradient: the gateway strictly beats owner-steering on p99 TTFT at
    equal-or-lower GPU count, with a strictly higher prefix hit rate
    (pinned by tests/golden/gateway_locality.json).  Event engine by
    default — replication completions and mid-decode OOMs are exact
    events there."""
    for variant in GATEWAY_VARIANTS:
        rep = run_gateway_variant(variant, engine=ENGINE)
        ks = rep.kv_summary()
        pre = f"{GATEWAY_TRACE},{variant}"
        emit("gateway", f"{pre},requests", len(rep.requests))
        emit("gateway", f"{pre},ttft_p99_ms",
             1e3 * rep.percentile("ttft", 99))
        emit("gateway", f"{pre},tpot_p99_ms",
             1e3 * rep.percentile("tpot", 99))
        emit("gateway", f"{pre},slo_pct", 100 * rep.slo_attainment())
        emit("gateway", f"{pre},avg_gpus", rep.avg_gpus())
        emit("gateway", f"{pre},prefix_hit_rate_pct",
             100 * ks["prefix_hit_rate"])
        emit("gateway", f"{pre},peak_blocks_frac", ks["peak_blocks_frac"])
        gw = rep.gw_summary()
        if gw:
            # routing-decision breakdown + replication/paging counters
            emit("gateway", f"{pre},affinity_hits", gw["affinity_hits"])
            emit("gateway", f"{pre},replica_hits", gw["replica_hits"])
            emit("gateway", f"{pre},balanced_fallbacks", gw["balanced"])
            emit("gateway", f"{pre},steered_tokens", gw["steered_tokens"])
            emit("gateway", f"{pre},replications", gw["replications"])
            emit("gateway", f"{pre},replica_mb", gw["replica_bytes"] / 1e6)
            emit("gateway", f"{pre},block_grows", gw["block_grows"])
            emit("gateway", f"{pre},oom_preemptions",
                 gw["oom_preemptions"])


#: the pareto fleet: a two-model cluster on mixed chips.  llama31-8B runs
#: a bursty route on an a100 primary pair plus — for the coordinated
#: planner only — an elastic l40s decode pool (higher decode tokens/s/$
#: than the a100 primary: scale-out placement lands on the cheaper chip).
#: qwen25-32B is the steady background tenant on h100-TP2; both models'
#: convertible pools share (a100, TP2) so burst prefill can spill across
#: models.  The per-model baseline plans the identical initial hardware,
#: minus the elastic pool it cannot express (one pool per role).
PARETO_CFG = dict(duration=120.0, seed=2, max_instances=12,
                  llama_rps=28.0, qwen_rps=2.0, qwen_trace="azure_conv")
PARETO_TRACES = ["burstgpt1", "burstgpt2"]
#: variant -> (policy name, elastic l40s decode pool present)
PARETO_VARIANTS = {"permodel": ("tokenscale", False),
                   "coord": ("tokenscale-coord", True)}


def pareto_fleet_spec(variant: str, trace: str):
    """The shared fleet recipe for one pareto bench cell."""
    from repro.core import FleetSpec, PoolSpec, TraceRoute
    _, elastic = PARETO_VARIANTS[variant]
    pools = [
        PoolSpec("pre-ll", "prefill", "llama31_8b", "a100", 1, init=1),
        PoolSpec("dec-ll", "decode", "llama31_8b", "a100", 1, init=1),
        PoolSpec("conv-ll", "convertible", "llama31_8b", "a100", 2, init=1),
        PoolSpec("pre-qw", "prefill", "qwen25_32b", "h100", 2, init=1),
        PoolSpec("dec-qw", "decode", "qwen25_32b", "h100", 2, init=1),
        PoolSpec("conv-qw", "convertible", "qwen25_32b", "a100", 2, init=2),
    ]
    if elastic:
        pools.insert(2, PoolSpec("dec-ll-l40s", "decode", "llama31_8b",
                                 "l40s", 1, init=0, min=0, max=8))
    routes = (TraceRoute("llama31_8b", trace, rps=PARETO_CFG["llama_rps"]),
              TraceRoute("qwen25_32b", PARETO_CFG["qwen_trace"],
                         rps=PARETO_CFG["qwen_rps"]))
    return FleetSpec(tuple(pools), routes)


def run_pareto_variant(variant: str, trace: str = "burstgpt2",
                       duration: float = None, engine: str = "events",
                       dt: float = None):
    """One pareto bench cell (shared with the golden regenerator and the
    smoke row, so the fixture and the bench can never drift apart).
    ``dt`` overrides the fluid tick (the differential test halves it, as
    in tests/test_sim_differential.py)."""
    policy, _ = PARETO_VARIANTS[variant]
    kw = {"dt": dt} if dt is not None else {}
    spec = ExperimentSpec(
        fleet=pareto_fleet_spec(variant, trace), policy=policy,
        engine=engine, duration=duration or PARETO_CFG["duration"],
        seed=PARETO_CFG["seed"], max_instances=PARETO_CFG["max_instances"],
        **kw)
    return run_spec(spec)


def pareto():
    """Cost-vs-attainment frontier on the mixed-chip two-model fleet, at
    event fidelity: the per-model TokenScale baseline (one pool per role,
    planned independently per model) against the coordinated cross-pool
    planner (cost-ranked placement onto the elastic l40s pool, drain-based
    scale-down, cross-model convertible spill).  The acceptance gradient:
    on the burst traces the coordinated planner Pareto-dominates — SLO
    attainment at least as high at strictly lower ``cost_dollars``
    (pinned by tests/golden/pareto_coord.json)."""
    for trace in PARETO_TRACES:
        for variant in PARETO_VARIANTS:
            rep = run_pareto_variant(variant, trace)
            cs = rep.cost_summary()
            pre = f"{trace},{variant}"
            emit("pareto", f"{pre},requests", len(rep.requests))
            emit("pareto", f"{pre},slo_pct", 100 * rep.slo_attainment())
            emit("pareto", f"{pre},ttft_p99_ms",
                 1e3 * rep.percentile("ttft", 99))
            emit("pareto", f"{pre},cost_dollars", cs["cost_dollars"])
            emit("pareto", f"{pre},cost_per_hour", cs["cost_per_hour"])
            emit("pareto", f"{pre},avg_gpus", rep.avg_gpus())
            for m in rep.models():
                emit("pareto", f"{pre},{m},slo_pct",
                     100 * rep.slo_attainment(model=m))


#: the chaos fleet: the llama31-8B a100 pool under a bursty trace with a
#: full fault mix — two decode crashes (KV purge + resident re-entry),
#: one prefill crash, a prefill straggler window, a swap-bandwidth
#: degradation, and two KVC link outages (sim.faults; every injection
#: lands, none skipped).  The priority mix + evict-lowest preemption
#: compose the shedding path: when crashes cost more capacity than the
#: replacement latency hides, the lowest class absorbs the loss.
CHAOS_CFG = dict(model="llama31_8b", chip="a100", tp=1, duration=60.0,
                 rps=12.0, seed=0)
CHAOS_TRACE = "burstgpt1"
CHAOS_MIX = {0: 0.2, 1: 0.6, 2: 0.2}
CHAOS_FAULTS = dict(seed=0, crashes=3, stragglers=1, swap_degrades=1,
                    link_outages=2, t0=8.0)
#: variant -> FaultConfig.recovery: the same fault schedule with the
#: self-healing control plane on vs blind (husks keep billing + counting,
#: residents re-enter only on client timeout)
CHAOS_VARIANTS = {"recovery": True, "norecovery": False}


def run_chaos_variant(variant: str, duration: float = None,
                      engine: str = "events"):
    """One chaos bench cell (shared with the golden regenerator and the
    smoke row, so the fixture and the bench can never drift apart)."""
    cfg = dict(CHAOS_CFG)
    if duration is not None:
        cfg["duration"] = duration
    return run_policy("tokenscale", CHAOS_TRACE, engine=engine,
                      preemption="evict-lowest", priority_mix=CHAOS_MIX,
                      block_size=16, prefix_cache=True,
                      faults=dict(CHAOS_FAULTS,
                                  recovery=CHAOS_VARIANTS[variant]), **cfg)


def chaos():
    """Fault injection with vs without the self-healing control plane,
    on the identical seeded fault schedule, through both engines.  The
    acceptance gradient (pinned by tests/golden/chaos_recovery.json):
    recovery-on strictly beats recovery-off on class-0 SLO attainment
    AND p99 TTFT on both engines — detection + warm replacement + KVC
    retry/fallback + prefix-reuse re-entry together beat a control plane
    that only sees the damage through lagging queue signals."""
    for engine in ("events", "fluid"):
        for variant in CHAOS_VARIANTS:
            rep = run_chaos_variant(variant, engine=engine)
            fs = rep.fault_summary()
            c0 = rep.class_summary(0)
            pre = f"{CHAOS_TRACE},{engine},{variant}"
            emit("chaos", f"{pre},requests", len(rep.requests))
            emit("chaos", f"{pre},slo_pct", 100 * rep.slo_attainment())
            emit("chaos", f"{pre},class0_slo_pct",
                 100 * c0["slo_attainment"])
            emit("chaos", f"{pre},class0_ttft_p99_ms",
                 1e3 * c0["ttft_p99"])
            emit("chaos", f"{pre},ttft_p99_ms",
                 1e3 * rep.percentile("ttft", 99))
            emit("chaos", f"{pre},avg_gpus", rep.avg_gpus())
            for k in ("crashes", "restarts", "residents_requeued",
                      "prefill_requeued", "kvc_retries", "kvc_fallbacks",
                      "straggler_windows", "swap_degrade_windows",
                      "link_down_windows", "skipped"):
                emit("chaos", f"{pre},{k}", fs[k])


def hetero():
    """Heterogeneous fleet (a100-TP2 prefill + h100-TP1 decode pools) and
    a two-model cluster, each through both engines via the same
    ``run_spec`` entry point — the two scenario axes the pool-centric
    control plane opens."""
    from repro.core import FleetSpec, PoolSpec, TraceRoute
    for eng in ["fluid", "events"]:
        rep = run_spec(hetero_demo_spec(duration=30.0, rps=6.0, engine=eng))
        emit("hetero", f"mixed_chips,{eng},requests", len(rep.requests))
        emit("hetero", f"mixed_chips,{eng},slo_pct",
             100 * rep.slo_attainment())
        emit("hetero", f"mixed_chips,{eng},ttft_p99_ms",
             1e3 * rep.percentile("ttft", 99))
        emit("hetero", f"mixed_chips,{eng},avg_gpus", rep.avg_gpus())
    two_model = ExperimentSpec(
        fleet=FleetSpec(
            pools=(
                PoolSpec("llama-pre", "prefill", "llama31_8b", "a100"),
                PoolSpec("llama-dec", "decode", "llama31_8b", "a100"),
                PoolSpec("qwen-pre", "prefill", "qwen25_32b", "a100", tp=4),
                PoolSpec("qwen-dec", "decode", "qwen25_32b", "a100", tp=4),
            ),
            routes=(TraceRoute("llama31_8b", "azure_conv", rps=5.0),
                    TraceRoute("qwen25_32b", "azure_code", rps=3.0))),
        policy="tokenscale", engine=ENGINE, duration=30.0, seed=0)
    rep = run_spec(two_model)
    for m in rep.models():
        s = rep.model_summary(m)
        emit("hetero", f"two_model,{m},requests", s["n"])
        emit("hetero", f"two_model,{m},slo_pct", 100 * s["slo_attainment"])
        emit("hetero", f"two_model,{m},ttft_p99_ms", 1e3 * s["ttft_p99"])
    emit("hetero", "two_model,avg_gpus", rep.avg_gpus())


def smoke():
    """~15 s sanity pass for scripts/check.sh: one small config through
    both engines, a tails smoke row (priority classes + preemption
    through the event engine), a heterogeneous-fleet row (mixed chips/TP
    through run_spec), a kvtiers row (paged KV + host-DRAM swap + prefix
    reuse on the contended fleet), a gateway row (hashtrie locality
    routing + lazy paging on the hot-prompt trace), a deflect row
    (chunked prefill deflection on the saturated burst fleet), and a
    chaos row (seeded fault injection with the self-healing control
    plane)."""
    from repro.sim.traces import DEFAULT_PRIORITY_MIX
    for eng in ["fluid", "events"]:
        rep = run_policy("tokenscale", "azure_conv", duration=20.0, rps=6.0,
                         seed=0, engine=eng)
        emit("smoke", f"{eng},requests", len(rep.requests))
        emit("smoke", f"{eng},slo_pct", 100 * rep.slo_attainment())
        emit("smoke", f"{eng},avg_gpus", rep.avg_gpus())
    cfg = dict(TAILS_CFG)
    cfg["duration"] = 22.0
    rep = run_policy("tokenscale", "burstgpt2", engine="events",
                     preemption="evict-lowest",
                     priority_mix=DEFAULT_PRIORITY_MIX, **cfg)
    emit("smoke", "tails,preemptions", len(rep.preemptions))
    emit("smoke", "tails,class0_ttft_p99_ms",
         1e3 * rep.percentile("ttft", 99, priority=0))
    emit("smoke", "tails,class0_slo_pct", 100 * rep.slo_attainment(0))
    rep = run_spec(hetero_demo_spec(duration=20.0, rps=6.0,
                                    engine="events"))
    emit("smoke", "hetero,requests", len(rep.requests))
    emit("smoke", "hetero,slo_pct", 100 * rep.slo_attainment())
    emit("smoke", "hetero,avg_gpus", rep.avg_gpus())
    rep = run_kvtiers_variant("swap+prefix", duration=22.0)
    ks = rep.kv_summary()
    emit("smoke", "kvtiers,preemptions", len(rep.preemptions))
    emit("smoke", "kvtiers,swap_outs", ks["swap_outs"])
    emit("smoke", "kvtiers,prefix_hit_rate_pct",
         100 * ks["prefix_hit_rate"])
    emit("smoke", "kvtiers,peak_blocks_frac", ks["peak_blocks_frac"])
    rep = run_gateway_variant("gateway", duration=22.0)
    gw = rep.gw_summary()
    emit("smoke", "gateway,requests", len(rep.requests))
    emit("smoke", "gateway,affinity_hits", gw["affinity_hits"])
    emit("smoke", "gateway,balanced_fallbacks", gw["balanced"])
    emit("smoke", "gateway,block_grows", gw["block_grows"])
    emit("smoke", "gateway,ttft_p99_ms", 1e3 * rep.percentile("ttft", 99))
    rep = run_deflect_variant("chunked", duration=20.0)
    emit("smoke", "deflect,requests", len(rep.requests))
    emit("smoke", "deflect,deflected", rep.n_deflected)
    emit("smoke", "deflect,ttft_p99_ms", 1e3 * rep.percentile("ttft", 99))
    emit("smoke", "deflect,tpot_p99_ms", 1e3 * rep.percentile("tpot", 99))
    rep = run_pareto_variant("coord", duration=30.0)
    cs = rep.cost_summary()
    emit("smoke", "pareto,requests", len(rep.requests))
    emit("smoke", "pareto,slo_pct", 100 * rep.slo_attainment())
    emit("smoke", "pareto,cost_dollars", cs["cost_dollars"])
    emit("smoke", "pareto,avg_gpus", rep.avg_gpus())
    rep = run_chaos_variant("recovery", duration=35.0)
    fs = rep.fault_summary()
    emit("smoke", "chaos,requests", len(rep.requests))
    emit("smoke", "chaos,slo_pct", 100 * rep.slo_attainment())
    emit("smoke", "chaos,crashes", fs["crashes"])
    emit("smoke", "chaos,restarts", fs["restarts"])
    emit("smoke", "chaos,residents_requeued", fs["residents_requeued"])


def perfscale():
    """Simulator wall-clock trajectory rows (benchmarks/perf.py): the
    tails replay + a scaled-down slice of the million-request streaming
    scenario.  The full suite (1M requests, 64-instance fleet) and the
    BENCH_sim.json trajectory live in ``python -m benchmarks.perf``."""
    from benchmarks.perf import run_million, run_tails_replay
    row = run_tails_replay(duration=22.0)
    for k, v in row.items():
        emit("perfscale", f"tails_replay_smoke,{k}", v)
    row = run_million(duration=300.0)
    for k, v in row.items():
        emit("perfscale", f"stream_smoke,{k}", v)


def obs():
    """Flight-recorder end-to-end row (scripts/check.sh): replay the
    deflect burst cell with telemetry on through *both* engines, write
    JSONL + Chrome-trace JSON (to --trace-out, or the system temp dir),
    schema-validate the JSONL, and run the scaling-decision explainer —
    the full record -> export -> explain pipeline in one bench."""
    import tempfile
    from repro.obs.explain import explain
    from repro.obs.export import (load_jsonl, validate_trace_lines,
                                  write_chrome_trace, write_jsonl)
    global TRACE_OUT
    if TRACE_OUT is None:
        TRACE_OUT = os.path.join(tempfile.gettempdir(), "obs_trace.jsonl")
    cfg = dict(DEFLECT_CFG)
    cfg["duration"] = 20.0
    for eng in ["fluid", "events"]:
        rep = run_policy("tokenscale", "burstgpt1", engine=eng,
                         prefill_chunking=DEFLECT_VARIANTS["chunked"],
                         telemetry=True, **cfg)
        rec = rep.obs
        jsonl_path, chrome_path = _trace_paths(eng)
        n_lines = write_jsonl(rec, jsonl_path)
        write_chrome_trace(rec, chrome_path)
        records = load_jsonl(jsonl_path)
        errors = validate_trace_lines(records)
        report = explain(records)
        emit("obs", f"{eng},requests", len(rec.requests))
        emit("obs", f"{eng},trace_lines", n_lines)
        emit("obs", f"{eng},schema_errors", len(errors))
        emit("obs", f"{eng},decisions", report["n_decisions"])
        emit("obs", f"{eng},scale_ups", len(report["scale_ups"]))
        emit("obs", f"{eng},ttft_violations", len(report["violations"]))
        for stage, n in sorted(report["violations_by_stage"].items()):
            emit("obs", f"{eng},violations_{stage}", n)
        for e in errors:
            print(f"# obs schema error ({eng}): {e}", file=sys.stderr)
        if errors:
            sys.exit(f"obs bench: {len(errors)} schema errors in "
                     f"{jsonl_path}")


def run_spec_files(paths: list[str]):
    """Run declarative ExperimentSpec JSON files (--spec=...) and emit
    their summary + per-model rows.  With --trace-out, each spec runs
    with telemetry forced on and writes its flight-recorder trace."""
    import dataclasses
    for path in paths:
        spec = ExperimentSpec.load(path)
        if TRACE_OUT is not None:
            spec = dataclasses.replace(spec, telemetry=True)
        rep = run_spec(spec)
        tag = os.path.splitext(os.path.basename(path))[0]
        if TRACE_OUT is not None and rep.obs is not None:
            from repro.obs.export import write_chrome_trace, write_jsonl
            jsonl_path, chrome_path = _trace_paths(tag)
            emit("spec", f"{tag},trace_lines",
                 write_jsonl(rep.obs, jsonl_path))
            write_chrome_trace(rep.obs, chrome_path)
        for k, v in rep.summary().items():
            emit("spec", f"{tag},{k}", v)
        models = rep.models()
        if len(models) > 1:
            for m in models:
                for k, v in rep.model_summary(m).items():
                    emit("spec", f"{tag},{m},{k}", v)


BENCHES = {
    "fig3": fig3_overprovisioning,
    "table2": table2_velocities,
    "fig7": fig7_characterization,
    "fig9": fig9_end_to_end,
    "fig9b": fig9b_large_model,
    "fig10": fig10_burst_adaptation,
    "fig11": fig11_provision_correlation,
    "fig12": fig12_predictor_accuracy,
    "fig13": fig13_convertible_count,
    "fig14": fig14_ablation,
    "fig15": fig15_h100,
    "micro": engine_microbench,
    "simspeed": sim_throughput,
    "pd": pd_runtime,
    "kv8": kv8_velocity,
    "multipod": multipod_scaling,
    "diffval": diffval,
    "tails": tails,
    "kvtiers": kvtiers,
    "gateway": gateway,
    "deflect": deflect,
    "pareto": pareto,
    "chaos": chaos,
    "hetero": hetero,
    "perfscale": perfscale,
    "obs": obs,
    "smoke": smoke,
}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("benches", nargs="*", metavar="bench",
                    help="bench names to run (default: all); "
                         f"one of {', '.join(sorted(BENCHES))}")
    ap.add_argument("--engine", default="fluid",
                    help="simulation engine for every sim-shaped bench "
                         "(fluid | events; DESIGN.md §1)")
    ap.add_argument("--bench", action="append", default=[],
                    metavar="NAME[,NAME...]",
                    help="comma-separated bench names (may repeat; "
                         "equivalent to positional args)")
    ap.add_argument("--spec", action="append", default=[], metavar="JSON",
                    help="run a declarative ExperimentSpec JSON file "
                         "(may repeat); skips the default all-bench run")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="flight-recorder output base path: the obs bench "
                         "and --spec runs record telemetry and write "
                         "PATH-<tag>.jsonl + PATH-<tag>.chrome.json "
                         "(repro.obs; default: telemetry off)")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    global ENGINE, TRACE_OUT
    args = parse_args(argv)
    get_engine(args.engine)         # fail fast on unknown engine names
    ENGINE = args.engine
    TRACE_OUT = args.trace_out
    names = list(args.benches)
    for group in args.bench:
        names += [n for n in group.split(",") if n]
    for n in names:
        if n not in BENCHES:
            sys.exit(f"unknown bench {n!r}; expected one of "
                     f"{', '.join(sorted(BENCHES))}")
    if not names and not args.spec:
        names = list(BENCHES)
    print("bench,metric,value")
    run_spec_files(args.spec)
    for n in names:
        t0 = time.perf_counter()
        BENCHES[n]()
        print(f"# {n} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)


if __name__ == "__main__":
    main()
