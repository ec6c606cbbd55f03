"""One run of one cell: build the cluster, warm it up, drive the open loop on
the wall clock, measure, and check what was served against the reference.

The entry the window drives is the program's ``PDCluster``, built as
``launch/serve.py`` builds it (TokenScale policy over a v5e profile of the
configuration that runs, the Alg. 1 router).  The harness calls ``submit``,
``step`` and, every ten steps, ``autoscale``, from its own loop:

- Arrivals come from the seeded schedule.  Before each call the cluster's
  clock is set to the wall seconds since the schedule began; while the
  cluster is idle the loop sleeps until the next arrival is due.
- Time stamps are the harness's own: the wall time, read after a call
  returns, at which a request's first and last tokens were in its output.
  Latency counts from the request's due time.
- Set-up is everything before the window: weights, every shape the window
  uses, and the mix's own warm-up phase.  Compiles inside the window are
  counted, and so is every call that holds the loop longer than
  ``SLOW_CALL_S``, with the garbage collector's pauses inside it.
"""
from __future__ import annotations

import gc
import math
import shutil
import statistics
import sys
import threading
import time
import traceback
from collections import Counter
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np
from jax import monitoring

from chipbench import correct, flops, slo, traffic
from chipbench.discover import Cell, peaks
from chipbench.weights import make_params

AUTOSCALE_EVERY = 10          # cluster steps per scaler tick, as serve.py
TRACE_S = 2.0                 # length of the traced stretch
SLOW_CALL_S = 0.25            # a call that holds the loop longer is logged
STACK_AFTER_S = 1.0           # ... and its Python stack is taken after this
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Compiles:
    """Backend compiles by program name, and those inside the window; also
    programs loaded from the persistent cache inside the window, which
    skip the backend compile but still stall the loop."""

    def __init__(self):
        self.total = Counter()
        self.in_window = Counter()
        self.loads_in_window = 0
        self.window = False
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw):
        if event == _COMPILE_EVENT:
            name = str(kw.get("fun_name", "?"))
            self.total[name] += 1
            if self.window:
                self.in_window[name] += 1

    def _on_event(self, event: str, **kw):
        if event == _CACHE_HIT_EVENT and self.window:
            self.loads_in_window += 1

    def close(self):
        monitoring.unregister_event_duration_listener(self._on)
        monitoring.unregister_event_listener(self._on_event)


@dataclass
class Calls:
    """Program calls made while the profiler traces, and the harness's
    calls around them: (span name, wall seconds, prefill calls before and
    after, decode calls before and after)."""
    on: bool = False
    prefill: list = field(default_factory=list)  # (kind, offset, n, final)
    decode: list = field(default_factory=list)   # (kind, cur_lens)
    spans: list = field(default_factory=list)


class Pauses:
    """The garbage collector's pauses, in seconds, as they add up."""

    def __init__(self):
        self.total, self.n, self._t0 = 0.0, 0, 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.total += time.perf_counter() - self._t0
            self.n += 1

    def close(self):
        gc.callbacks.remove(self._on)


class Watch:
    """A thread that takes the main thread's Python stack once when one
    harness call has run for STACK_AFTER_S, so that a stall says where it
    is."""

    def __init__(self):
        self.main = threading.get_ident()
        self.t0, self.stack = None, None
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._run, daemon=True)
        self._th.start()

    def begin(self):
        self.stack, self.t0 = None, time.perf_counter()

    def end(self) -> Optional[str]:
        self.t0 = None
        return self.stack

    def _run(self):
        while not self._stop.wait(0.05):
            t0 = self.t0
            if (t0 is not None and self.stack is None
                    and time.perf_counter() - t0 > STACK_AFTER_S):
                frame = sys._current_frames().get(self.main)
                self.stack = "".join(traceback.format_stack(frame)[-6:]) \
                    if frame is not None else "?"

    def close(self):
        self._stop.set()
        self._th.join()


def _done_rows(eng, skip: int = -1) -> np.ndarray:
    """cur_lens of the rows that decode a real token this call."""
    rows = [s for s in range(eng.num_slots)
            if eng.active[s] and s != skip and eng.slot_req[s] is not None
            and eng.slot_req[s].prefill_done >= len(eng.slot_req[s].prompt)]
    return eng.cur_lens[rows].copy()


def _wrap_engine(eng, calls: Calls):
    """Record each program call of a decoder engine while tracing."""
    if getattr(eng, "_bench_wrapped", False):
        return
    dec, mix, pre = eng._decode, eng._mixed, eng._prefill

    def decode(*a):
        if calls.on:
            calls.decode.append(("decode", _done_rows(eng)))
        return dec(*a)

    def mixed(*a):
        if calls.on:
            pc = eng.pending_chunked
            calls.decode.append(("mixed", _done_rows(eng, skip=pc.slot)))
            n = min(eng.chunk_size, len(pc.prompt) - pc.prefill_done)
            calls.prefill.append(("mixed", pc.prefill_done, n,
                                  pc.prefill_done + n >= len(pc.prompt)))
        return mix(*a)

    def prefill(params, state, tokens, length, *a):
        if calls.on:
            calls.prefill.append(("prefill", 0, int(np.asarray(length)[0]),
                                  True))
        return pre(params, state, tokens, length, *a)

    eng._decode, eng._mixed, eng._prefill = decode, mixed, prefill
    eng._bench_wrapped = True


def _wrap_prefiller(p, calls: Calls):
    if getattr(p, "_bench_wrapped", False):
        return
    fn = p._fn

    def prefill(params, state, tokens, length, *a):
        if calls.on:
            calls.prefill.append(("prefill", 0, int(np.asarray(length)[0]),
                                  True))
        return fn(params, state, tokens, length, *a)

    p._fn = prefill
    p._bench_wrapped = True


def instrument(cluster, calls: Calls):
    for p in cluster.prefillers:
        _wrap_prefiller(p, calls)
    for d in cluster.decoders + cluster.convertibles:
        _wrap_engine(d.eng, calls)


def build_cluster(cell: Cell, mcfg, params):
    from repro.core import CHIPS, InstanceSpec, TokenScalePolicy, profile
    from repro.serving import PDCluster
    c = cell.spec["cluster"]
    prof = profile(mcfg, InstanceSpec(CHIPS["v5e"], tp=1))
    return PDCluster(
        mcfg, params, TokenScalePolicy(prof, convertible=c["convertibles"]),
        n_prefillers=c["prefillers"], n_decoders=c["decoders"],
        n_convertible=c["convertibles"],
        slots_per_decoder=c["slots_per_decoder"], max_len=c["max_len"],
        chunk_size=c["chunk_size"], max_instances=c["max_instances"])


def warm_lengths(mix: dict, max_len: int) -> list[int]:
    """Prompt lengths that reach every prefill length (powers of two, at
    least 8) and every KV transfer length (multiples of 128) that the mix's
    prompts can reach."""
    lo, hi = traffic.prompt_range(mix)
    pads = {min(max(8, 1 << (L - 1).bit_length()), max_len)
            for L in range(lo, hi + 1)}
    blocks = {-(-L // 128) * 128 for L in range(lo, hi + 1)}
    out = set()
    for top in pads | blocks:
        # the longest prompt of the mix that maps to this length
        out.add(min(top, hi))
    return sorted(L for L in out if L >= lo)


def warm_shapes(cluster, mix: dict):
    """Drive every program shape the window can use through the cluster:
    each prefill and transfer length through the prefiller and a decoder,
    a short and a long prompt through a Convertible Decoder (its own
    prefill, then the fused decode + chunk), and decode steps."""
    from repro.serving import Request
    c = cluster
    rid = -1
    for L in warm_lengths(mix, c.max_len):
        c.prefillers[0].submit(Request(rid=rid, prompt=np.full(
            (L,), 1, np.int32), max_new_tokens=2))
        rid -= 1
    for conv in c.convertibles[:1]:
        lo, hi = traffic.prompt_range(mix)
        for L in sorted({min(lo, c.chunk_size), hi}):
            conv.eng.add_request(Request(rid=rid, prompt=np.full(
                (L,), 2, np.int32), max_new_tokens=2))
            rid -= 1
    while c.busy():
        c.step()
    _sync(c)


def _sync(cluster):
    """Wait for every decoder's state, so the device is idle."""
    jax.block_until_ready([d.eng.state for d in cluster.decoders
                           + cluster.convertibles])


@dataclass
class Loop:
    """What the open loop saw."""
    outcomes: dict                  # rid -> slo.Outcome, sampled requests
    routes: dict                    # rid -> "prefiller" | "convertible"
    window_tokens: int
    steps: int
    lateness: list                  # submit - due, seconds
    instances: list                 # (t, prefillers, decoders) per tick
    transfers: tuple                # (n, bytes, tokens) inside the window
    slow: list                      # (span, t, seconds, CPU seconds, gc
                                    #  seconds, compiles, cache loads,
                                    #  stack or None) > SLOW_CALL_S


class Clock:
    """Seconds on the schedule's clock: wall time since it was made, less the
    time spent starting and stopping the profiler (writing a trace takes
    seconds, and the schedule waits for it)."""

    def __init__(self):
        self.epoch = time.perf_counter()
        self.paused = 0.0

    def __call__(self) -> float:
        return time.perf_counter() - self.epoch - self.paused

    def pause(self, fn):
        t0 = time.perf_counter()
        fn()
        self.paused += time.perf_counter() - t0


def drive(cluster, sched: traffic.Schedule, requests: list, calls: Calls,
          clock: Clock, compiles: Compiles,
          trace_dir: Optional[Path] = None) -> Loop:
    """The open loop over the whole schedule.  Returns once the drain has
    ended, or once every sampled request has finished after the window.
    With `trace_dir`, the profiler traces the window's busiest TRACE_S
    seconds (`busiest_stretch`), between two cluster calls with the device
    idle; the schedule's clock stands still while the profiler starts and
    stops, so only the traced stretch is disturbed."""
    from jax.profiler import TraceAnnotation

    ws, we, end = sched.window_start, sched.window_end, sched.end
    live: dict = {}                 # rid -> [request, tokens seen, outcome]
    outcomes, routes = {}, {}
    steps = 0
    lateness, instances = [], []
    st = {"tokens": 0, "left": sum(a.phase == "window"
                                   for a in sched.arrivals)}
    tr0 = tr1 = None                # transfer counters at window edges
    tracing, trace_t0 = False, 0.0
    trace_from = busiest_stretch(sched) if trace_dir is not None else None
    i, n = 0, len(requests)
    slow = []
    pauses, watch = Pauses(), Watch()

    def transfers():
        t = cluster.transfers
        return (t.n_transfers, t.total_bytes, t.total_tokens)

    @contextmanager
    def span(name):
        g0, c0 = pauses.total, sum(compiles.total.values())
        l0, p0, d0 = compiles.loads_in_window, len(calls.prefill), \
            len(calls.decode)
        t0, cpu0 = time.perf_counter(), time.thread_time()
        watch.begin()
        with (TraceAnnotation(name) if tracing else _NULL):
            yield
        dt = time.perf_counter() - t0
        stack = watch.end()
        if calls.on:
            calls.spans.append((name, dt, p0, len(calls.prefill), d0,
                                len(calls.decode)))
        if dt > SLOW_CALL_S and name != "bench.idle":
            slow.append((name, clock() - dt, dt, time.thread_time() - cpu0,
                         pauses.total - g0,
                         sum(compiles.total.values()) - c0,
                         compiles.loads_in_window - l0, stack))

    def observe(t):
        for rid in list(live):
            entry = live[rid]
            req, seen, oc = entry
            k = len(req.output)
            if k == seen:
                continue
            if ws <= t < we:
                st["tokens"] += k - seen
            entry[1] = k
            if oc is not None:
                if oc.first_t is None:
                    oc.first_t = t
                oc.tokens = k
                if k >= oc.max_new:
                    oc.last_t = t
                    st["left"] -= 1
            if k >= req.max_new_tokens:
                del live[rid]
        for rid in [r for r, (req, k, _) in live.items()
                    if k and req.finish_t >= 0]:
            # the engine let go of it before all its tokens: a wrong answer
            oc = live.pop(rid)[2]
            if oc is not None:
                oc.ended_short = True
                st["left"] -= 1

    def set_trace(on: bool):
        nonlocal tracing, trace_t0
        _sync(cluster)
        if on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            clock.pause(lambda: jax.profiler.start_trace(
                str(trace_dir), profiler_options=opts))
            trace_t0 = clock()
        else:
            clock.pause(jax.profiler.stop_trace)
        tracing = calls.on = on

    while True:
        now = clock()
        if tr0 is None and now >= ws:
            tr0 = transfers()
            compiles.window = True
        if trace_from is not None and now >= trace_from:
            trace_from = None
            set_trace(True)
        if tracing and now >= min(trace_t0 + TRACE_S, we):
            set_trace(False)
        if tr1 is None and now >= we:
            tr1 = transfers()
            compiles.window = False
        if now >= end or (now >= we and st["left"] == 0):
            break
        while i < n and sched.arrivals[i].due <= now:
            a, req = sched.arrivals[i], requests[i]
            cluster.now = clock()
            lateness.append(cluster.now - a.due)
            with span("bench.submit"):
                cluster.submit(req)
            routes[req.rid] = ("prefiller" if any(
                q is req for p in cluster.prefillers for q in p.queue)
                else "convertible")
            oc = None
            if a.phase == "window":
                oc = slo.Outcome(len(req.prompt), req.max_new_tokens, a.due)
                outcomes[req.rid] = oc
            live[req.rid] = [req, 0, oc]
            i += 1
            observe(clock())
        if cluster.busy():
            cluster.now = clock()
            with span("bench.step"):
                cluster.step()
            steps += 1
            observe(clock())
            if steps % AUTOSCALE_EVERY == 0:
                cluster.now = clock()
                with span("bench.autoscale"):
                    cluster.autoscale()
                instrument(cluster, calls)
                instances.append((cluster.now, len(cluster.prefillers),
                                  len(cluster.decoders)))
        else:
            nxt = min(sched.arrivals[i].due if i < n else end, end)
            edges = [ws, we] + ([trace_t0 + TRACE_S] if tracing else []) \
                + ([trace_from] if trace_from is not None else [])
            for edge in edges:
                if now < edge:
                    nxt = min(nxt, edge)
            with span("bench.idle"):
                time.sleep(max(0.0, nxt - clock()))
    if tracing:
        set_trace(False)
    compiles.window = False
    pauses.close()
    watch.close()
    tr0 = tr0 or transfers()
    tr1 = tr1 or transfers()
    return Loop(outcomes, routes, st["tokens"], steps, lateness, instances,
                tuple(b - a for a, b in zip(tr0, tr1)), slow)


def busiest_stretch(sched: traffic.Schedule) -> float:
    """Where the traced stretch starts: at the due time of a sampled request,
    the one that opens the TRACE_S seconds inside the window with the most
    prompt tokens due, so that the trace holds prefill and decode alike."""
    win = sched.sampled()
    last = max(sched.window_end - TRACE_S, sched.window_start)
    best, best_t = -1, sched.window_start
    for a in win:
        t = min(a.due, last)
        toks = sum(len(b.prompt) for b in win if t <= b.due < t + TRACE_S)
        if toks > best:
            best, best_t = toks, t
    return best_t


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()


def device_record(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, log: Callable[[str], None], out_dir: Path,
        control: bool = False, rate_rps: Optional[float] = None,
        params=None) -> dict:
    """One run.  Returns the result object (the last line's content).

    `control` puts the float8 control in the program's place: `correct` is
    decided on the tokens it ranks first, and the program's own gap is
    still reported.  `rate_rps` replaces the cell's rate and `params` the
    seed's weights (the sweep uses both to share one process's set-up)."""
    from repro.models import abstract_params
    from repro.serving import Request

    devs = jax.devices()
    kind = devs[0].device_kind
    pk = peaks(kind, cell.root) if devs[0].platform == "tpu" else None
    compiles = Compiles()
    spec, cl = cell.spec, cell.spec["cluster"]
    mix = traffic.cut(cell.mix, cl["max_len"])
    mcfg = cell.builder().model_config(spec)
    if params is None:
        params = make_params(abstract_params(mcfg), seed)
        jax.block_until_ready(params)
    log(f"weights: {time.perf_counter() - t_start:.3f} s since start")
    cluster = build_cluster(cell, mcfg, params)
    warm_shapes(cluster, mix)
    log(f"shapes warm: {time.perf_counter() - t_start:.3f} s since start, "
        f"{sum(compiles.total.values())} compiles")
    rate = float(cell.fixed["rate_rps"] if rate_rps is None else rate_rps)
    sched = traffic.generate(mix, rate, seed, seconds, mcfg.vocab_size)
    requests = [Request(rid=a.rid, prompt=a.prompt, max_new_tokens=a.max_new)
                for a in sched.arrivals]
    calls = Calls()
    instrument(cluster, calls)
    trace_dir = None
    if trace:
        trace_dir = out_dir / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    clock = Clock()
    loop = drive(cluster, sched, requests, calls, clock, compiles,
                 trace_dir=trace_dir)
    setup_s = (clock.epoch + sched.window_start) - t_start
    _sync(cluster)
    stats = devs[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)

    outs = list(loop.outcomes.values())
    e2e = slo.end_to_end(outs, loop.window_tokens, seconds)
    e2e["setup_s"] = setup_s
    failed = sum(not o.finished for o in outs)
    short = sum(o.ended_short for o in outs)
    late = sorted(loop.lateness) or [0.0]
    log(f"rate: {rate!r} requests/s; window: {len(outs)} sampled "
        f"requests, {failed} unfinished ({short} ended short), "
        f"{loop.steps} cluster steps, {loop.window_tokens} tokens in window")
    log(f"medians: ttft_p50_ms {e2e['ttft_p50_ms']!r} tpot_p50_ms "
        f"{e2e['tpot_p50_ms']!r}")
    log(f"generator lateness: median {1e3 * statistics.median(late):.3f} "
        f"ms, max {1e3 * late[-1]:.3f} ms")
    log(f"kv transfers in window: {loop.transfers[0]} ({loop.transfers[1]}"
        f" bytes, {loop.transfers[2]} tokens); instances now "
        f"{len(cluster.prefillers)}P/{len(cluster.decoders)}D/"
        f"{len(cluster.convertibles)}C, peak "
        f"{max((x[1] for x in loop.instances), default=0)}P/"
        f"{max((x[2] for x in loop.instances), default=0)}D")
    log(f"compiles inside the window: {sum(compiles.in_window.values())} "
        f"{dict(compiles.in_window)}; persistent-cache loads there: "
        f"{compiles.loads_in_window}")
    log(f"calls over {SLOW_CALL_S} s: {len(loop.slow)}")
    for name, t, dt, cpu, g, c, ld, stack in loop.slow:
        log(f"  slow {name} at {t - sched.window_start:+.3f} s from the "
            f"window's start: {dt:.3f} s, {cpu:.3f} s on the loop's CPU "
            f"thread, gc {g:.3f} s, {c} compiles, {ld} cache loads")
        if stack:
            log("    stack after " + str(STACK_AFTER_S) + " s:\n" + stack)
    log(f"memory: peak_bytes_in_use {peak}, bytes_limit "
        f"{stats.get('bytes_limit')}")

    # what was served, kept before the cluster is freed
    finished = [r for r in requests if r.rid in loop.outcomes
                and loop.outcomes[r.rid].finished]
    rows = [correct.Row(np.asarray(r.prompt), list(r.output),
                        loop.routes[r.rid]) for r in finished]
    per_layer = None
    breakdown = None
    dev_extra = {}
    if trace:
        from chipbench import tracereduce
        red = tracereduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = LayerContext(red, calls, loop, flops.Dims.of(spec), pk,
                           requests, log)
        per_layer = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        dev_extra = {"busy_s": red.busy_s(), "window_s": red.window_s()}
        breakdown = red.breakdown()
        del ctx
    del cluster, requests, sched
    gc.collect()                    # the engines' caches, held in cycles
    compiles.close()

    # correctness: the reference over a sample of what was served
    limit = float(cell.fixed["gap_limit"])
    sample = correct.pick_sample(rows, seed, int(spec["check"]["min_tokens"]),
                                 int(spec["check"]["max_rows"]))
    t0 = time.perf_counter()
    gap, gap8, n_cmp = correct.widest_gap(
        cell.reference(), spec, params, sample, cl["max_len"],
        traffic.max_output(mix), int(spec["check"]["rows_per_block"]),
        control=control)
    log(f"reference: {len(sample)} rows, {n_cmp} served tokens compared "
        f"in {time.perf_counter() - t0:.3f} s")
    compared = {"logit_gap": [gap, limit], "unfinished": [failed, 0],
                "short_outputs": [short, 0], "compared_tokens": [n_cmp, 1]}
    judged = gap
    if control:
        log(f"control (float8 reference): widest gap {gap8!r}")
        compared["control_logit_gap"] = [gap8, limit]
        judged = gap8
    ok = (math.isfinite(judged) and judged <= limit and failed == 0
          and short == 0 and n_cmp >= 1)
    result = {
        "correct": bool(ok),
        "attempted": len(outs),
        "failed": failed,
        "metrics": {},
        "device": {**device_record(devs, peak), **dev_extra},
    }
    if trace:
        result["metrics"] = per_layer
        result["breakdown"] = breakdown
    else:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


@dataclass
class LayerContext:
    """What a per-layer metric reads: the reduced trace, the program calls
    made while tracing, the loop's counters, the configuration's sizes and
    the chip's peaks."""
    trace: object
    calls: Calls
    loop: Loop
    dims: flops.Dims
    peaks: Optional[dict]
    requests: list
    log: Callable[[str], None]

    def note(self, msg: str):
        self.log(f"  {msg}")
