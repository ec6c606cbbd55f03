"""The chip benchmark's trace reduction: on a synthetic trace with known
answers, on the profiler's own object layout, and on a slice of a trace
recorded on a TPU v5e."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import tracereduce as tr  # noqa: E402
from chipbench.discover import BENCH_DIR, load_module  # noqa: E402

SAMPLES = sorted((Path(__file__).parent / "data").glob("trace_sample_*.json"))


def synthetic() -> tr.Reduced:
    spans = [tr.Span("bench.step", 0, 150), tr.Span("bench.idle", 150, 190),
             tr.Span("bench.step", 190, 320)]
    ops = [[tr.Ev("while.1", 0, 100), tr.Ev("decode_attention.3", 10, 30),
            tr.Ev("fusion.2", 50, 40),
            tr.Ev("chunked_prefill_attention.1", 200, 50),
            tr.Ev("fusion.7", 260, 40)]]
    mods = [[tr.Ev("jit__decode_all", 0, 100),
             tr.Ev("jit__prefill_one", 200, 100)]]
    return tr.Reduced(spans, ops, mods)


def test_busy_union_and_window():
    r = synthetic()
    assert r.window_s() == pytest.approx(320e-9)
    assert r.busy_s() == pytest.approx(190e-9)        # 100 + 50 + 40
    assert r.busy_in(0, 150) == 100
    assert r.busy_in(190, 320) == 90
    assert r.busy_in(20, 30) == 10


def test_kernels_programs_and_self_time():
    r = synthetic()
    assert r.kernel_seconds("decode_attention") == pytest.approx(30e-9)
    assert r.kernel_seconds("chunked_prefill_attention") == \
        pytest.approx(50e-9)
    assert r.kernel_seconds("decode") == 0.0
    assert [e.name for e in r.program_runs(["jit__decode_all"])] == \
        ["jit__decode_all"]
    assert tr.self_times(r.ops[0]) == [30, 30, 40, 50, 40]


def test_breakdown_names_gaps_by_span():
    b = synthetic().breakdown()
    assert b["device_ops"][0] == ["chunked_prefill_attention.1",
                                  pytest.approx(50e-9)]
    assert [n for n, _ in b["device_ops"]][-2:] == ["while.1",
                                                   "decode_attention.3"]
    assert b["idle_gaps"] == [["bench.idle", pytest.approx(100e-9)],
                              ["bench.step", pytest.approx(20e-9)],
                              ["bench.step", pytest.approx(10e-9)]]


def test_layer_readers_on_the_synthetic_trace():
    run = SimpleNamespace(trace=synthetic())
    idle = load_module(BENCH_DIR / "layer_metrics" / "device_idle_share.py")
    host = load_module(BENCH_DIR / "layer_metrics" / "host_ms_per_step.py")
    dec = load_module(BENCH_DIR / "layer_metrics" /
                      "decode_step_device_ms.py")
    assert idle.read(run) == pytest.approx(100 * (1 - 190 / 280))
    assert host.read(run) == pytest.approx(45 / 1e6)  # (50 + 40) / 2 ns
    assert dec.read(run) == pytest.approx(100 / 1e6)
    empty = SimpleNamespace(trace=tr.Reduced(run.trace.spans, [], []))
    assert idle.read(empty) is None and host.read(empty) is None
    assert dec.read(empty) is None


def _profile(planes):
    def ev(n, s, d):
        return SimpleNamespace(name=n, start_ns=s, duration_ns=d)
    return SimpleNamespace(planes=[
        SimpleNamespace(name=pn, lines=[
            SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
            for ln, evs in lines.items()])
        for pn, lines in planes.items()])


def test_reduce_profile_reads_the_profilers_layout():
    pd = _profile({
        "/device:TPU:0": {
            "XLA Modules": [("jit__decode_all(177625827100144337)", 5, 90)],
            "XLA Ops": [("%fusion.9 = bf16[64] fusion(%a)", 40, 20),
                        ("%decode_attention.6 = f32[64,14,128] "
                         "custom-call(%q)", 10, 25)],
            "Async XLA Ops": [("%copy-start.4 = (bf16[896])", 0, 500)]},
        "/host:CPU": {"python3": [("bench.step", 0, 100),
                                  ("$engine.py:236 step", 1, 98)],
                      "main/287": [("CommonPjRtLoadedExecutable", 2, 3)]},
        "/host:metadata": {},
    })
    r = tr.reduce_profile(pd)
    assert [s.name for s in r.spans] == ["bench.step"]
    assert [e.name for e in r.ops[0]] == ["decode_attention.6", "fusion.9"]
    assert [e.name for e in r.modules[0]] == ["jit__decode_all"]
    assert r.busy_s() == pytest.approx(45e-9)


def _union(intervals):
    """Covered length by counting +1/-1 at sorted endpoints."""
    pts = sorted([(s, 1) for s, _ in intervals]
                 + [(e, -1) for _, e in intervals])
    depth, last, total = 0, None, 0.0
    for t, d in pts:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


@pytest.mark.parametrize("path", SAMPLES, ids=[p.stem for p in SAMPLES])
def test_recorded_chip_trace(path):
    r = tr.load(path)
    ops = r.ops[0]
    assert ops and r.spans
    inside = [(max(e.start, r.start), min(e.end, r.end)) for e in ops
              if e.end > r.start and e.start < r.end]
    assert r.busy_in(r.start, r.end) == pytest.approx(_union(inside))
    assert 0 < r.busy_s() <= r.window_s()
    kernel = sum(e.dur for e in ops if e.name.split(".")[0] in
                 ("decode_attention", "chunked_prefill_attention")
                 and r.start <= e.start < r.end)
    assert kernel > 0
    assert (r.kernel_seconds("decode_attention")
            + r.kernel_seconds("chunked_prefill_attention")) == \
        pytest.approx(kernel / 1e9)
    own = tr.self_times(ops)
    assert all(x >= -1e-6 for x in own)
    assert sum(own) == pytest.approx(_union([(e.start, e.end) for e in ops]))
    b = r.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(name.startswith("bench.") or name == "between spans"
               for name, _ in b["idle_gaps"])
