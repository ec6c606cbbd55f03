"""The chip benchmark's harness, run on the CPU at a tiny size: the open
loop end to end, what the last line holds, discovery by name, the float8
control, and the faults that ``correct`` has to catch.

The tiny cell's limit (0.012) was set from CPU readings of this
configuration and mix: the program's widest gap over seeds 1-12 and
2**31 + 7 was 0.0053, the float8 control's narrowest 0.0267.
"""
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from chipbench import harness  # noqa: E402
from chipbench.discover import (BENCH_DIR, REPO_DIR, Cell,  # noqa: E402
                                find_cell, load_json)

DATA = Path(__file__).parent / "data"
BENCH = load_json(REPO_DIR / "BENCHMARK.json")
SEED = 2 ** 31 + 7
WINDOW_S = 2.0


def tiny_cell(root: Path = BENCH_DIR) -> Cell:
    return Cell(name="tiny.chat", chips=1,
                spec=load_json(DATA / "tiny-dense.json"),
                mix=load_json(DATA / "tiny-chat.json"),
                fixed=load_json(DATA / "tiny.json"),
                end_to_end=BENCH["end_to_end"], per_layer=BENCH["per_layer"],
                root=root)


def run(cell=None, trace=False, control=False, seed=SEED):
    return harness.run(cell or tiny_cell(), seed, WINDOW_S, trace,
                       t_start=time.perf_counter(), log=lambda m: None,
                       out_dir=BENCH_DIR / ".out", control=control)


@pytest.fixture(scope="module")
def clean():
    return run()


def test_last_line_keys_and_metrics(clean):
    line = json.loads(json.dumps(clean))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert set(line["metrics"]) == e2e
    for m in BENCH["end_to_end"]:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and np.isfinite(got["value"])
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["compared"]["logit_gap"]["limit"] == 0.012
    assert line["compared"]["logit_gap"]["value"] <= 0.012
    assert line["compared"]["unfinished"] == {"value": 0, "limit": 0}


def test_float8_control_fails_the_limit():
    """The control in the program's place: the harness's own comparison
    finds it not correct, while the program's gap stays in its limit."""
    res = run(control=True)
    c = res["compared"]
    assert res["correct"] is False
    assert c["control_logit_gap"]["value"] > c["control_logit_gap"]["limit"]
    assert c["logit_gap"]["value"] <= c["logit_gap"]["limit"]


def _alter_third_token(monkeypatch):
    from repro.serving import engine
    orig = engine.Request.pick

    def pick(self, row):
        tok = orig(self, row)
        return (tok + 1) % len(row) if len(self.output) == 2 else tok
    monkeypatch.setattr(engine.Request, "pick", pick)


def _wrap_decode(monkeypatch, broken):
    from repro.serving import engine
    orig = engine.programs

    def programs(cfg):
        pre, dec, mix = orig(cfg)
        return pre, broken(dec), mix
    monkeypatch.setattr(engine, "programs", programs)


def _state_unchanged(monkeypatch):
    def broken(dec):
        def f(params, state, last, lens):
            logits, _ = dec(params, state, last, lens)
            return logits, state
        return f
    _wrap_decode(monkeypatch, broken)


def _half_batch_left_out(monkeypatch):
    def broken(dec):
        def f(params, state, last, lens):
            logits, new = dec(params, state, last, lens)
            half = logits.shape[0] // 2
            return logits.at[:half].set(0.0), new
        return f
    _wrap_decode(monkeypatch, broken)


def _never_admitted(monkeypatch):
    from repro.serving import disagg
    orig = disagg.PDCluster.submit

    def submit(self, req):
        if req.rid % 4 != 3:
            orig(self, req)
    monkeypatch.setattr(disagg.PDCluster, "submit", submit)


def _exchange_left_out(monkeypatch):
    from repro.serving import kvtransfer
    monkeypatch.setattr(kvtransfer, "insert",
                        lambda cfg, pool, payload, slot: pool)


@pytest.mark.parametrize("fault,caught_by", [
    (_state_unchanged, "logit_gap"), (_half_batch_left_out, "logit_gap"),
    (_exchange_left_out, "logit_gap"), (_alter_third_token, "logit_gap"),
    (_never_admitted, "unfinished")],
    ids=["state_unchanged", "half_batch", "exchange_left_out",
         "token_altered", "never_admitted"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, caught_by):
    fault(monkeypatch)
    res = run()
    assert res["correct"] is False, res["compared"]
    c = res["compared"][caught_by]
    assert c["value"] > c["limit"], res["compared"]


def test_the_trace_covers_the_busiest_stretch():
    from chipbench import traffic
    arr = [traffic.Arrival(i, t, np.zeros((L,), np.int32), 4, 0, ph)
           for i, (t, L, ph) in enumerate([
               (0.5, 900, "warmup"), (1.2, 50, "window"),
               (4.0, 300, "window"), (5.5, 400, "window"),
               (9.5, 600, "window"), (11.5, 900, "drain")])]
    sched = traffic.Schedule(arr, 1.0, 10.0, 5.0, 1.0)
    # 4.0 opens 300 + 400 tokens; 9.5 is pulled back to 9.0, the last
    # start whose stretch ends inside the window, and holds 600
    assert harness.busiest_stretch(sched) == 4.0
    sched.arrivals[4].prompt = np.zeros((800,), np.int32)
    assert harness.busiest_stretch(sched) == 9.0


def test_new_mix_and_metric_files_are_found(tmp_path):
    """A cell on a new mix, with a new per-layer metric, from new files
    only: the harness finds both by name."""
    root = tmp_path / "chip"
    for sub in ("builders", "refs", "layer_metrics", "configs", "mixes",
                "cells"):
        shutil.copytree(BENCH_DIR / sub, root / sub)
    shutil.copy(BENCH_DIR / "peaks.json", root / "peaks.json")
    shutil.copy(DATA / "tiny-dense.json", root / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-chat.json", root / "mixes" / "tiny-bursts.json")
    shutil.copy(DATA / "tiny.json", root / "cells" / "tiny.tiny-bursts.json")
    (root / "layer_metrics" / "sampled_requests.py").write_text(
        "def read(run):\n    return float(len(run.loop.outcomes))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "tiny.tiny-bursts", "config": "tiny",
                               "traffic": "tiny-bursts", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "sampled_requests", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "cluster", "moves": "ttft_p90_ms",
                               "workloads": ["tiny.tiny-bursts"]})
    cell = find_cell(bench, "tiny.tiny-bursts", root=root)
    assert cell.mix == load_json(DATA / "tiny-chat.json")
    assert "sampled_requests" in [m["name"] for m in cell.per_layer]
    res = run(cell, trace=True)
    assert res["metrics"]["sampled_requests"]["value"] == res["attempted"]
    assert list(res)[-1] == "compared" and "breakdown" in res
