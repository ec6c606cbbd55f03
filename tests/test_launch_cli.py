"""Launcher CLIs and examples execute end-to-end (subprocess smoke)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def _run(args, timeout=240, env=ENV):
    r = subprocess.run([sys.executable] + args, env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    return r.stdout


@pytest.mark.slow
def test_train_cli(tmp_path):
    out = _run(["-m", "repro.launch.train", "--arch", "qwen2-0.5b",
                "--smoke", "--steps", "6", "--batch", "2",
                "--seq-len", "32",
                "--checkpoint", str(tmp_path / "ck")])
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["steps"] == 6
    assert rec["loss_last"] > 0
    assert (tmp_path / "ck" / "index.json").exists()


@pytest.mark.slow
def test_serve_cli():
    out = _run(["-m", "repro.launch.serve", "--arch", "llama-3.1-8b",
                "--smoke", "--requests", "4", "--max-new", "4",
                "--chunk-size", "8", "--max-len", "128", "--slots", "4"])
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["completed"] == 4
    assert rec["convertibles"] == 1
    assert rec["kv_transfers"] > 0


@pytest.mark.slow
def test_dryrun_cli_single_pair():
    out = _run(["-m", "repro.launch.dryrun", "--arch", "qwen2_0_5b",
                "--shape", "decode_32k"], timeout=300)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["status"] == "ok"
    assert rec["chips"] == 256
    assert rec["bottleneck"] in ("compute", "memory", "collective")


def test_dryrun_cli_skip_reason():
    out = _run(["-m", "repro.launch.dryrun", "--arch", "yi_9b",
                "--shape", "long_500k"], timeout=300)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["status"] == "skipped"
    assert "sub-quadratic" in rec["reason"]


@pytest.mark.parametrize("example", ["burst_absorption.py"])
def test_example_runs(example):
    _run([os.path.join("examples", example)], timeout=300)
