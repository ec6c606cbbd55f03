#!/usr/bin/env python3
"""Reads the two numbers a cell's limit is set from, on the chip: the
widest logit gap of what the program served, and that of the float8
control (the reference in the program's place, one precision below
bfloat16), for each of several seeds, at the cell's own load and sizes.
``correct`` in each line is the harness's verdict on the control, which
has to read false.

    python3 benchmarks/chip/limits.py --workload qwen2-0.5b.chat-steady \\
        --seeds 1,2,3 --seconds 10

One JSON line per seed, also appended to
``benchmarks/chip/.out/limits.jsonl``.
The limit in ``cells/<workload>.json`` lies above the largest program
reading and below the smallest control reading (see PERF.md).
"""
from __future__ import annotations

import time

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.open_cell(args.workload)
    if cell is None:
        return 2
    from chipbench import harness
    out = run.HERE / ".out"
    out.mkdir(parents=True, exist_ok=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run(cell, seed, args.seconds, False,
                          t_start=time.perf_counter(), log=run.log,
                          out_dir=run.HERE / ".out", control=True)
        c = res["compared"]
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "program_gap": c["logit_gap"]["value"],
                           "control_gap": c["control_logit_gap"]["value"],
                           "compared_tokens": c["compared_tokens"]["value"],
                           "correct": res["correct"],
                           "metrics": res["metrics"]})
        print(line, flush=True)
        with open(out / "limits.jsonl", "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
