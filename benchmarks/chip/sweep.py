#!/usr/bin/env python3
"""Finds a cell's knee once, on the chip: the cell's traffic at each of
several fixed rates, one window each, in one process on one seed's weights.

    python3 benchmarks/chip/sweep.py --workload yi-9b-pp2.burst-long \\
        --rates 1,1.5,2,3 --seed 11 --seconds 30

One JSON line per rate (its end-to-end readings and ``correct``), also
appended to ``benchmarks/chip/.out/sweep.jsonl``.  The knee is the highest
rate at which 90% of the sampled requests meet both SLOs; the cell's fixed
rate is about four fifths of it (``cells/<workload>.json``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.open_cell(args.workload)
    if cell is None:
        return 2
    import jax
    from chipbench import harness
    from chipbench.weights import make_params
    from repro.models import abstract_params
    params = make_params(abstract_params(
        cell.builder().model_config(cell.spec)), args.seed)
    jax.block_until_ready(params)
    out = run.HERE / ".out"
    out.mkdir(parents=True, exist_ok=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        t0 = time.perf_counter()
        res = harness.run(cell, args.seed, args.seconds, False,
                          t_start=t0, log=run.log,
                          out_dir=run.HERE / ".out", rate_rps=rate,
                          params=params)
        line = json.dumps({"workload": args.workload, "seed": args.seed,
                           "seconds": args.seconds, "sweep_rate": rate,
                           **res})
        print(line, flush=True)
        with open(out / "sweep.jsonl", "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
