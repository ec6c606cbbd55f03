#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once, on the chip it is started on.

    python3 benchmarks/chip/run.py --workload qwen2-0.5b.chat-steady \\
        --seed 7 --seconds 30 --trace 0

From the checkout's root, with nothing set beforehand.  The cell, its
configuration, mix and metrics are found by name from ``BENCHMARK.json``
(see ``chipbench/discover.py``).  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces part of the window with JAX's
profiler and reports its per-layer metrics, the device's busy seconds and a
breakdown.  Earlier lines of standard output say how the run went; the last
line is one JSON object.  The numbers compared for ``correct`` are the last
lines of standard error, and the last key of that object.

Where JAX finds no TPU, or fewer chips than the cell asks for, the run exits
with code 2 and prints no result.  JAX's persistent compilation cache is
``benchmarks/chip/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def log(msg: str):
    print(msg, flush=True)


def open_cell(workload: str):
    """The cell, with the program and JAX's compilation cache set up; None
    (after saying why) where JAX finds fewer TPU chips than it asks for."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(HERE / ".jax_cache"))
    sys.path[:0] = [str(REPO / "src"), str(HERE)]
    from chipbench.discover import find_cell, load_json
    cell = find_cell(load_json(REPO / "BENCHMARK.json"), workload)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"{workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}")
    return cell


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    cell = open_cell(args.workload)
    if cell is None:
        return 2

    from chipbench import harness
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, log=log, out_dir=HERE / ".out")
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
