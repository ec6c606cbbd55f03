"""Host time per cluster step: each traced ``bench.step`` span's wall time
less the device's busy time inside it, averaged over the traced steps.
Layer: the engine's host path (serving/engine.py, serving/kvtransfer.py)."""


def read(run):
    steps = [s for s in run.trace.spans if s.name == "bench.step"]
    if not run.trace.ops or not steps:
        return None
    host = sum((s.end - s.start) - run.trace.busy_in(s.start, s.end)
               for s in steps)
    return host / len(steps) / 1e6
