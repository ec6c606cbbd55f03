"""Share of the cluster's working time in which nothing ran on the device:
1 - the device's busy time inside the traced ``bench.step``,
``bench.submit`` and ``bench.autoscale`` spans, over those spans' wall
time.  Time spent sleeping for the next arrival is left out: below the knee
that idle is the load's.  Layer: the device."""

WORK = ("bench.step", "bench.submit", "bench.autoscale")


def read(run):
    spans = [s for s in run.trace.spans if s.name in WORK]
    wall = sum(s.end - s.start for s in spans)
    if not run.trace.ops or not wall:
        return None
    busy = sum(run.trace.busy_in(s.start, s.end) for s in spans)
    return 100.0 * (1.0 - busy / wall)
