"""The whole cluster step's share of the chip's peak: model operations of
every prompt and generated token the traced steps processed, over the wall
time of the traced ``bench.step`` and ``bench.submit`` spans (a submit may
prefill on a Convertible Decoder) times the bf16 peak.  Layer: the device,
whole step.  Moves tpot_p90_ms."""

from chipbench import flops

WORK = ("bench.step", "bench.submit")


def read(run):
    if run.peaks is None:
        return None
    wall = sum(s.end - s.start for s in run.trace.spans if s.name in WORK)
    if not wall:
        return None
    ops = flops.calls_flops(run.dims, run.calls.decode, run.calls.prefill)
    return 100.0 * ops / (wall / 1e9 * run.peaks["bf16_flops_per_s"])
