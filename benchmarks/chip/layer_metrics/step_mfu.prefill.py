"""The share of the chip's peak in the cluster calls that prefilled: model
operations of every program call (prefill, fused decode + chunk, decode)
made inside a traced ``bench.step`` or ``bench.submit`` that ran a prefill,
over the host's wall time of those calls times the bf16 peak.  Layer: the
device, whole step, on the prefill side.  Moves ttft_p90_ms, beside the
prefill attention kernel's roofline; ``step_mfu`` is every step's."""

from chipbench import flops


def read(run):
    if run.peaks is None:
        return None
    wall = ops = 0.0
    for _, dt, p0, p1, d0, d1 in run.calls.spans:
        if p1 > p0:
            wall += dt
            ops += flops.calls_flops(run.dims, run.calls.decode[d0:d1],
                                     run.calls.prefill[p0:p1])
    if not wall:
        return None
    return 100.0 * ops / (wall * run.peaks["bf16_flops_per_s"])
