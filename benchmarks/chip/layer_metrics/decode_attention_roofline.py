"""The flash-decode kernel's share of its roofline: the least time the
chip needs for the bytes (and operations) of every traced call, at each
live row's true length, over the kernel's traced device time.  HBM bound at
every decode shape here.  Layer: kernels (kernels/decode_attention.py)."""

from chipbench import flops

KERNEL = "decode_attention"


def read(run):
    if run.peaks is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    if not t:
        return None
    cost = flops.Cost()
    for _, lens in run.calls.decode:
        cost += flops.decode_attention(run.dims, lens) * run.dims.layers
    least, _ = flops.least_seconds(cost, run.peaks["bf16_flops_per_s"],
                                   run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
