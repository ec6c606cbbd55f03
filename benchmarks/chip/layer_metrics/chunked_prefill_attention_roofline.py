"""The prefill attention kernel's share of its roofline: the least time the
chip needs for the causal operations and bytes of every traced call, at its
true length and offset, over the kernel's traced device time.  The bound
(compute for long prompts, memory for short ones) is printed with the
metric.  Layer: kernels (kernels/chunked_prefill_attention.py)."""

from chipbench import flops

KERNEL = "chunked_prefill_attention"


def read(run):
    if run.peaks is None:
        return None
    t = run.trace.kernel_seconds(KERNEL)
    if not t:
        return None
    cost = flops.Cost()
    for _, offset, n, _ in run.calls.prefill:
        cost += flops.chunked_prefill_attention(run.dims, offset, n) \
            * run.dims.layers
    least, bound = flops.least_seconds(cost, run.peaks["bf16_flops_per_s"],
                                       run.peaks["hbm_bytes_per_s"])
    run.note(f"{KERNEL}: {bound} bound, {cost.flops!r} flops, "
             f"{cost.bytes!r} bytes, {t!r} s on the device")
    return 100.0 * least / t
