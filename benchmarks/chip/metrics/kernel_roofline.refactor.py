"""The fused kernel's share of its roofline: the least time the chip needs
for the supernodes the kernel factored in the window (the larger of their
flops over the peak FLOP/s and their least bytes over the peak HBM
bandwidth, both counted from true extents) over the kernel's measured
device time.  The kernel computes in float32 at the highest matmul
precision, so the bf16 peak caps this share well below 100."""

#: the kernel's instruction: a TPU custom call named after the jitted
#: ``kernels.fused.fused_factor_syrk`` that wraps its ``pallas_call``
KERNEL = ("fused_factor_syrk", "custom-call")


def read(run):
    if run.trace is None or run.peak is None or not run.kernel_work:
        return None
    from chipbench import trace, work

    seconds, events = trace.matching_seconds(run.trace, *KERNEL)
    work_ = run.kernel_work
    if not events or seconds <= 0 or work_["flops"] <= 0:
        return None
    return 100.0 * work.least_seconds(work_["flops"], work_["bytes"],
                                      run.peak) / seconds
