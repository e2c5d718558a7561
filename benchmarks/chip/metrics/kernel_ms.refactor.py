"""Device time of the fused Pallas kernel (kernels/fused.py) per step:
the summed durations of the trace events of its instruction."""

#: the kernel's instruction: a TPU custom call named after the jitted
#: ``kernels.fused.fused_factor_syrk`` that wraps its ``pallas_call``
KERNEL = ("fused_factor_syrk", "custom-call")


def read(run):
    if run.trace is None:
        return None
    from chipbench import trace

    seconds, events = trace.matching_seconds(run.trace, *KERNEL)
    if not events:
        return None
    return 1e3 * seconds / run.steps
