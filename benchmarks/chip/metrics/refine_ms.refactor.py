"""Host self time per step of refinement (``repro.core.refine``): the
program's ``chol.refine`` spans less the spans nested in them (the device
solves), which leaves the host residuals and updates."""


def read(run):
    from chipbench import spans

    return spans.span_ms(run, "chol.refine", self_time=True)
