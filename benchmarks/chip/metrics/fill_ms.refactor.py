"""Host time per step of the value fill (``CachedPlan.fill_storage``): the
program's ``chol.fill`` spans."""


def read(run):
    from chipbench import spans

    return spans.span_ms(run, "chol.fill")
