"""Host self time per step of the GMRES cycles of refinement
(``repro.core.refine._refine_one``): the program's ``chol.gmres`` spans
less the spans nested in them (the device solves), which leaves the
Arnoldi orthogonalization, the least-squares updates and the true
residuals."""


def read(run):
    from chipbench import spans

    return spans.span_ms(run, "chol.gmres", self_time=True)
