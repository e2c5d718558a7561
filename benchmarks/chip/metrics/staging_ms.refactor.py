"""Host time per step of staging (``repro.core.device_store``): the index
plan's concatenation and upload, each level's chunk gather and upload, the
solve's auxiliary upload; the program's ``chol.stage`` spans."""


def read(run):
    from chipbench import spans

    return spans.span_ms(run, "chol.stage")
