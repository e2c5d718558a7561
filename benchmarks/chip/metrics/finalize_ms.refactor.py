"""Device time per step of finalize (``repro.core.device_store``): the
device factor's concatenation, the panel gathers out of it and the
diagonal-block inversions, traced under ``chol.finalize``."""


def read(run):
    from chipbench import spans

    return spans.scope_ms(run, "chol.finalize")
