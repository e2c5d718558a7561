"""Device time per step of the XLA group program's factor (POTRF + TRSM +
SYRK of the buckets past the kernel's VMEM bound, ``engines``): the
operations traced under ``chol.xla_factor``."""


def read(run):
    from chipbench import spans

    return spans.scope_ms(run, "chol.xla_factor")
