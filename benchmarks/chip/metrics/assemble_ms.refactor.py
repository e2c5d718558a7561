"""Device time per step of group assembly (``repro.core.engines``): the
operations traced under ``chol.assemble`` in the group programs (pool
gather, update segment sums, padded panel gather, pack), the union taken so
that a loop and the fusions inside it count once."""


def read(run):
    from chipbench import spans

    return spans.scope_ms(run, "chol.assemble")
