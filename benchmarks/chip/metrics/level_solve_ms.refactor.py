"""Device time per step of the level-scheduled triangular solves and the
right-hand side's (un)staging, traced under ``chol.level_solve``."""


def read(run):
    from chipbench import spans

    return spans.scope_ms(run, "chol.level_solve")
