"""Refinement steps per solve (CholeskyFactor.ir_history), mean over the
window's steps."""


def read(run):
    if not run.refine_steps:
        return None
    return sum(run.refine_steps) / len(run.refine_steps)
