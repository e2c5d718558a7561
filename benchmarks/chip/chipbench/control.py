"""The control: the solver's own device path with every value it holds
between programs rounded to bfloat16.

The configuration states float32 device values.  The precision below it is
bfloat16, and holding values in bfloat16 is the step a later change is
tempted by: it halves the value upload, the update pool and the factor.
The solver's fused kernel does not lower in bfloat16 (its matmul needs a
32-bit accumulator), so the control keeps the float32 programs and rounds
what passes between them: every floating upload (the staged values, the
right-hand sides) and each group program's factored panels and update pool.
It must come out not correct.
"""
from __future__ import annotations

import numpy as np


def bf16_storage(eng):
    """Wrap ``eng`` in place; returns a function that undoes it."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    rnd = jax.jit(lambda x: x.astype(jnp.bfloat16).astype(x.dtype))
    put, fused_group = eng.put, eng.fused_group

    def put_bf16(x):
        if np.issubdtype(x.dtype, np.floating):
            x = x.astype(ml_dtypes.bfloat16).astype(x.dtype)
        return put(x)

    def fused_group_bf16(chunk, pool, g, lvl=-1, **kw):
        out = fused_group(chunk, pool, g, lvl, **kw)
        return (rnd(out[0]), rnd(out[1])) + tuple(out[2:])

    eng.put, eng.fused_group = put_bf16, fused_group_bf16

    def undo():
        del eng.put, eng.fused_group

    return undo
