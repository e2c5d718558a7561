"""Device time per step of the update sums' segment ids: the operations
traced under ``chol.segment_ids`` (the ``searchsorted`` of
``engines.segment_sums``, inside ``chol.assemble``)."""


def read(run):
    from chipbench import spans

    return spans.scope_ms(run, "chol.segment_ids")
