"""Host time per step spent dispatching device programs: the program's
``chol.dispatch`` spans, one per ``DeviceEngine.stats["device_calls"]``
increment (the group programs, finalize, the level solves)."""


def read(run):
    from chipbench import spans

    return spans.span_ms(run, "chol.dispatch")
