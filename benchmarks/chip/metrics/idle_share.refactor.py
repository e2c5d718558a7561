"""Share of the traced window in which the device ran no operation."""


def read(run):
    if run.trace is None:
        return None
    from chipbench import trace

    return 100.0 * (1.0 - trace.busy_seconds(run.trace)
                    / trace.window_seconds(run.trace))
