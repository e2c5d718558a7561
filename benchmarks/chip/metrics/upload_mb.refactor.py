"""Host-to-device bytes per step (DeviceEngine.stats["bytes_in"]): the
index plan re-uploaded at every factorization, the float32 values, and the
right-hand sides of the solves."""


def read(run):
    return run.counters["bytes_in"] / 1e6 / run.steps
