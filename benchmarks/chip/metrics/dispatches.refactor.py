"""Device programs dispatched per step (DeviceEngine.stats["device_calls"]):
group programs, finalize gathers and inversions, level solves."""


def read(run):
    return run.counters["device_calls"] / run.steps
