"""Host time per step of device-to-host transfers, each including the wait
for the device: the program's ``chol.readback`` spans, one per
``DeviceEngine.stats["transfers_out"]`` increment."""


def read(run):
    from chipbench import spans

    return spans.span_ms(run, "chol.readback")
