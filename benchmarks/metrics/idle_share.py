"""1 minus the union of device-operation intervals over the traced window."""


def read(run, trace):
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
