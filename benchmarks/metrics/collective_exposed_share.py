"""Time with a collective in flight while no other operation runs on that chip over
the traced window, mean over chips."""


def read(run, trace):
    return 100.0 * trace.collective_exposed_s / trace.window_s if trace.collective_s else None
