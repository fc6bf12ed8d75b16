"""Time with an all-gather, reduce-scatter, all-reduce or collective-permute in flight
over the traced window, mean over chips."""


def read(run, trace):
    return 100.0 * trace.collective_s / trace.window_s if trace.collective_s else None
