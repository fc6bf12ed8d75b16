"""XLA backend compilations (cache hits included) inside the measured window; expected 0."""


def read(run, trace):
    return run["compiles_in_window"]
