"""Peak of live buffers on the fullest chip (memory_stats peak_bytes_in_use) at the window's end."""


def read(run, trace):
    return run["memory"]["peak_bytes_in_use"] / 2**30 or None
