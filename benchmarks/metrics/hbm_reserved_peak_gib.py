"""Peak of what the runtime set aside for programs' temporaries on the fullest chip
(memory_stats peak_bytes_reserved) at the window's end."""


def read(run, trace):
    return run["memory"]["peak_bytes_reserved"] / 2**30 or None
