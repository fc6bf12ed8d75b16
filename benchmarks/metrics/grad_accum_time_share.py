"""Device time under the engine's scopes ``grad_accum`` (the micro-batch
accumulator: its zeros, the gradients' cast and add) and ``grad_norm`` (the
norm of the accumulated gradients) over device busy time."""

from benchmarks.lib import sublayers


def read(run, trace):
    seconds = sublayers.seconds_of(run, trace, sublayers.is_grad_part)
    return 100.0 * seconds / trace.busy_s if seconds else None
