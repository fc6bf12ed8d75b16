"""Device time of the instructions whose ``op_name`` has no component that the
program wrote (a scope, a kernel's name, a parameter key, a flax module of the
layer stack: ``lib/sublayers.py::PROGRAM_NAMES``), over device busy time: a
serving cell's two programs, a training cell's all. Left out for a program
that writes none of the sub-layer names, where the share would say nothing."""

from benchmarks.lib import sublayers


def read(run, trace):
    if not sublayers.has_names(run):
        return None
    return 100.0 * sublayers.seconds_of(run, trace, lambda op_name: not sublayers.path(op_name)) / trace.busy_s
