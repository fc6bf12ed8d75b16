"""Device time under the train step's scope ``layer_scan`` and in no layer
(no ``layers`` component): what the layer scan itself does, the stacking of
the residuals it saves for the backward pass and the slices of its stacked
parameters and residuals, over device busy time."""

from benchmarks.lib import sublayers


def read(run, trace):
    seconds = sublayers.seconds_of(run, trace, sublayers.is_scan_stacking)
    return 100.0 * seconds / trace.busy_s if seconds else None
