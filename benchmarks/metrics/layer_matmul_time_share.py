"""Device time of the instructions that a layer's weight names (``wq`` ...
``w_down``, ``wq_a`` ... ``wkv_b``: the parameter key the serving path reads,
the module flax names in training, forward and transposed; a routed layer's
``moe*`` left to its own readers) over device busy time. A fusion carries one
op's name: a bias add, an activation or a residual add fused into a product is
counted with it, and a product fused under another name is not."""

from benchmarks.lib import sublayers


def read(run, trace):
    seconds = sublayers.seconds_of(run, trace, sublayers.weight_of)
    return 100.0 * seconds / trace.busy_s if seconds else None
