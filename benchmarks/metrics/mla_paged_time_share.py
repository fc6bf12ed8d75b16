"""Device time of the latent paged kernel (``mla_paged_attn``) in the
decode-chain program over device busy time."""

from benchmarks.lib import routed


def read(run, trace):
    seconds = routed.mla_seconds(run, trace)
    return 100.0 * seconds / trace.busy_s if seconds else None
