"""Device time of the custom-call named ``paged_attn`` in the decode-chain
program over device busy time: ``paged_time_share`` by the kernel's name."""

from benchmarks.lib import kernels, scopes, spans


def read(run, trace):
    path = spans.trace_file(run)
    if path is None:
        return None
    scopes.report(path, trace.n_devices)
    seconds = sum(i.seconds for i in scopes.instructions(path)
                  if i.program == kernels.CHAIN_PROGRAM and i.name.split(".")[0] == "paged_attn"
                  and i.category == "custom-call") / trace.n_devices
    return 100.0 * seconds / trace.busy_s if seconds else None
