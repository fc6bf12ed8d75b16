"""Device time that moves the KV pool over device busy time: what runs under
the program's scope ``kv_write`` (the scatters of new keys and values into the
pool, in place since PR 26), plus any data-formatting instruction of the two
serving programs under no scope of ours whose result has the pool's own shape:
a copy of the whole pool, which the compiler gives no ``op_name``. The shape is
the engine's own pool's, recorded by the serve runner (``run["kv_pool_shape"]``),
not one made up from a configuration's keys. Both parts are printed."""

from benchmarks.lib import harness, kernels, scopes, spans, xplane


def read(run, trace):
    scoped = scopes.seconds_under(run, trace, "kv_write")
    if not scoped or not run.get("kv_pool_shape"):
        return None
    pool = "[%s]" % ",".join(str(n) for n in run["kv_pool_shape"])
    by_shape = sum(i.seconds for i in scopes.instructions(spans.trace_file(run))
                   if i.program in (kernels.CHAIN_PROGRAM, kernels.PREFILL_PROGRAM)
                   and i.category == "data formatting"
                   and xplane.split_instruction(i.text)[2].endswith(pool)
                   and scopes.innermost_scope(i.op_name) == scopes.UNSCOPED) / trace.n_devices
    harness.say(pool_copy_under_scopes_s=scoped, pool_copy_by_shape_s=by_shape,
                pool_shape=pool, busy_s=trace.busy_s)
    return 100.0 * (scoped + by_shape) / trace.busy_s
