"""Device time of the KV pool's copies over device busy time: what runs under
the program's scopes ``page_view``, ``pool_scan`` and ``kv_write`` (kernels
have scopes of their own and are not in it), plus the whole-pool ``copy``
instructions the compiler inserts, which carry no ``op_name`` and are matched
by the pool's own shape ``[layers, slots, kv_heads, head_dim]`` in the two
serving programs. Both parts are printed."""

import re

from benchmarks.lib import costs, harness, kernels, scopes, spans


def read(run, trace):
    scoped = scopes.seconds_under(run, trace, "page_view", "pool_scan", "kv_write")
    if not scoped:
        return None
    cfg = run["config"]
    pool = re.compile(r"\[%d,\d+,%d,%d\]" % (
        cfg["num_hidden_layers"], cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
        costs.head_dim(cfg)))
    by_shape = sum(i.seconds for i in scopes.instructions(spans.trace_file(run))
                   if i.program in (kernels.CHAIN_PROGRAM, kernels.PREFILL_PROGRAM)
                   and i.category == "data formatting" and pool.search(i.text)
                   and scopes.innermost_scope(i.op_name) == scopes.UNSCOPED) / trace.n_devices
    harness.say(pool_copy_under_scopes_s=scoped, pool_copy_by_shape_s=by_shape,
                busy_s=trace.busy_s)
    return 100.0 * (scoped + by_shape) / trace.busy_s
