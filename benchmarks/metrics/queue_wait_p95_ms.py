"""p95 of first admission minus the time a request was due (RequestRecord), over the
requests admitted before the profiler was switched on: its start stalls the
serving loop, and the backlog after it is the tracer's."""

from benchmarks.lib import stats


def read(run, trace):
    until = run.get("trace_started_s") or float("inf")
    waits = [1e3 * r["queue_wait_s"] for r in run["requests"]
             if r["queue_wait_s"] is not None and r["due_s"] + r["queue_wait_s"] < until]
    return stats.percentile(waits, 95) if waits else None
