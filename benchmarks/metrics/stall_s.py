"""Seconds the traced window lost to slow calls: the sum of ``excess_s`` over
its ``dstpu:serve:stall`` spans, which the serving loop opens where it has
decided a slow call's cause (``excess_s``: the call's cadence less the median
of its class). 0.0 where the window's ``serve:fetch`` spans carry
``cadence_ms``, the quantity the loop's rule judges, and none stalled; in a
trace of a program that keeps no call log (no such arg) nothing is found and
the metric is left out. A window that reads above 0 took its other per-layer
numbers across a stall; each stall's ``cause`` is on its own line."""

from benchmarks.lib import harness, spans


def read(run, trace):
    seen = spans.of_run(run)
    stalls = spans.named(seen, "serve:stall")
    for s in stalls:
        harness.say(stall_in_window=s.args.get("cause"), chain=s.args.get("chain"), kind=s.args.get("kind"),
                    seconds=s.args.get("seconds"), excess_s=s.args.get("excess_s"),
                    in_fetch_s=s.args.get("in_fetch_s"), next_wait_s=s.args.get("next_wait_s"))
    if stalls:
        return sum(float(s.args["excess_s"]) for s in stalls)
    judged = any("cadence_ms" in s.args for s in spans.named(seen, "serve:fetch"))
    return 0.0 if judged else None
