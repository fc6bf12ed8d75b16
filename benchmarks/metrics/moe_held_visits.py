"""Median over the traced decode chains of ``held_visits`` on the
``dstpu:serve:accept`` spans: how many (row, pick) pairs of a step landed on
an expert HELD by this chip in a routed layer, the mean over the chain's steps
and layers: the load a share cell was sized for (the wave x the picks a token
over the chips that share a layer, if the router spreads evenly). In a trace
of a program without the arg (one that holds every expert, or the parent of
the share) nothing is found and the metric is left out."""

from benchmarks.lib import spans, stats


def read(run, trace):
    visits = [float(s.args["held_visits"]) for s in spans.named(spans.of_run(run), "serve:accept", kind="chain")
              if "held_visits" in s.args]
    return stats.median(visits) if visits else None
