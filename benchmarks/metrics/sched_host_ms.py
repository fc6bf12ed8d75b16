"""Median per decode chain of the host time outside dispatch and fetch: the
``dstpu:serve:schedule`` + ``serve:assemble`` + ``serve:accept`` spans that
carry one ``chain`` id, over the chains whose three spans lie in the window."""

from benchmarks.lib import spans, stats

PARTS = ("serve:schedule", "serve:assemble", "serve:accept")


def read(run, trace):
    per_chain = {}
    for s in spans.of_run(run):
        if s.name in PARTS and "chain" in s.args:
            per_chain.setdefault(s.args["chain"], {})[s.name] = s.seconds
    whole = [sum(parts.values()) for parts in per_chain.values() if len(parts) == len(PARTS)]
    return 1e3 * stats.median(whole) if whole else None
