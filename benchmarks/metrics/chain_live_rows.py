"""Mean ``live`` (rows that hold a request) of the ``dstpu:serve:dispatch``
spans of decode chains: ``rows_per_chain`` as the program itself counts it."""

from benchmarks.lib import spans


def read(run, trace):
    live = [s.args["live"] for s in spans.named(spans.of_run(run), "serve:dispatch", kind="chain")
            if "live" in s.args]
    return sum(live) / len(live) if live else None
