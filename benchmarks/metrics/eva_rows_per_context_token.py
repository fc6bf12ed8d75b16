"""Median over the traced decode chains of ``attended_rows`` over
``context_tokens`` on the ``dstpu:serve:dispatch`` spans: the cache rows EVA
attention reads a token of context (1.0 for full attention)."""

from benchmarks.lib import eva, stats


def read(run, trace):
    shares = [c["attended_rows"] / c["context_tokens"] for c in eva.chains(run) if c["context_tokens"]]
    return stats.median(shares) if shares else None
