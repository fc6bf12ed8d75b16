"""The ring class's share of the pool BYTES the traced window's calls' rows
hold, each page at its own class's geometry (``ring_bytes_held``,
``global_bytes_held`` on the program's ``dstpu:serve:dispatch`` spans, summed
over the calls): what the sliding layers' rings cost beside the global pages
that grow."""

from benchmarks.lib import two_width


def read(run, trace):
    held = two_width.bytes_held(run)
    total = sum(r + g for r, g in held)
    return 100.0 * sum(r for r, _ in held) / total if total else None
