"""Pages the traced window's calls' rows hold in TWO classes (the sliding
layers' rings + the full layers' global pages) over what ONE class of page
would hold for the same rows (every layer a page a block of positions), from
the program's own counts on its ``dstpu:serve:dispatch`` spans (``ring_pages``,
``global_pages``, ``one_class_pages``), summed over the calls. Lower is better;
100 says no layer keeps a window."""

from benchmarks.lib import swa


def read(run, trace):
    held = swa.pages_held(run)
    one_class = sum(o for _, _, o in held)
    return 100.0 * sum(r + g for r, g, _ in held) / one_class if one_class else None
