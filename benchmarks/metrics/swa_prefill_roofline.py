"""Least time by the roofline for the sliding layers' attention of the traced
window's prefills (the architecture file's ``swa_prefill_cost``: a query at
``t`` attends ``min(t + 1, sliding_window)`` keys, THE BAND'S WORK, in every
sliding layer) over the device time of the kernel ``swa_flash_fwd`` in those
calls' own runs. The kernel runs whole cells of 512 x 512, masking the
diagonal and the band's lower edge, so it computes about 9/8 of the band."""

from benchmarks.lib import swa


def read(run, trace):
    return swa.prefill_roofline(run, "swa_prefill_cost", swa.SWA_PREFILL_KERNEL, "sliding_layers")
