"""A scope of the program in the two serving programs, and the traced window's
decode chains PAIRED with their own runs on the device, with the scope as a
PARAMETER: what ``lib/ssm.py`` does for ``ssm`` and ``lib/mhc.py`` for ``mhc``,
each with its scope written in, for any scope a later mixer opens (``gdn``
first). A chain half inside the window is on neither side of a roofline share.

A chain's ``serve:dispatch`` span (``kind=chain``) carries its ``chain`` id,
``k``, ``live`` and, from a program with recurrent state, ``state_rows``: the
live rows x steps whose state slots it updates, as its budgets plan it. Its
``serve:fetch`` span carries the same id, the chain after it may be dispatched
in between (PR 36), and its run of the program ``chain`` is the LAST one that
starts after its dispatch and ends by the end of its fetch. A chain counts only
if both spans lie wholly inside the window and its run is found. In a trace of
a program without the scope or the ``state_rows`` arg nothing is found, and the
readers return None."""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from benchmarks.lib import harness, kernels, routed, scopes, spans, stats, xplane

CLOCK_SKEW_S = 2e-3  # host and device clocks of one trace differ by about a millisecond


def seconds(run, trace, scope: str) -> float:
    """Device seconds under ``scope`` in ``step`` and ``chain``, mean over the chips."""
    return routed.seconds_under(run, trace, (scope,))


def paired_chains(run, scope: str) -> List[Dict[str, float]]:
    """One entry a decode chain of the traced window whose own run on the
    first chip was found: ``state_rows``, ``steps`` (those some row was live
    at: ``state_rows`` over the rows live at its start, rounded up), ``run_s``
    (the run's device seconds) and ``scope_s`` (those of its instructions under
    ``scope``)."""
    path = spans.trace_file(run)
    if path is None:
        return []
    under = {i.name for i in scopes.instructions(path)
             if i.program == kernels.CHAIN_PROGRAM and scope in i.op_name.split("/")}
    planes = list(spans.profile(path).planes)
    device = next((p for p in planes if p.name.startswith("/device:TPU:")), None)
    lines = {ln.name: ln for ln in device.lines} if device is not None else {}
    window = spans._window(planes)
    if not under or window is None or xplane.OPS_LINE not in lines or xplane.MODULES_LINE not in lines:
        return []
    lo, hi = window
    mod_names, mod_iv = xplane._events(lines[xplane.MODULES_LINE])
    op_names, op_iv = xplane._events(lines[xplane.OPS_LINE])
    runs = [iv for name, iv in zip(mod_names, mod_iv) if xplane.module_name(name) == kernels.CHAIN_PROGRAM]
    in_scope = np.asarray([xplane.split_instruction(text)[0] in under for text in op_names], bool)

    def whole(s):  # read_spans clips a span to the window: one cut by an edge lies ON it
        return s.start_s > lo and s.end_s < hi

    seen = spans.read_spans(path)
    fetches = {f.args.get("chain"): f for f in spans.named(seen, "serve:fetch", kind="chain")}
    out = []
    for d in spans.named(seen, "serve:dispatch", kind="chain"):
        fetch = fetches.get(d.args.get("chain"))
        if "state_rows" not in d.args or fetch is None or not (whole(d) and whole(fetch)):
            continue
        own = [(a, b) for a, b in runs if a >= d.start_s - CLOCK_SKEW_S and b <= fetch.end_s + CLOCK_SKEW_S]
        if not own:
            continue
        a, b = own[-1]
        inside = (op_iv[:, 0] >= a) & (op_iv[:, 1] <= b) & in_scope if len(op_iv) else np.zeros(0, bool)
        rows, live = float(d.args["state_rows"]), max(float(d.args.get("live", 1)), 1.0)
        out.append({"state_rows": rows, "steps": float(math.ceil(rows / live)), "run_s": b - a,
                    "scope_s": float((op_iv[inside, 1] - op_iv[inside, 0]).sum())})
    chain_ms = [1e3 * c["run_s"] for c in out]
    harness.say(paired_scope=scope, paired_chains=len(out),
                paired_chain_ms=stats.median(chain_ms) if chain_ms else None,
                paired_state_rows=sum(c["state_rows"] for c in out),
                paired_scope_s=sum(c["scope_s"] for c in out))
    return out
