"""What the two readers of the hyper-connections (mHC) share: device seconds
under the program's scope ``mhc`` (``mhc_mix``, ``mhc_pre``, ``mhc_post``
inside it) in the two serving programs, and the traced window's calls PAIRED
with their own runs on the device, so that a call half inside the window is on
neither side of a roofline share.

A prefill is dispatched and fetched at once: its ``serve:dispatch`` span
(``kind=prefill``; since PR 39 with ``tokens``, the call's live tokens) is
followed by its ``serve:fetch`` span, and its run of the program ``step`` lies
between the start of the one and the end of the other. A chain's
``serve:dispatch`` and ``serve:fetch`` spans carry its ``chain`` id, the chain
after it may be dispatched in between (PR 36), and its run of ``chain`` is the
LAST one that starts after its dispatch and ends by the end of its fetch; the
tokens it moved are ``emitted`` of its ``serve:accept`` span. A call counts
only if those spans lie wholly inside the window and its run is found.
``lib/scopes.py``'s list of scopes is closed, so the path components of an
instruction's ``op_name`` are matched here. In a trace of a program without the
scope or the ``tokens`` arg nothing is found and the readers return None."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.lib import harness, kernels, routed, scopes, spans, stats, xplane

MHC_SCOPE = "mhc"
CLOCK_SKEW_S = 2e-3  # host and device clocks of one trace differ by about a millisecond


def seconds(run, trace) -> float:
    """Device seconds under ``mhc`` in ``step`` and ``chain``, mean over the chips."""
    return routed.seconds_under(run, trace, (MHC_SCOPE,))


def _device_lines(path: str):
    planes = list(spans.profile(path).planes)
    device = next((p for p in planes if p.name.startswith("/device:TPU:")), None)
    lines = {ln.name: ln for ln in device.lines} if device is not None else {}
    return planes, lines


def paired_calls(run) -> List[Dict[str, float]]:
    """One entry a prefill or chain of the traced window whose own run on the
    first chip was found: ``kind``, ``tokens`` (a prefill's live tokens, a
    chain's emitted ones), ``run_s`` (the run's device seconds) and ``mhc_s``
    (those of its instructions under ``mhc``)."""
    path = spans.trace_file(run)
    if path is None:
        return []
    under = {(i.program, i.name) for i in scopes.instructions(path)
             if i.program in routed.SERVING_PROGRAMS and MHC_SCOPE in i.op_name.split("/")}
    planes, lines = _device_lines(path)
    if not under or xplane.OPS_LINE not in lines or xplane.MODULES_LINE not in lines:
        return []
    window = spans._window(planes)
    if window is None:
        return []
    lo, hi = window
    mod_names, mod_iv = xplane._events(lines[xplane.MODULES_LINE])
    op_names, op_iv = xplane._events(lines[xplane.OPS_LINE])
    runs = {}
    for name, (a, b) in zip(mod_names, mod_iv):
        runs.setdefault(xplane.module_name(name), []).append((a, b))
    op_name = [xplane.split_instruction(text)[0] for text in op_names]

    def whole(s):  # read_spans clips a span to the window: one cut by an edge lies ON it
        return s.start_s > lo and s.end_s < hi

    def mhc_in(program, a, b):
        inside = np.nonzero((op_iv[:, 0] >= a) & (op_iv[:, 1] <= b))[0] if len(op_iv) else []
        return float(sum(op_iv[i, 1] - op_iv[i, 0] for i in inside if (program, op_name[i]) in under))

    seen = spans.read_spans(path)
    fetches = spans.named(seen, "serve:fetch")
    emitted = {s.args.get("chain"): float(s.args["emitted"])
               for s in spans.named(seen, "serve:accept", kind="chain") if "emitted" in s.args}
    out = []
    for d in spans.named(seen, "serve:dispatch"):
        kind = d.args.get("kind")
        if kind == "prefill" and "tokens" in d.args:
            program, tokens = kernels.PREFILL_PROGRAM, float(d.args["tokens"])
            fetch = next((f for f in fetches if f.args.get("kind") == "prefill" and f.start_s >= d.start_s), None)
        elif kind == "chain" and d.args.get("chain") in emitted:
            program, tokens = kernels.CHAIN_PROGRAM, emitted[d.args["chain"]]
            fetch = next((f for f in fetches if f.args.get("kind") == "chain"
                          and f.args.get("chain") == d.args["chain"]), None)
        else:
            continue
        if fetch is None or not (whole(d) and whole(fetch)):
            continue
        own = [(a, b) for a, b in runs.get(program, ())
               if a >= d.start_s - CLOCK_SKEW_S and b <= fetch.end_s + CLOCK_SKEW_S]
        if own:
            a, b = own[-1]
            out.append({"kind": kind, "tokens": tokens, "run_s": b - a, "mhc_s": mhc_in(program, a, b)})
    prefill_ms = [1e3 * c["run_s"] for c in out if c["kind"] == "prefill"]
    harness.say(mhc_paired_calls=len(out), prefills=len(prefill_ms),
                paired_prefill_ms=stats.median(prefill_ms) if prefill_ms else None,
                paired_prefill_tokens=sum(c["tokens"] for c in out if c["kind"] == "prefill"),
                paired_chain_tokens=sum(c["tokens"] for c in out if c["kind"] == "chain"))
    return out
