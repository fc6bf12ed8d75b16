"""What the four readers of a learned sparse-attention indexer share: device
seconds under the program's scopes ``dsa_index`` (the indexer's products and
scores), ``dsa_select`` (the choice of the kept tokens) and ``dsa_attend``
(attention over them) in the two serving programs; the traced window's
PREFILLS paired with their own runs on the device, each with the seconds of the
two kernels inside it (``dsa_index``, the index scores; ``dsa_paged_attn``, the
latent walk under a per-query mask) and with the rows it fed (the ``fed`` arg of
its ``serve:dispatch`` span, ``start:count`` a row), from which the
architecture file's ``dsa_index_cost`` and ``dsa_attend_cost`` count the work;
and the counters of the decode chains and of the prefills (``tokens_scored``,
``tokens_kept`` on their ``serve:accept`` spans).

A call is paired as ``lib/mhc.py::paired_calls`` pairs one: its
``serve:dispatch`` and ``serve:fetch`` spans wholly inside the window, its run of
``step`` between the start of the one and the end of the other, so that a call
half inside the window is on neither side of a roofline share. In a trace of a
program without the scopes, the kernels or the args (the parent of the PR that
brought them) everything here finds nothing and the readers return None."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.lib import harness, kernels, mhc, routed, spans, xplane

SCOPES = ("dsa_index", "dsa_select", "dsa_attend")
INDEX_KERNEL = "dsa_index"
ATTEND_KERNEL = "dsa_paged_attn"


def seconds(run, trace) -> float:
    """Device seconds under the three scopes in ``step`` and ``chain``, mean over the chips."""
    return routed.seconds_under(run, trace, SCOPES)


def fed_rows(text: str) -> List[Tuple[int, int]]:
    """``"0:4096 0:8000"`` -> ``[(0, 4096), (0, 8000)]``: each row's first position and the tokens it was fed."""
    return [(int(a), int(b)) for a, _, b in (part.partition(":") for part in text.split())]


def paired_prefills(run) -> List[Dict[str, object]]:
    """One entry a prefill of the traced window whose own run on the first chip
    was found and whose span says what it fed: ``rows`` (``fed_rows``),
    ``run_s``, ``index_s`` and ``attend_s`` (the two kernels' device seconds
    inside the run)."""
    path = spans.trace_file(run)
    if path is None:
        return []
    planes, lines = mhc._device_lines(path)
    if xplane.OPS_LINE not in lines or xplane.MODULES_LINE not in lines:
        return []
    window = spans._window(planes)
    if window is None:
        return []
    lo, hi = window
    mod_names, mod_iv = xplane._events(lines[xplane.MODULES_LINE])
    op_names, op_iv = xplane._events(lines[xplane.OPS_LINE])
    runs = [(a, b) for name, (a, b) in zip(mod_names, mod_iv) if xplane.module_name(name) == kernels.PREFILL_PROGRAM]
    kernel = np.asarray([xplane.split_instruction(text)[0].partition(".")[0] for text in op_names])

    def whole(s):  # read_spans clips a span to the window: one cut by an edge lies ON it
        return s.start_s > lo and s.end_s < hi

    def inside(name, a, b):
        if not len(op_iv):
            return 0.0
        at = (op_iv[:, 0] >= a) & (op_iv[:, 1] <= b) & (kernel == name)
        return float((op_iv[at, 1] - op_iv[at, 0]).sum())

    seen = spans.read_spans(path)
    fetches = [f for f in spans.named(seen, "serve:fetch") if f.args.get("kind") == "prefill"]
    out = []
    for d in spans.named(seen, "serve:dispatch", kind="prefill"):
        if "fed" not in d.args:
            continue
        fetch = next((f for f in fetches if f.start_s >= d.start_s), None)
        if fetch is None or not (whole(d) and whole(fetch)):
            continue
        own = [(a, b) for a, b in runs if a >= d.start_s - mhc.CLOCK_SKEW_S and b <= fetch.end_s + mhc.CLOCK_SKEW_S]
        if own:
            a, b = own[-1]
            out.append({"rows": fed_rows(str(d.args["fed"])), "run_s": b - a,
                        "index_s": inside(INDEX_KERNEL, a, b), "attend_s": inside(ATTEND_KERNEL, a, b)})
    harness.say(dsa_paired_prefills=len(out), fed_tokens=sum(n for c in out for _, n in c["rows"]),
                index_kernel_s=sum(c["index_s"] for c in out), attend_kernel_s=sum(c["attend_s"] for c in out),
                run_s=sum(c["run_s"] for c in out))
    return out


def roofline_share(run, cost_name: str, seconds_key: str):
    """100 x the least time by the roofline for the paired prefills' work (the
    architecture file's ``cost_name`` over the rows they fed, every layer) over
    the kernel's device seconds in their runs; None where nothing was found."""
    from benchmarks.lib import costs, peaks

    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, cost_name):
        return None
    calls = [c for c in paired_prefills(run) if c[seconds_key] > 0]
    seconds_ = sum(c[seconds_key] for c in calls)
    if not seconds_:
        return None
    flops, bytes_ = getattr(arch, cost_name)(cfg, [row for c in calls for row in c["rows"]])
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    least *= arch.layers(cfg)
    harness.say(**{cost_name + "_least_s": least}, bound=bound, kernel_s=seconds_, calls=len(calls))
    return 100.0 * least / seconds_


def counters(run) -> List[Tuple[float, float, float]]:
    """(queries, tokens scored, tokens kept) of every ``serve:accept`` span of the traced window that says what
    its call's queries scored and kept, the two a query and layer: a decode chain's (its queries are the tokens it
    ``emitted``) and a prefill's (``queries``, the tokens it fed: the window always holds one whole)."""
    return [(float(s.args["queries" if s.args.get("kind") == "prefill" else "emitted"]),
             float(s.args["tokens_scored"]), float(s.args["tokens_kept"]))
            for s in spans.named(spans.of_run(run), "serve:accept")
            if "tokens_scored" in s.args and "tokens_kept" in s.args]
