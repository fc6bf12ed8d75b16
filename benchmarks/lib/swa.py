"""What the readers of a sliding kind share: device seconds under the program's
scopes ``swa`` (a sliding layer's attention: the kernel, its page writes) and
``attn_full`` (the full layer's) in the two serving programs; the traced
window's PREFILLS paired with their own runs on the device, each with the
seconds of the two flash kernels inside it (``swa_flash_fwd``, the forward under
the band; ``flash_fwd``, the causal one) and with the rows it fed (the ``fed``
arg of its ``serve:dispatch`` span, ``start:count`` a row), from which the
architecture file's ``swa_prefill_cost`` and ``full_prefill_cost`` count the
work; the window's decode CHAINS paired likewise, each with the seconds of the
kernel ``swa_paged_attn`` and the tokens its rows' rings held (``ring_tokens``
on its ``serve:dispatch`` span); the pages the calls' rows hold by class
(``ring_pages``, ``global_pages``, ``one_class_pages`` on the same spans); and,
from the benchmark's own spans around ``engine.decode_chain`` over the WHOLE run
(``run["calls"]``), what a decode step takes on the wall, which does not wait
for the profiler's three seconds to catch a chain.

A call is paired as ``lib/dsa.py::paired_prefills`` pairs one: its
``serve:dispatch`` and ``serve:fetch`` spans wholly inside the window, its run
between the start of the one and the end of the other, so that a call half
inside the window is on neither side of a roofline share. In a trace of a
program without the scopes, the kernels or the args (the parent of the PR that
brought them) everything here finds nothing and the readers return None."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.lib import harness, kernels, mhc, routed, spans, stats, xplane

SWA_SCOPE, FULL_SCOPE = "swa", "attn_full"
SWA_PREFILL_KERNEL, FULL_PREFILL_KERNEL, SWA_DECODE_KERNEL = "swa_flash_fwd", "flash_fwd", "swa_paged_attn"


def seconds(run, trace, scope: str) -> float:
    """Device seconds under ``scope`` in ``step`` and ``chain``, mean over the chips."""
    return routed.seconds_under(run, trace, (scope,))


def fed_rows(text: str) -> List[Tuple[int, int]]:
    """``"0:8192 0:16000"`` -> ``[(0, 8192), (0, 16000)]``: each row's first position and the tokens it was fed."""
    return [(int(a), int(b)) for a, _, b in (part.partition(":") for part in text.split())]


def _paired(run, kind: str, program: str, needs: str, kernel_names: Tuple[str, ...]) -> List[Dict[str, object]]:
    """One entry a call of ``kind`` of the traced window whose ``serve:dispatch``
    span carries the arg ``needs`` and whose own run of ``program`` on the first
    chip was found: the span's ``args``, ``run_s`` and each named kernel's
    device seconds inside the run."""
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
    runs = [(a, b) for name, (a, b) in zip(mod_names, mod_iv) if xplane.module_name(name) == program]
    kernel = np.asarray([xplane.split_instruction(text)[0].partition(".")[0] for text in op_names])

    def whole(s):  # read_spans clips a span to the window: one cut by an edge lies ON it
        return s.start_s > lo and s.end_s < hi

    def inside(name, a, b):
        if not len(op_iv):
            return 0.0
        at = (op_iv[:, 0] >= a) & (op_iv[:, 1] <= b) & (kernel == name)
        return float((op_iv[at, 1] - op_iv[at, 0]).sum())

    seen = spans.read_spans(path)
    fetches = [f for f in spans.named(seen, "serve:fetch") if f.args.get("kind") == kind]
    out = []
    for d in spans.named(seen, "serve:dispatch", kind=kind):
        if needs not in d.args:
            continue
        if kind == "chain":
            fetch = next((f for f in fetches if f.args.get("chain") == d.args.get("chain")), None)
        else:
            fetch = next((f for f in fetches if f.start_s >= d.start_s), None)
        if fetch is None or not (whole(d) and whole(fetch)):
            continue
        own = [(a, b) for a, b in runs if a >= d.start_s - mhc.CLOCK_SKEW_S and b <= fetch.end_s + mhc.CLOCK_SKEW_S]
        if own:
            a, b = own[-1]
            out.append({"args": d.args, "run_s": b - a, **{name: inside(name, a, b) for name in kernel_names}})
    return out


def paired_prefills(run) -> List[Dict[str, object]]:
    """The window's whole prefills: ``rows`` (``fed_rows``), ``run_s``, and the
    two flash kernels' seconds under their own names."""
    out = [dict(c, rows=fed_rows(str(c["args"]["fed"])))
           for c in _paired(run, "prefill", kernels.PREFILL_PROGRAM, "fed", (SWA_PREFILL_KERNEL, FULL_PREFILL_KERNEL))]
    harness.say(swa_paired_prefills=len(out), fed_tokens=sum(n for c in out for _, n in c["rows"]),
                swa_flash_s=sum(c[SWA_PREFILL_KERNEL] for c in out),
                full_flash_s=sum(c[FULL_PREFILL_KERNEL] for c in out), run_s=sum(c["run_s"] for c in out))
    return out


def paired_chains(run) -> List[Dict[str, object]]:
    """The window's whole decode chains: ``ring_tokens`` (the sum over the
    chain's rows and steps, as its budgets plan them, of the tokens a sliding
    layer's ring holds for the query: ``min(position + 1, window)``), ``run_s``
    and the kernel ``swa_paged_attn``'s seconds."""
    out = [dict(c, ring_tokens=float(c["args"]["ring_tokens"]))
           for c in _paired(run, "chain", kernels.CHAIN_PROGRAM, "ring_tokens", (SWA_DECODE_KERNEL,))]
    harness.say(swa_paired_chains=len(out), ring_tokens=sum(c["ring_tokens"] for c in out),
                swa_paged_s=sum(c[SWA_DECODE_KERNEL] for c in out))
    return out


def prefill_roofline(run, cost_name: str, kernel: str, layers_name: str):
    """100 x the least time by the roofline for the paired prefills' attention
    (the architecture file's ``cost_name`` over the prompts they fed, times the
    layers of that kind) over the kernel's device seconds in their runs; None
    where nothing was found."""
    from benchmarks.lib import costs, peaks

    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, cost_name):
        return None
    calls = [c for c in paired_prefills(run) if c[kernel] > 0]
    seconds_ = sum(c[kernel] for c in calls)
    if not seconds_:
        return None
    flops, bytes_ = getattr(arch, cost_name)(cfg, [n for c in calls for start, n in c["rows"] if start == 0])
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    least *= getattr(arch, layers_name)(cfg)
    harness.say(**{cost_name + "_least_s": least}, bound=bound, kernel_s=seconds_, calls=len(calls))
    return 100.0 * least / seconds_


def decode_roofline(run):
    """100 x the least time by the roofline for the paired chains' reads of
    their rings (``swa_decode_cost`` at ``ring_tokens``: every token's key and
    value once a sliding layer) over ``swa_paged_attn``'s seconds in those
    chains' own runs; None where the window holds no whole chain."""
    from benchmarks.lib import costs, peaks

    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, "swa_decode_cost"):
        return None
    calls = [c for c in paired_chains(run) if c[SWA_DECODE_KERNEL] > 0]
    seconds_ = sum(c[SWA_DECODE_KERNEL] for c in calls)
    if not seconds_:
        return None
    # (the cost takes contexts; a chain's span says their sum under the window already)
    tokens = sum(c["ring_tokens"] for c in calls)
    flops, bytes_ = (x * tokens for x in arch.swa_decode_cost(cfg, [1]))
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    least *= arch.sliding_layers(cfg)
    harness.say(swa_decode_least_s=least, bound=bound, kernel_s=seconds_, chains=len(calls))
    return 100.0 * least / seconds_


def decode_step_ms(run):
    """Median over EVERY decode chain of the run of the chain's wall milliseconds
    a step (``row_steps / rows``: the steps its rows took), for a configuration
    whose architecture file knows a sliding kind (``swa_decode_cost``). The
    serving loop dispatches the next chain before it fetches this one's tokens,
    so a call returns when its chain is done on the device and the wall time of
    a call is a chain's time there; None where the run made no chain."""
    if not hasattr(run["architecture"], "swa_decode_cost"):
        return None
    per_step = [1e3 * (c["t1"] - c["t0"]) * c["rows"] / c["row_steps"]
                for c in run["calls"] if c["kind"] == "decode_chain" and c["row_steps"]]
    if not per_step:
        return None
    harness.say(swa_decode_chains=len(per_step), step_ms=stats.describe(per_step))
    return stats.median(per_step)


def pages_held(run) -> List[Tuple[float, float, float]]:
    """(ring pages, global pages, what one class of page would hold) of every
    ``serve:dispatch`` span of the traced window that says them."""
    return [(float(s.args["ring_pages"]), float(s.args["global_pages"]), float(s.args["one_class_pages"]))
            for s in spans.named(spans.of_run(run), "serve:dispatch")
            if all(k in s.args for k in ("ring_pages", "global_pages", "one_class_pages"))]
