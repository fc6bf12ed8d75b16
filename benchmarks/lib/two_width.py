"""What the readers of a pattern whose two attention kinds differ in MORE than
the band share (``mimo_v2``: kv heads by kind, keys of 192 columns beside values
of 128, a sink a query head in the sliding kind's softmax): the mechanisms'
costs by the mathematics alone, WITH THE KEY'S WIDTH AND THE VALUE'S COUNTED
APART, GQA's keys and values read once a KEY-VALUE head (not once a query
head), the band's keys and not the square's, and the sink's scalars; and the
traced window's decode CHAINS paired with their own runs on the device
(``lib/swa.py::_paired``), each with the seconds of the two paged kernels
inside it (``swa_paged_attn`` over the ring, ``paged_attn`` over the global
table) and what its ``serve:dispatch`` span says of its rows.

A padded page would be a layout: the costs below count a key's 192 columns
whatever lies in memory. In a trace of a program without the kernels or the
args (the parent of the PR that brought them) everything here finds nothing
and the readers return None."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.lib import harness, kernels, routed, swa

BF16, F32 = 2, 4
RING_KERNEL, GLOBAL_KERNEL = swa.SWA_DECODE_KERNEL, kernels.PAGED_KERNEL


def decode_cost(keys_read: float, row_steps: float, calls: float, heads: int, kv_heads: int, key_dim: int,
                value_dim: int, sink: bool = False) -> Tuple[float, float]:
    """(FLOPs, bytes) of single-token attention over paged keys of ``key_dim`` columns and values of
    ``value_dim``: ``keys_read`` the sum over rows and steps of the keys a query sees, ``row_steps`` the (row,
    step) pairs, ``calls`` the kernel's calls (a layer-step each). A (query head, key) pair costs ``2 key_dim``
    FLOPs of ``q . k`` and ``2 value_dim`` of ``p . v``; a key and its value are read ONCE for all the query
    heads of their key-value head; a (row, step) reads its query (``heads x key_dim``) and writes its output
    (``heads x value_dim``); a call reads the sinks, ``heads`` float32 scalars."""
    flops = 2.0 * keys_read * heads * (key_dim + value_dim)
    bytes_ = (keys_read * kv_heads * (key_dim + value_dim) * BF16 + row_steps * heads * (key_dim + value_dim) * BF16
              + (calls * heads * F32 if sink else 0.0))
    return flops, bytes_


def attended(tokens: int, width) -> int:
    """Sum over the queries ``t = 0 .. tokens - 1`` of the keys each sees: ``min(t + 1, width)`` (None: every key
    up to its own)."""
    if width is None or tokens <= width:
        return tokens * (tokens + 1) // 2
    return width * (width + 1) // 2 + (tokens - width) * width


def prefill_cost(prompts, width, heads: int, kv_heads: int, key_dim: int, value_dim: int,
                 sink: bool = False) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's attention over fresh prompts of the given lengths under a band of ``width``
    keys (None: the causal mask alone): a (query head, attended key) pair costs ``2 key_dim + 2 value_dim`` FLOPs;
    q is read and o written a query head (``key_dim`` and ``value_dim`` columns), k and v read a KEY-VALUE head,
    each once; the sinks once."""
    prompts = [int(n) for n in prompts]
    pairs, tokens = sum(attended(n, width) for n in prompts), sum(prompts)
    flops = 2.0 * heads * (key_dim + value_dim) * pairs
    bytes_ = float(tokens * (heads + kv_heads) * (key_dim + value_dim) * BF16 + (heads * F32 if sink else 0))
    return flops, bytes_


def paired_chains(run) -> List[Dict[str, object]]:
    """The window's whole decode chains whose ``serve:dispatch`` span says ``global_tokens`` (the sum over the
    chain's rows and steps of the keys a global layer's table holds for the query) beside ``ring_tokens``:
    ``args``, ``run_s`` and the two paged kernels' seconds under their own names."""
    out = swa._paired(run, "chain", kernels.CHAIN_PROGRAM, "global_tokens", (RING_KERNEL, GLOBAL_KERNEL))
    harness.say(two_width_paired_chains=len(out), ring_paged_s=sum(c[RING_KERNEL] for c in out),
                global_paged_s=sum(c[GLOBAL_KERNEL] for c in out), run_s=sum(c["run_s"] for c in out))
    return out


def decode_roofline(run, kind: str):
    """100 x the least time by the roofline for the paired chains' attention of ``kind`` (``"sliding"``: the
    ring's kernel at ``ring_tokens``; ``"global"``: the global table's at ``global_tokens``; the architecture
    file's ``paged_decode_cost``, times the layers of that kind) over that kernel's device seconds in those
    chains' own runs; None where the window holds no whole chain or the architecture has no such cost."""
    from benchmarks.lib import costs, peaks

    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, "paged_decode_cost"):
        return None
    kernel, said = (RING_KERNEL, "ring_tokens") if kind == "sliding" else (GLOBAL_KERNEL, "global_tokens")
    calls = [c for c in paired_chains(run) if c[kernel] > 0]
    seconds = sum(c[kernel] for c in calls)
    if not seconds:
        return None
    keys = sum(float(c["args"][said]) for c in calls)
    # (``row_steps``: the (row, step) pairs the chain's budgets plan, what both sums run over; a chain of ``k``
    # steps calls the kernel ``k`` times a layer)
    row_steps = sum(float(c["args"]["row_steps"]) for c in calls)
    steps = sum(float(c["args"]["k"]) for c in calls)
    flops, bytes_ = arch.paged_decode_cost(cfg, kind, keys, row_steps, steps)
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    n = arch.sliding_layers(cfg) if kind == "sliding" else arch.full_layers(cfg)
    least *= n
    harness.say(**{kind + "_decode_least_s": least}, bound=bound, kernel_s=seconds, chains=len(calls), keys=keys)
    return 100.0 * least / seconds


def attention_share_of_chains(run, trace):
    """100 x the device seconds under the scopes ``swa`` + ``attn_full`` in the chain program over the chain
    program's own device seconds (its instructions', mean over the chips); None without the scopes."""
    from benchmarks.lib import scopes, spans

    under = routed.seconds_under(run, trace, (swa.SWA_SCOPE, swa.FULL_SCOPE), (kernels.CHAIN_PROGRAM,))
    path = spans.trace_file(run)
    if not under or path is None:
        return None
    whole = sum(i.seconds for i in scopes.instructions(path) if i.program == kernels.CHAIN_PROGRAM) / trace.n_devices
    return 100.0 * under / whole if whole else None


def bytes_held(run) -> List[Tuple[float, float]]:
    """(ring bytes, global bytes) the rows of every ``serve:dispatch`` span of the traced window hold, each page
    at its own class's geometry, where the span says them."""
    from benchmarks.lib import spans

    return [(float(s.args["ring_bytes_held"]), float(s.args["global_bytes_held"]))
            for s in spans.named(spans.of_run(run), "serve:dispatch")
            if "ring_bytes_held" in s.args and "global_bytes_held" in s.args]


def ring_turns(run) -> List[float]:
    """Of every decode chain of the traced window that says ``ring_turns``: the ring pages a sliding layer of its
    rows starts writing over, a live row."""
    from benchmarks.lib import spans

    return [float(s.args["ring_turns"]) / float(s.args["live"])
            for s in spans.named(spans.of_run(run), "serve:dispatch", kind="chain")
            if "ring_turns" in s.args and float(s.args.get("live", 0)) > 0]
