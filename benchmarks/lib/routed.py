"""What the readers of a routed, latent-attention cell share: device seconds
under the program's scopes ``moe`` / ``moe_router`` / ``moe_experts`` /
``moe_shared`` and of its kernel ``mla_paged_attn``, and the decode chains'
``experts_touched`` from the ``dstpu:serve:accept`` spans. ``lib/scopes.py``'s
list of scopes is closed, so these match the path components of an
instruction's ``op_name`` themselves. In a trace of a program without these
names every function here finds nothing and the readers return None."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.lib import kernels, scopes, spans

MLA_KERNEL = "mla_paged_attn"
MOE_SCOPE = "moe"
MOE_PARTS = ("moe_experts", "moe_shared", "moe_router")
SERVING_PROGRAMS = (kernels.CHAIN_PROGRAM, kernels.PREFILL_PROGRAM)


def seconds_under(run, trace, names, programs=SERVING_PROGRAMS) -> float:
    """Device seconds, mean over the chips, of the instructions of
    ``programs`` whose ``op_name`` has one of ``names`` as a path component."""
    path = spans.trace_file(run)
    if path is None:
        return 0.0
    names = set(names)
    return sum(i.seconds for i in scopes.instructions(path)
               if i.program in programs and names & set(i.op_name.split("/"))) / trace.n_devices


def mla_seconds(run, trace) -> float:
    """Device seconds of the latent paged kernel in the decode-chain program."""
    return trace.op_seconds(
        lambda op: op.module == kernels.CHAIN_PROGRAM and kernels.kernel_name(op) == MLA_KERNEL)


def chains(run) -> List[Dict[str, float]]:
    """One entry a decode chain whose ``serve:accept`` span says
    ``experts_touched`` and whose ``serve:dispatch`` span lies in the window:
    the mean count of distinct experts a step read in a routed layer, the
    tokens it emitted and the rows that were live."""
    seen = spans.of_run(run)
    live = {s.args["chain"]: float(s.args["live"])
            for s in spans.named(seen, "serve:dispatch", kind="chain") if "live" in s.args}
    out = []
    for s in spans.named(seen, "serve:accept", kind="chain"):
        if "experts_touched" in s.args and s.args.get("chain") in live:
            out.append({"experts_touched": float(s.args["experts_touched"]),
                        "emitted": float(s.args["emitted"]), "live": live[s.args["chain"]]})
    return out


def decode_totals(run, routed_layers: int) -> Tuple[float, float, float]:
    """(experts read, token-steps, layer-steps) of the traced chains, summed
    over steps and routed layers, for ``routed_decode_cost``."""
    experts = tokens = pairs = 0.0
    for c in chains(run):
        steps = c["emitted"] / c["live"] if c["live"] else 0.0
        experts += c["experts_touched"] * steps * routed_layers
        tokens += c["emitted"] * routed_layers
        pairs += steps * routed_layers
    return experts, tokens, pairs
