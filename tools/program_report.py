#!/usr/bin/env python
"""Compiled-program inventory report (telemetry/programs.py).

Two uses:

  - **library**: ``render_report()`` formats whatever the process-global
    ``ProgramRegistry`` has captured — call it at the end of any run with
    telemetry enabled to see every program XLA built, its cost/memory
    analysis, collective content, and the HBM estimate-vs-actual ratio.
  - **CLI**: run standalone it builds tiny CPU engines (one training
    engine, one v2 serving engine), drives a few steps through each, and
    dumps the inventory — proving that the capture path records real
    train-step and decode-chain programs with nonzero flops/peak-HBM and a
    computed calibration ratio.

Exit 0 iff the inventory holds a captured training step AND a v2 serving
program, each with nonzero flops and peak HBM, and an ``hbm/estimate_ratio``
was computed for both scopes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _fmt_bytes(b: float) -> str:
    if b >= (1 << 30):
        return f"{b / (1 << 30):.2f}G"
    if b >= (1 << 20):
        return f"{b / (1 << 20):.2f}M"
    if b >= (1 << 10):
        return f"{b / (1 << 10):.1f}K"
    return f"{int(b)}"


def _fmt_flops(f: float) -> str:
    if f >= 1e12:
        return f"{f / 1e12:.2f}T"
    if f >= 1e9:
        return f"{f / 1e9:.2f}G"
    if f >= 1e6:
        return f"{f / 1e6:.2f}M"
    return f"{f:.3g}"


def render_report(registry=None) -> str:
    """Text inventory of every captured compile, in capture order."""
    if registry is None:
        from deepspeed_tpu.telemetry.programs import get_program_registry

        registry = get_program_registry()
    records = registry.records()
    header = (f"{'#':>3} {'program':<28} {'hlo':<12} {'instr':>6} "
              f"{'compile':>9} {'flops':>8} {'bytes':>8} {'peak_hbm':>9} "
              f"{'alias':>8} {'coll':>4} {'coll_B':>8} {'est_ratio':>9}")
    lines = ["compiled-program inventory "
             f"({len(records)} capture(s), {len(registry.labels())} program(s), "
             f"{registry.capture_failures} capture failure(s))",
             header, "-" * len(header)]
    for r in records:
        wall = (f"{r.compile_wall_s * 1e3:8.1f}ms"
                if r.compile_wall_s is not None else "        -")
        ratio = (f"{r.hbm_estimate_ratio:9.2f}"
                 if r.hbm_estimate_ratio is not None else "        -")
        lines.append(
            f"{r.index:>3} {r.label:<28} {r.fingerprint:<12} "
            f"{r.instruction_count:>6} {wall} {_fmt_flops(r.flops):>8} "
            f"{_fmt_bytes(r.bytes_accessed):>8} {_fmt_bytes(r.peak_hbm_bytes):>9} "
            f"{_fmt_bytes(r.alias_bytes):>8} {len(r.collectives):>4} "
            f"{_fmt_bytes(r.collective_bytes):>8} {ratio}")
        for c in r.collectives:
            lines.append(f"      - {c['kind']:<20} {_fmt_bytes(c['bytes']):>8} "
                         f"{c['replica_groups']}")
    for scope in ("train", "serving"):
        est = registry.hbm_estimate(scope)
        if est:
            lines.append(f"hbm estimate [{scope}]: {_fmt_bytes(est)} "
                         "(utils/hbm.py pre-flight; ratio = XLA peak / estimate)")
    return "\n".join(lines)


def _drive_probe_engines(steps: int, decode_tokens: int) -> None:
    """Build the tiny CPU engines and step them so the registry holds
    a real train-step and a real v2 decode-chain program."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.models.transformer import CausalLM
    import jax
    import jax.numpy as jnp

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq = 64
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 10_000,
            "telemetry": {"enabled": True},
        })
    r = np.random.default_rng(0)
    for step in range(steps):
        engine.train_batch({"input_ids": r.integers(
            0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)})

    module = CausalLM(cfg)
    params = module.init(
        {"params": jax.random.PRNGKey(0)},
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    v2 = InferenceEngineV2(cfg, params, config={
        "num_kv_blocks": 128, "kv_block_size": 16, "max_seqs": 4,
        "decode_chain": 4, "hbm_check": "warn"})
    prompts = [np.arange(6, dtype=np.int32), np.arange(9, dtype=np.int32)]
    v2.generate(prompts, max_new_tokens=decode_tokens)


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the full inventory as JSON")
    ap.add_argument("--no-probe", action="store_true",
                    help="report only what the process already captured "
                         "(library mode; skips building the probe engines)")
    ap.add_argument("--steps", type=int, default=2,
                    help="training steps to drive through the probe engine")
    ap.add_argument("--decode-tokens", type=int, default=8,
                    help="tokens to decode through the probe v2 engine")
    args = ap.parse_args(argv)

    from deepspeed_tpu.telemetry import configure as telemetry_configure
    from deepspeed_tpu.telemetry.programs import get_program_registry

    registry = get_program_registry()
    if not args.no_probe:
        telemetry_configure(enabled=True)
        _drive_probe_engines(args.steps, args.decode_tokens)

    print(render_report(registry), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({
                "records": [r.as_dict() for r in registry.records()],
                "hbm_estimates": {s: registry.hbm_estimate(s)
                                  for s in ("train", "serving")},
                "capture_failures": registry.capture_failures,
            }, f, indent=1)
        print(f"wrote {args.json}", flush=True)

    if args.no_probe:
        return 0
    # the gate: real programs, real costs, calibrated against the guard
    train = [r for r in registry.records() if r.label == "train_step"]
    serving = [r for r in registry.records() if r.label.startswith("v2:")]
    ok = {
        "train_step_captured": bool(train),
        "train_step_costs": any(r.flops > 0 and r.peak_hbm_bytes > 0 for r in train),
        "train_ratio": any(r.hbm_estimate_ratio is not None for r in train),
        "v2_captured": bool(serving),
        "v2_decode_chain": any(r.label.startswith("v2:decode_chain")
                               for r in serving),
        "v2_costs": any(r.flops > 0 and r.peak_hbm_bytes > 0 for r in serving),
        "v2_ratio": any(r.hbm_estimate_ratio is not None for r in serving),
    }
    print(json.dumps({"program_report": ok, "ok": all(ok.values())}), flush=True)
    return 0 if all(ok.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
