"""Run the autotuner over the bench headline config and write AUTOTUNE.json.

Reference analog: ``autotuning/autotuner.py:404 tune()`` producing the
experiment table + chosen config (round-3 verdict item 9: a committed
artifact of the tuner choosing a config on real hardware). On the TPU this
reproduces PERF.md's scan/fused-CE table automatically; ``bench.py`` consumes
the artifact (model-level knobs for the headline run) when present.

Usage:  python tools/run_autotune.py [--steps N] [--out AUTOTUNE.json]
        [--cpu-smoke]   (tiny model on CPU — validates the plumbing only)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--out", default=os.path.join(REPO, "AUTOTUNE.json"))
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny model on CPU (plumbing check, not a perf artifact)")
    args = ap.parse_args()

    if args.cpu_smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if args.cpu_smoke:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from deepspeed_tpu.autotuning import Autotuner
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    on_tpu = jax.default_backend() == "tpu"
    if args.cpu_smoke:
        dims = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
                    num_layers=2, num_heads=4, max_seq_len=64)
        model_kw = dict(dims)
        seq, micros, stages, gas = 32, (1,), (1,), 1
    else:
        # the bench.py headline config's dimensions — IMPORTED so the tuner
        # and the bench cannot drift; recorded in the artifact and rejected
        # by bench._autotune_overrides on mismatch
        from bench import GPT2_HEADLINE_DIMS

        dims = dict(GPT2_HEADLINE_DIMS)
        model_kw = dict(dims, dtype=jax.numpy.bfloat16)
        seq, micros, stages, gas = 1024, (4, 8), (1,), 8

    def factory(**overrides):
        return causal_lm_spec(TransformerConfig(**model_kw, **overrides),
                              example_seq_len=seq)

    def batch_fn(s):
        rng = np.random.default_rng(s)
        # a POOL with rows for the largest candidate; the tuner slices each
        # candidate's train_batch_size rows out of it
        n_dev = len(jax.devices())
        return {"input_ids": rng.integers(
            0, dims["vocab_size"], (max(micros) * gas * n_dev, seq), dtype=np.int32)}

    # match the CONSUMER's step shape (bench.py headline: gas + clipping) —
    # a micro that wins at gas=1 need not win at gas=8
    base = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.1}},
            "gradient_accumulation_steps": gas,
            "gradient_clipping": 1.0,
            "bf16": {"enabled": not args.cpu_smoke},
            "steps_per_print": 100000}
    tuner = Autotuner(
        factory(), base,
        micro_batch_candidates=micros,
        stage_candidates=stages,
        remat_candidates=(False,),
        model_factory=factory,
        # the PERF.md round-3 table's model-level knobs, plus round-5
        # flash-kernel scheduling candidates (attn_kwargs flows through
        # TransformerConfig -> causal_attention -> pallas kernel; dropped on
        # the XLA path) so the tuner can pick kernel blocking on hardware
        model_override_candidates=(
            {}, {"scan_layers": False},
            {"scan_layers": False, "fused_ce": False},
            {"scan_layers": False, "fused_ce": False,
             "attn_kwargs": {"block_q": 512, "block_k": 512, "k_splits": 2}},
            {"scan_layers": False, "fused_ce": False,
             "attn_kwargs": {"block_q": 1024, "block_k": 1024, "k_splits": 4}},
        ) if not args.cpu_smoke else ({}, {"scan_layers": False},
                                      {"scan_layers": False, "fused_ce": False}),
    )
    best, results = tuner.tune(steps=args.steps, batch_fn=batch_fn)

    artifact = {
        "backend": jax.default_backend(),
        "plumbing_smoke_only": bool(args.cpu_smoke),
        "model_dims": dims,
        "best_config": best,
        "best_model_overrides": tuner.best_overrides or {},
        "table": [
            {"config": {k: v for k, v in r.config.items()},
             "throughput_samples_per_s": round(r.throughput, 2),
             "error": r.error}
            for r in results
        ],
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {args.out}: best micro="
          f"{best['train_micro_batch_size_per_gpu']} overrides={tuner.best_overrides}")


if __name__ == "__main__":
    main()
