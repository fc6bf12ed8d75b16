#!/usr/bin/env python
"""MoE-at-scale smoke (ISSUE 15).

Exit-gated evidence, one JSON line (``--output`` also writes it to a
file):

  1. **ep x tp interpret smoke** — a dp2 x ep2 x tp2 CPU-mesh MoE engine
     (the composition the engine used to refuse) trains finite steps
     through the collective token dispatch, and a replay of its trained
     params through the plain GLOBAL math matches the mesh loss (the
     mis-routing gate).
  2. **expert-parallel v2 decode parity** — an ``ep_size=2`` v2 inference
     engine decodes greedy TOKEN-IDENTICAL to the ep=1 engine on the same
     bf16 checkpoint, with the collective dispatch actually traced.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _train_gates() -> dict:
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.topology import mesh as mesh_mod

    cfg = TransformerConfig(
        vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, max_seq_len=32, num_experts=4, moe_top_k=2,
        moe_capacity_factor=2.0)
    eng, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0,
                                  "param_persistence_threshold": 1},
            "mesh": {"dp": 2, "ep": 2, "tp": 2},
            "steps_per_print": 1000,
        }, seed=21)

    def tokens(seed):
        rng = np.random.default_rng(seed)
        return {"input_ids": rng.integers(0, 256, size=(4, 16), dtype=np.int32)}

    losses = [float(eng.train_batch(tokens(90 + i))["loss"]) for i in range(6)]
    # mis-routing gate: replay the engine's own params through plain global
    # math; the collective dispatch must reproduce it (the GSPMD constraint
    # path deviates ~0.5% here — the silent corruption the old refusal
    # guarded against)
    host = jax.device_get(eng.state.params)
    rng = jax.random.PRNGKey(7)
    mesh_mod.set_mesh(eng.mesh)
    mesh_loss = float(jax.jit(eng.model.loss_fn)(host, tokens(99), rng)[0])
    mesh_mod._ACTIVE_MESH = None
    global_loss = float(jax.jit(eng.model.loss_fn)(host, tokens(99), rng)[0])
    parity_rel = abs(mesh_loss - global_loss) / max(abs(global_loss), 1e-9)

    return {
        "ep_tp_losses": [round(v, 4) for v in losses],
        "ep_tp_finite": bool(np.isfinite(losses).all()),
        "ep_tp_learns": losses[-1] < losses[0],
        "global_math_rel_err": parity_rel,
        "global_math_ok": parity_rel < 1e-5,
    }


def _decode_gates() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu.parallel.moe as pmoe
    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=128, num_experts=4,
        moe_top_k=2)
    module = CausalLM(cfg)
    rng = jax.random.PRNGKey(0)
    params = module.init({"params": rng, "dropout": rng},
                         {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                         train=False)["params"]
    prng = np.random.RandomState(7)
    prompts = [prng.randint(0, cfg.vocab_size, (n,)) for n in (6, 9, 4)]
    base = {"dtype": "bf16", "kv_block_size": 4, "num_kv_blocks": 64}
    ref = InferenceEngineV2(cfg, params, dict(base)).generate(
        prompts, max_new_tokens=8)
    calls = []
    orig = pmoe.collective_moe_apply
    try:
        pmoe.collective_moe_apply = lambda *a, **k: (calls.append(1),
                                                     orig(*a, **k))[1]
        ep_eng = InferenceEngineV2(cfg, params, dict(base, ep_size=2))
        outs = ep_eng.generate(prompts, max_new_tokens=8)
    finally:
        pmoe.collective_moe_apply = orig
    identical = all((np.asarray(a) == np.asarray(b)).all()
                    for a, b in zip(outs, ref))
    sharded = "ep" in str(
        ep_eng.params["layers"]["moe"]["experts"]["w_up"].sharding.spec)
    return {
        "v2_ep_collective_traced": bool(calls),
        "v2_ep_weights_sharded": sharded,
        "v2_ep_decode_token_identical": bool(identical),
    }


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--output", default=None,
                    help="also write the gates as a JSON artifact")
    args = ap.parse_args(argv)

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    gates = {**_train_gates(), **_decode_gates()}
    ok = all(gates[k] for k in (
        "ep_tp_finite", "ep_tp_learns", "global_math_ok",
        "v2_ep_collective_traced", "v2_ep_weights_sharded",
        "v2_ep_decode_token_identical"))
    doc = {"moe_smoke": gates, "ok": ok}
    print(json.dumps(doc), flush=True)
    if args.output:
        with open(args.output, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
