#!/usr/bin/env python3
"""Does the system still start on the chip? One process, one model, both paths.

``python chip_smoke.py`` needs ONE TPU chip and drives, through the entry
points a user calls and at the full width and depth of the flagship
GPT-2-125M (seq 1024, bf16):

  1. device : ``jax.devices()``; anything but a TPU is exit != 0 at once
  2. train  : ``deepspeed_tpu.initialize`` + 5 ``train_batch`` steps on one
              seeded batch (micro 4 x gas 8, AdamW, ZeRO-1, clipping); losses
              finite and falling, flash-attention kernel in the compiled step
  3. serve  : ``InferenceEngineV2.generate`` (8 prompts x 200 tokens, 32 new,
              greedy) with a bf16 and an int8 KV pool; paged-attention kernel
              in the decode program, prefill logits against the v1 engine
              built with ``attn_impl="xla"`` on the same weights

``python chip_smoke.py --chips 4`` needs four chips and runs ONLY the
multi-chip phase: the same model and global batch on one device and then
under ZeRO-3 over ``fsdp=4`` (losses agree, state spread over four devices),
and one ``dist.all_reduce`` against NumPy.

Weights and data come from ``--seed``. The last line of stdout is one JSON
object, ``{"ok": ..., "device": {...}}``; a phase that raises or fails its
check leaves ``"ok": false`` there and a non-zero exit code. Every figure
printed is a SMOKE figure from the named device — not a benchmark result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

# GPT-2-125M — the repo's flagship dims
GPT2_DIMS = dict(
    vocab_size=50304, hidden_size=768, intermediate_size=3072,
    num_layers=12, num_heads=12, max_seq_len=1024,
    norm="layernorm", activation="gelu", position="learned",
    tie_embeddings=True,
)
SEQ = 1024
MICRO, GAS = 4, 8
TRAIN_STEPS = 5
N_PROMPTS, PROMPT_LEN, NEW_TOKENS = 8, 200, 32
# relative L2 error of the v2 prefill logits against the XLA-attention v1
# engine: bf16 matmuls in a different order (flash tiles vs one softmax), and
# for the int8 pool the KV quantization error on top
LOGIT_TOL = {None: 0.03, "int8": 0.05}
MULTI_STEPS = 3
COLLECTIVE_ELEMS = 32 * 1024 * 1024  # fp32 elements per device (~GPT-2 grads)

_cache_events = {"hits": 0, "misses": 0}


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def device_phase(info: dict, want_chips: int) -> None:
    import jax
    import jaxlib

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "absent"
    say("device", **info, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_version)
    check(info["platform"] == "tpu", f"no TPU: jax reports platform {info['platform']!r}")
    check(info["count"] == want_chips,
          f"this phase needs {want_chips} chip(s), jax reports {info['count']}")


def _count_cache_events() -> None:
    import jax.monitoring

    def on_event(name, **_):
        if name.endswith("/cache_hits"):
            _cache_events["hits"] += 1
        elif name.endswith("/cache_misses"):
            _cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)


def _kernels_of(label_prefix: str, since: int = 0) -> list:
    """Custom-kernel call targets the program registry recorded for every
    program compiled after its first ``since`` records whose label starts
    with ``label_prefix``."""
    from deepspeed_tpu.telemetry.programs import get_program_registry

    recs = [r for r in get_program_registry().records()[since:]
            if r.label.startswith(label_prefix)]
    check(bool(recs), f"program registry holds no record of {label_prefix!r}")
    return [k["target"] for r in recs for k in r.custom_kernels]


def _train_config(zero_stage: int = 1, mesh: dict = None) -> dict:
    cfg = {
        "train_micro_batch_size_per_gpu": MICRO,
        "gradient_accumulation_steps": GAS,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": zero_stage},
        "hbm_guard": {"enabled": True},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
    }
    if mesh:
        cfg["mesh"] = mesh
    return cfg


def _model_config():
    import jax.numpy as jnp

    from deepspeed_tpu.models import TransformerConfig

    return TransformerConfig(**GPT2_DIMS, dtype=jnp.bfloat16)


def _run_steps(engine, batch, steps: int):
    """``steps`` train_batch calls, each timed to block_until_ready."""
    import jax

    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(engine.train_batch(batch)["loss"])
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, times


def train_phase(info: dict, seed: int) -> None:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm_spec

    cfg = _model_config()  # attn_impl stays "auto"
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=SEQ), config=_train_config(), seed=seed)
    rng = np.random.default_rng(seed)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, SEQ), dtype=np.int32)}
    losses, times = _run_steps(engine, batch, TRAIN_STEPS)
    steady = float(np.median(times[2:]))
    tokens = engine.train_batch_size * SEQ
    kernels = _kernels_of("train_step")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    say("train", device=info["kind"], losses=[round(l, 4) for l in losses],
        first_step_s=round(times[0], 2), compile_s=round(times[0] - steady, 2),
        steady_step_s=round(steady, 4), tokens_per_s=round(tokens / steady),
        peak_bytes_in_use=peak, flash_kernels=len(kernels))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(any("tpu_custom_call" in k for k in kernels),
          "attn_impl='auto' gave way to XLA attention: no tpu_custom_call in the train step")


def _serving_weights(seed: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import CausalLM

    cfg = _model_config()
    params = CausalLM(cfg).init(
        {"params": jax.random.PRNGKey(seed)},
        {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    return cfg, params


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def serve_phase(info: dict, seed: int) -> None:
    import deepspeed_tpu
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.telemetry.programs import get_program_registry

    cfg, params = _serving_weights(seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (PROMPT_LEN,), dtype=np.int32)
               for _ in range(N_PROMPTS)]

    # the plain reference: the v1 engine with XLA attention — dense KV cache,
    # no paged pool, no Pallas kernel — on the same weights and prompts
    ref = deepspeed_tpu.init_inference(
        dataclasses.replace(cfg, attn_impl="xla"), params=params,
        config={"dtype": "bfloat16"})
    ref_logits = np.asarray(ref.forward(np.stack(prompts))[:, -1], np.float32)
    ref_tokens = ref.generate(np.stack(prompts), max_new_tokens=NEW_TOKENS,
                              do_sample=False)[:, PROMPT_LEN:]
    check(np.isfinite(ref_logits).all(), "reference logits are not finite")

    for kv in (None, "int8"):
        conf = {"dtype": "bf16", "hbm_check": "refuse"}
        if kv:
            conf["kv_cache_dtype"] = kv
        seen = len(get_program_registry().records())
        eng = InferenceEngineV2(cfg, params, conf)
        uids = list(range(N_PROMPTS))
        logits = eng.put(uids, prompts)  # prefill; the put API returns logits
        for uid in uids:
            eng.flush(uid)
        err = _rel_err(logits, ref_logits)

        eng.generate(prompts[:1], max_new_tokens=1)  # warm every bucket used below
        eng.generate(prompts, max_new_tokens=NEW_TOKENS)
        t0 = time.perf_counter()
        eng.generate(prompts[:1], max_new_tokens=1)
        ttft = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.generate(prompts, max_new_tokens=1)
        t_prefill = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = eng.generate(prompts, max_new_tokens=NEW_TOKENS)
        t_all = time.perf_counter() - t0
        tokens = np.stack([np.asarray(o)[-NEW_TOKENS:] for o in out])
        agree = int((tokens == ref_tokens).sum())
        decode_kernels = _kernels_of("v2:decode", since=seen)
        say("serve", device=info["kind"], kv_pool=kv or "bf16",
            prefill_logits_rel_err=round(err, 5), tol=LOGIT_TOL[kv],
            greedy_tokens_agree=f"{agree}/{tokens.size}",
            smoke_ttft_ms=round(ttft * 1e3, 2),
            smoke_decode_tokens_per_s=round(
                N_PROMPTS * (NEW_TOKENS - 1) / max(t_all - t_prefill, 1e-9), 1),
            paged_kernels=len(decode_kernels))
        check(tokens.shape == (N_PROMPTS, NEW_TOKENS), f"generate returned {tokens.shape}")
        check(np.isfinite(np.asarray(logits, np.float32)).all(), "prefill logits not finite")
        check(err <= LOGIT_TOL[kv],
              f"prefill logits off the XLA reference: rel err {err:.4f} > {LOGIT_TOL[kv]}")
        check(any("tpu_custom_call" in k for k in decode_kernels),
              f"no paged-attention kernel in the decode program (kv pool {kv or 'bf16'})")
        del eng


def _spread_over(tree, devices, what: str) -> None:
    """Every device holds about 1/len(devices) of ``tree``'s bytes."""
    import jax

    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            held[s.device.id] += s.data.nbytes
    share = {d: round(b / total, 3) for d, b in held.items()}
    say("multichip", state=what, total_bytes=total, share_per_device=share)
    n = len(devices)
    check(all(0.8 / n <= s <= 1.25 / n for s in share.values()),
          f"{what} is not spread over {n} devices: {share}")


def multichip_phase(info: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import deepspeed_tpu
    from deepspeed_tpu.comm import comm as dist
    from deepspeed_tpu.models import causal_lm_spec
    from deepspeed_tpu.topology.mesh import build_mesh
    from deepspeed_tpu.utils.compat import shard_map

    n = len(jax.devices())
    cfg = _model_config()
    rng = np.random.default_rng(seed)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (MICRO * GAS, SEQ), dtype=np.int32)}

    # what it is compared with: the same model, seed and GLOBAL batch on ONE
    # device (gas absorbs the missing data-parallel width)
    one = build_mesh(devices=jax.devices()[:1], axis_sizes={"dp": 1})
    ref_cfg = _train_config(zero_stage=0)
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=SEQ), config=ref_cfg, mesh=one, seed=seed)
    check(engine.train_batch_size == MICRO * GAS, "one-device global batch drifted")
    ref_losses, ref_times = _run_steps(engine, batch, MULTI_STEPS)
    say("multichip", device=info["kind"], run="1 device, stage 0", losses=ref_losses,
        steady_step_s=round(ref_times[-1], 4))
    del engine

    sharded_cfg = _train_config(zero_stage=3, mesh={"fsdp": n})
    sharded_cfg["gradient_accumulation_steps"] = GAS // n  # same global batch
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=SEQ), config=sharded_cfg, seed=seed)
    check(engine.train_batch_size == MICRO * GAS, "fsdp global batch drifted")
    losses, times = _run_steps(engine, batch, MULTI_STEPS)
    say("multichip", device=info["kind"], run=f"{n} devices, stage 3, fsdp={n}",
        losses=losses, steady_step_s=round(times[-1], 4))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-2,
                               err_msg="fsdp losses left the one-device losses")
    _spread_over(engine.state.params, jax.devices(), "params")
    _spread_over(engine.state.opt_state, jax.devices(), "optimizer state")
    mesh = engine.mesh
    del engine

    # one all-reduce through the comm facade at a gradient-sized payload,
    # against NumPy's sum of the rows
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(seed), (n, COLLECTIVE_ELEMS), jnp.float32),
        NamedSharding(mesh, P("fsdp")))
    fn = jax.jit(shard_map(lambda row: dist.all_reduce(row, "fsdp"), mesh=mesh,
                           in_specs=P("fsdp"), out_specs=P("fsdp"), check_vma=False))
    want = np.tile(np.asarray(x).sum(axis=0, keepdims=True), (n, 1))
    got = jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(x))
    dt = time.perf_counter() - t0
    say("multichip", device=info["kind"], all_reduce="dist.all_reduce",
        bytes_per_device=COLLECTIVE_ELEMS * 4, smoke_seconds=round(dt, 5),
        max_abs_diff_vs_numpy=float(np.abs(np.asarray(got) - want).max()))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4,
                               err_msg="dist.all_reduce != the NumPy sum of the rows")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the multi-chip phase (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    ok, info = False, None
    try:
        info = device_info()
        device_phase(info, args.chips)

        from deepspeed_tpu.telemetry.programs import get_program_registry
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache

        say("cache", dir=enable_compile_cache())
        _count_cache_events()
        # the registry records every program the engines compile, with the
        # custom kernels found in its HLO — the kernel-presence checks read it
        get_program_registry().configure(enabled=True)
        if args.chips == 1:
            train_phase(info, args.seed)
            serve_phase(info, args.seed)
        else:
            multichip_phase(info, args.seed)
        say("cache", persistent_hits=_cache_events["hits"],
            persistent_misses=_cache_events["misses"])
        ok = True
    finally:
        # no except: a failed phase keeps its traceback and its exit code
        print(json.dumps({"ok": ok, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
