#!/usr/bin/env python
"""Profile the bench train step on the real chip — all four stages in one
parameterized tool (formerly profile_bench.py + profile_bench{2,3,4}.py).

  --stage 1   step/engine/dispatch breakdown + XLA cost analysis: pure jitted
              step latency, chained x10 amortized dispatch, engine.train_batch,
              batch placement, no-op dispatch floor, flops + MFU
  --stage 2   block_until_ready honesty + true device times: chained
              dispatch/block/fetch split, fwd-only, fwd+bwd, 8k matmul rate
  --stage 3   step decomposition: in-program matmul rate (50x fori_loop),
              fwd and fwd+bwd at micro 8/32, optimizer-only update, lm-head
              matmul
  --stage 4   per-shape matmul sweep, flash-vs-xla attention fwd/bwd, and a
              jax.profiler trace attempt
  --stage attn
              tuned flash kernel vs xla reference: fwd + bwd latency and
              max-abs-diff parity check (formerly profile_attn.py)
  --stage attn-sweep
              flash kernel block-size sweep chained inside ONE jitted program
              via lax.scan so dispatch amortizes away; --grad times fwd+bwd
              (formerly profile_attn_sweep.py)
  --stage all run every stage in order

Usage: python tools/profile_bench.py [--stage 1|2|3|4|attn|attn-sweep|all]
                                     [--batch B] [--seq S] [--grad]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


# --------------------------------------------------------------- shared bits
def timeit(fn, n=10, warmup=3, block=lambda r: jax.block_until_ready(r)):
    for _ in range(warmup):
        r = fn()
    block(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    block(r)
    return (time.perf_counter() - t0) / n


def fetch_time(fn, out_leaf=lambda r: r, n=5, warmup=2):
    """Time dispatch->device->host-fetch of one output leaf (the honest
    per-call latency on an async-dispatch runtime)."""
    for _ in range(warmup):
        r = fn()
    _ = np.asarray(out_leaf(r))
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    _ = np.asarray(out_leaf(r))
    return (time.perf_counter() - t0) / n


def _gpt2_cfg():
    from deepspeed_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=50304, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, max_seq_len=1024,
        norm="layernorm", activation="gelu", position="learned",
        tie_embeddings=True, dtype=jnp.bfloat16,
    )


def _engine(cfg, micro, seq, stage3=False):
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm_spec

    config = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}
                      if stage3 else {"lr": 1e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "steps_per_print": 10_000,
    }
    if not stage3:
        config["gradient_clipping"] = 1.0
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq), config=config)
    return engine


# ------------------------------------------------------------------ stage 1
def stage1():
    """Where does the time go: step vs engine vs dispatch floor + MFU."""
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    print(f"backend={backend}")

    if on_tpu:
        cfg = _gpt2_cfg()
        micro, seq = 8, 1024
        peak_flops = 197e12
    else:
        from deepspeed_tpu.models import TransformerConfig

        cfg = TransformerConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                                num_layers=2, num_heads=4, max_seq_len=256)
        micro, seq = 2, 128
        peak_flops = 1e12

    engine = _engine(cfg, micro, seq)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}

    # dispatch floor: trivial jit call round-trip
    f_nop = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    t_nop_async = timeit(lambda: f_nop(x), n=50, warmup=5, block=lambda r: None)
    t_nop_sync = timeit(lambda: jax.block_until_ready(f_nop(x)), n=50, warmup=5)
    print(f"dispatch nop: async={t_nop_async*1e3:.2f} ms, sync-roundtrip={t_nop_sync*1e3:.2f} ms")

    # pure jitted step
    placed = engine._shard_global_batch(batch)
    state = engine.state
    step_fn = engine._train_step

    def pure():
        nonlocal state
        state, m = step_fn(state, placed)
        return m["loss"]

    t_pure = timeit(pure, n=10, warmup=3)
    print(f"pure jitted step: {t_pure*1e3:.1f} ms")
    engine.state = state

    # pure step, async chain of 10 then block (amortized dispatch)
    def chain10():
        nonlocal state
        for _ in range(10):
            state, m = step_fn(state, placed)
        return m["loss"]

    t_chain = timeit(chain10, n=3, warmup=1) / 10
    engine.state = state
    print(f"chained x10 step (amortized dispatch): {t_chain*1e3:.1f} ms")

    # engine.train_batch (adds _shard_global_batch + metrics np.asarray sync)
    t_engine = timeit(lambda: engine.train_batch(batch)["loss"], n=10, warmup=3,
                      block=lambda r: None)
    print(f"engine.train_batch: {t_engine*1e3:.1f} ms")

    t_place = timeit(lambda: engine._shard_global_batch(batch), n=10, warmup=3,
                     block=lambda r: jax.block_until_ready(r))
    print(f"batch placement: {t_place*1e3:.1f} ms")

    # XLA cost analysis vs model flops
    lowered = step_fn.lower(engine.state, placed)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    xla_flops = ca.get("flops", float("nan"))
    tokens = engine.train_batch_size * seq
    model_flops = cfg.flops_per_token(seq) * tokens
    print(f"xla flops/step: {xla_flops:.3e}; model flops/step (6ND-style): {model_flops:.3e}")

    best = min(t_pure, t_chain)
    print(json.dumps({
        "t_pure_ms": t_pure * 1e3, "t_chain_ms": t_chain * 1e3,
        "t_engine_ms": t_engine * 1e3, "t_place_ms": t_place * 1e3,
        "nop_async_ms": t_nop_async * 1e3, "nop_sync_ms": t_nop_sync * 1e3,
        "mfu_pure": model_flops / best / peak_flops,
        "mfu_engine": model_flops / t_engine / peak_flops,
        "xla_flops": xla_flops, "model_flops": model_flops,
    }))


# ------------------------------------------------------------------ stage 2
def stage2():
    """Is block_until_ready honest, and what is the true device time?"""
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.topology.mesh import set_mesh

    cfg = _gpt2_cfg()
    micro, seq = 8, 1024
    engine = _engine(cfg, micro, seq)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    placed = engine._shard_global_batch(batch)
    state = engine.state
    step_fn = engine._train_step

    for _ in range(2):
        state, m = step_fn(state, placed)
    _ = np.asarray(m["loss"])

    # A: chain 5 steps; dispatch vs block vs fetch
    t0 = time.perf_counter()
    for _ in range(5):
        state, m = step_fn(state, placed)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(m["loss"])
    t_block = time.perf_counter() - t0
    _ = np.asarray(m["loss"])
    t_fetch = time.perf_counter() - t0
    print(f"5 steps: dispatch={t_dispatch*1e3:.1f}ms block={t_block*1e3:.1f}ms fetch={t_fetch*1e3:.1f}ms")
    print(f"=> true per-step: {t_fetch*1e3/5:.1f} ms")

    # B: forward-only loss
    module = CausalLM(cfg)
    set_mesh(engine.mesh)
    params16 = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        p))(state.params)
    micro_b = {"input_ids": jnp.asarray(batch["input_ids"])}

    @jax.jit
    def fwd(p, b):
        loss, _ = module.apply({"params": p}, b, train=False)
        return loss

    t_fwd = fetch_time(lambda: fwd(params16, micro_b))
    print(f"fwd-only: {t_fwd*1e3:.1f} ms")

    # C: fwd+bwd grads only (no optimizer)
    @jax.jit
    def fwdbwd(p, b):
        def loss_fn(pp):
            loss, _ = module.apply({"params": pp}, b, train=False)
            return loss
        return jax.value_and_grad(loss_fn)(p)[0]

    t_fb = fetch_time(lambda: fwdbwd(params16, micro_b))
    print(f"fwd+bwd: {t_fb*1e3:.1f} ms")

    # D: big matmul sanity
    a = jnp.zeros((8192, 8192), jnp.bfloat16)
    b = jnp.zeros((8192, 8192), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    t_mm = fetch_time(lambda: mm(a, b), lambda r: r[0, 0], n=10)
    fl = 2 * 8192**3
    print(f"8k matmul: {t_mm*1e3:.2f} ms => {fl/t_mm/1e12:.1f} TFLOP/s")


# ------------------------------------------------------------------ stage 3
def stage3():
    """Decompose the step: honest fwd+bwd, optimizer-only, in-program rate."""
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.topology.mesh import set_mesh

    cfg = _gpt2_cfg()
    seq = 1024
    module = CausalLM(cfg)
    engine = _engine(cfg, 8, seq, stage3=True)
    set_mesh(engine.mesh)
    state = engine.state
    params16 = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        p))(state.params)
    rng = np.random.default_rng(0)

    # true device matmul rate: 50 matmuls inside one program
    a = jnp.zeros((8192, 8192), jnp.bfloat16)

    @jax.jit
    def mm50(a):
        def body(i, acc):
            return acc + a @ a * (1.0 / (i + 1))
        return jax.lax.fori_loop(0, 50, body, jnp.zeros_like(a))[0, 0]

    t = fetch_time(lambda: mm50(a), n=2, warmup=1)
    print(f"50x 8k matmul in-program: {t*1e3:.1f} ms => {50*2*8192**3/t/1e12:.1f} TFLOP/s")

    for micro in (8, 32):
        b = {"input_ids": jnp.asarray(rng.integers(0, cfg.vocab_size, (micro, seq), dtype=np.int32))}

        @jax.jit
        def fwd(p, b):
            loss, _ = module.apply({"params": p}, b, train=False)
            return loss

        @jax.jit
        def fwdbwd(p, b):
            def loss_fn(pp):
                loss, _ = module.apply({"params": pp}, b, train=False)
                return loss
            return jax.value_and_grad(loss_fn)(p)

        t_f = fetch_time(lambda: fwd(params16, b))
        t_fb = fetch_time(
            lambda: fwdbwd(params16, b),
            lambda r: r[1]["lm_head"]["embedding"] if "lm_head" in r[1]
            else jax.tree_util.tree_leaves(r[1])[0])
        fwd_fl = 2 * 124e6 * micro * seq  # 2*N*T matmul flops approx (fwd)
        print(f"micro={micro}: fwd={t_f*1e3:.1f}ms ({fwd_fl/t_f/1e12:.1f} TF/s) "
              f"fwd+bwd={t_fb*1e3:.1f}ms ({3*fwd_fl/t_fb/1e12:.1f} TF/s)")

    # optimizer-only update (adamw on fp32 master)
    tx = engine.tx
    grads = jax.tree_util.tree_map(lambda x: jnp.ones(x.shape, jnp.float32), state.params)

    @jax.jit
    def opt_only(params, opt_state, grads):
        import optax

        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    t_o = fetch_time(lambda: opt_only(state.params, state.opt_state, grads),
                     lambda r: jax.tree_util.tree_leaves(r[0])[0])
    print(f"optimizer-only: {t_o*1e3:.1f} ms")

    # lm-head matmul microbench (vocab is the big matmul)
    emb = jnp.zeros((50304, 768), jnp.bfloat16)
    h = jnp.zeros((8 * 1024, 768), jnp.bfloat16)
    head = jax.jit(lambda h, emb: (h @ emb.T)[0, 0])
    t_h = fetch_time(lambda: head(h, emb))
    print(f"lm head matmul (8k x 768 x 50k): {t_h*1e3:.2f} ms => {2*8192*768*50304/t_h/1e12:.1f} TF/s")


# ------------------------------------------------------------------ stage 4
def stage4():
    """Per-shape matmul sweep, flash-vs-xla attention, profiler trace."""
    def mm_rate(M, K, N, dtype=jnp.bfloat16, n=10):
        a = jnp.zeros((M, K), dtype)
        b = jnp.zeros((K, N), dtype)
        f = jax.jit(lambda a, b: (a @ b).sum())
        t = fetch_time(lambda: f(a, b), n=n, warmup=3)
        return t, 2 * M * K * N / t / 1e12

    print("matmul shape sweep (bf16):")
    for (M, K, N) in [(8192, 768, 768), (8192, 768, 3072), (8192, 3072, 768),
                      (8192, 768, 50304), (32768, 768, 3072), (8192, 8192, 8192)]:
        t, r = mm_rate(M, K, N)
        print(f"  [{M},{K}]x[{K},{N}]: {t*1e3:.2f} ms {r:.1f} TF/s")

    # attention: flash vs xla, fwd + bwd
    from deepspeed_tpu.ops.registry import dispatch
    B, S, H, D = 8, 1024, 12, 64
    q = jnp.zeros((B, S, H, D), jnp.bfloat16)
    k = jnp.zeros((B, S, H, D), jnp.bfloat16)
    v = jnp.zeros((B, S, H, D), jnp.bfloat16)
    att_fl = 4 * B * H * S * S * D
    for impl in ("pallas", "xla"):
        try:
            fn = jax.jit(lambda q, k, v, f=dispatch("causal_attention", impl): f(q, k, v, mask=None).sum())
            t = fetch_time(lambda: fn(q, k, v), n=10, warmup=3)
            print(f"attention {impl}: {t*1e3:.2f} ms ({att_fl/t/1e12:.1f} TF/s)")
        except Exception as e:
            print(f"attention {impl}: FAILED {type(e).__name__} {e}")
    for impl in ("pallas", "xla"):
        try:
            f = dispatch("causal_attention", impl)
            fn = jax.jit(lambda q, k, v: jax.grad(
                lambda qq: f(qq, k, v, mask=None).astype(jnp.float32).sum())(q).sum())
            t = fetch_time(lambda: fn(q, k, v), n=10, warmup=3)
            print(f"attention-bwd {impl}: {t*1e3:.2f} ms")
        except Exception as e:
            print(f"attention-bwd {impl}: FAILED {type(e).__name__} {e}")

    # profiler trace attempt
    try:
        a = jnp.zeros((4096, 4096), jnp.bfloat16)
        f = jax.jit(lambda a: a @ a)
        with jax.profiler.trace("/tmp/jaxtrace"):
            r = f(a)
            np.asarray(r[0, 0])
        import glob
        files = glob.glob("/tmp/jaxtrace/**/*", recursive=True)
        print(f"profiler trace files: {len(files)}")
        for p in files[:8]:
            print("  ", p, os.path.getsize(p) if os.path.isfile(p) else "dir")
    except Exception as e:
        print(f"profiler trace FAILED: {type(e).__name__} {e}")


# --------------------------------------------------------------- stage attn
def stage_attn(batch=None, seq=None, grad=False):
    """Tuned flash kernel vs the xla reference: fwd/bwd latency + parity."""
    del grad  # attn always times both fwd and bwd
    from deepspeed_tpu.ops.registry import dispatch

    B, S, H, D = batch or 8, seq or 1024, 12, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D), jnp.bfloat16)
    att_fl = 4 * B * H * S * S * D  # fwd flops (causal halves useful work)

    outs = {}
    for impl in ("pallas", "xla"):
        f = dispatch("causal_attention", impl)
        fn = jax.jit(lambda q, k, v, f=f: f(q, k, v))
        r = fn(q, k, v)
        outs[impl] = np.asarray(r, np.float32)
        t = fetch_time(lambda: fn(q, k, v), lambda r: r[0, 0, 0, 0], n=10, warmup=3)
        print(f"fwd {impl}: {t*1e3:.2f} ms ({att_fl/t/1e12:.1f} TF/s)")

    err = np.abs(outs["pallas"] - outs["xla"]).max()
    print(f"fwd max abs diff pallas vs xla: {err:.4f}")

    grads = {}
    for impl in ("pallas", "xla"):
        f = dispatch("causal_attention", impl)

        @jax.jit
        def gfn(q, k, v, f=f):
            def loss(q, k, v):
                return f(q, k, v).astype(jnp.float32).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        r = gfn(q, k, v)
        t = fetch_time(lambda: gfn(q, k, v), lambda r: r[0][0, 0, 0, 0], n=10, warmup=3)
        print(f"bwd {impl}: {t*1e3:.2f} ms")
        grads[impl] = [np.asarray(x, np.float32) for x in r]
    for nm, a, b in zip("qkv", grads["pallas"], grads["xla"]):
        print(f"d{nm} max abs diff: {np.abs(a-b).max():.4f} (scale {np.abs(b).max():.2f})")


# --------------------------------------------------------- stage attn-sweep
def stage_attn_sweep(batch=None, seq=None, grad=False):
    """Sweep flash-attention block sizes inside ONE jitted program.

    A lax.scan chains the kernel invocations with a data dependency (the
    output feeds the next query), so per-program dispatch amortizes away
    and the measured time is the kernel itself.
    """
    from deepspeed_tpu.ops.pallas.flash_attention import flash_causal_attention

    def bench(fn, *args, iters=20):
        # grad mode differentiates w.r.t. ALL of q/k/v and feeds every
        # gradient back into the carry — otherwise the dkv kernel is dead
        # code under jit and the sweep never times it.
        inner = jax.grad(lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum(),
                         argnums=(0, 1, 2))

        @jax.jit
        def chained(q, k, v):
            def body(carry, _):
                q, k, v = carry
                decay = jnp.asarray(0.999, q.dtype)
                eps = jnp.asarray(1e-3, q.dtype)
                if grad:
                    dq, dk, dv = inner(q, k, v)
                    new = (q * decay + dq.astype(q.dtype) * eps,
                           k * decay + dk.astype(k.dtype) * eps,
                           v * decay + dv.astype(v.dtype) * eps)
                else:
                    new = (fn(q, k, v) * eps + q * decay, k, v)
                return new, ()

            (q, k, v), _ = jax.lax.scan(body, (q, k, v), None, length=iters)
            return q

        r = chained(*args)
        _ = np.asarray(r[0, 0, 0, 0])  # warm compile + sync
        t0 = time.perf_counter()
        r = chained(*args)
        _ = np.asarray(r[0, 0, 0, 0])
        return (time.perf_counter() - t0) / iters

    B, S, H, D = batch or 4, seq or 1024, 12, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D), jnp.bfloat16)
    fl = 4 * B * H * S * S * D  # dense fwd flops; causal useful ~ (1+nblk)/(2 nblk)
    if grad:
        # fwd (2 matmuls) + dq kernel (3: s, dp, ds@k) + dkv kernel (4: s, dv,
        # dp, dk) = 18 B·H·S²·D dense matmul flops per step
        fl = fl * 18 // 4

    # k_splits > 1 = sub-chunked online softmax (next QK^T hoisted over the
    # previous chunk's VPU passes) — the round-5 attack on the per-cell
    # softmax serialization named in PERF.md.
    for bq, bk, ks in ((256, 256, 1), (256, 512, 1), (512, 256, 1),
                       (512, 512, 1), (512, 512, 2), (512, 1024, 1),
                       (512, 1024, 2), (512, 1024, 4),
                       (1024, 512, 1), (1024, 512, 2),
                       (1024, 1024, 1), (1024, 1024, 2), (1024, 1024, 4),
                       (1024, 2048, 4), (2048, 2048, 4)):
        if bq > S or bk > S:
            continue
        fn = lambda q, k, v: flash_causal_attention(q, k, v, block_q=bq,
                                                    block_k=bk, k_splits=ks)
        try:
            t = bench(fn, q, k, v)
        except Exception as e:  # noqa: BLE001 - sweep keeps going past bad configs
            print(f"bq={bq} bk={bk} ks={ks}: FAIL {type(e).__name__}")
            continue
        print(f"bq={bq:5d} bk={bk:5d} ks={ks}: {t*1e3:7.3f} ms  "
              f"dense-rate {fl/t/1e12:6.1f} TF/s")


STAGES = {"1": stage1, "2": stage2, "3": stage3, "4": stage4}
ATTN_STAGES = {"attn": stage_attn, "attn-sweep": stage_attn_sweep}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--stage", choices=[*STAGES, *ATTN_STAGES, "all"], default="1")
    ap.add_argument("--batch", type=int, default=None,
                    help="batch dim for the attn stages (attn: 8, attn-sweep: 4)")
    ap.add_argument("--seq", type=int, default=None,
                    help="seq dim for the attn stages (default 1024)")
    ap.add_argument("--grad", action="store_true",
                    help="attn-sweep: time fwd+bwd instead of fwd-only")
    args = ap.parse_args()
    for name in STAGES if args.stage == "all" else [args.stage]:
        if args.stage == "all":
            print(f"\n===== stage {name} =====")
        if name in ATTN_STAGES:
            ATTN_STAGES[name](batch=args.batch, seq=args.seq, grad=args.grad)
        else:
            STAGES[name]()


if __name__ == "__main__":
    main()
