"""Benchmark: flagship CausalLM training + inference throughput.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extras": {...}}

Headline (value/vs_baseline): tokens/sec/chip for GPT-2-small (125M params,
bf16, seq 1024, gas 8) full train steps (fwd+bwd+AdamW) through the engine on
one TPU chip. vs_baseline = achieved MFU / 0.45, the north-star MFU from
BASELINE.md (the reference's Ulysses/FPDT blogs claim ~54%/55% peak on A100).

"extras" adds the other BASELINE.json tracked configs that fit one chip: a
Llama-style ZeRO-3 + remat + fused-CE config, a Mixtral-style expert-parallel
step, the v2 inference engine's p50 TTFT + decode tokens/sec, and the
host-overhead guards.

A measurement path: no chip -> exit != 0 and no throughput line; a benchmark
that fails fails the run. Every benchmark runs in its own child process (one
process holds the chip at a time; the parent never imports jax). Peaks come
from profiling/flops_profiler.DEVICE_PEAKS, keyed by ``device_kind``. The
rest of this file predates the benchmark contract of this round (cells,
seeds, traces) and is rebuilt by the ``benchmark`` PR (ROADMAP S1); the
start-up proof on the chip is ``chip_smoke.py``.
"""

from __future__ import annotations

import json
import time


def _train_tokens_per_sec(engine, batch, steps, warmup):
    import jax

    for _ in range(warmup):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        m = engine.train_batch(batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    return engine.train_batch_size * batch["input_ids"].shape[1] * steps / dt


# The headline model's dimensions — shared with tools/run_autotune.py so the
# tuner and the bench cannot drift (an AUTOTUNE.json recorded for different
# dims is rejected).
GPT2_HEADLINE_DIMS = dict(
    vocab_size=50304, hidden_size=768, intermediate_size=3072,
    num_layers=12, num_heads=12, max_seq_len=1024,
    norm="layernorm", activation="gelu", position="learned",
    tie_embeddings=True,
)


def _telemetry_enabled() -> bool:
    """Telemetry opt-in for bench runs (DSTPU_TELEMETRY=1). Default OFF so
    the headline timed loop carries zero instrumentation overhead. The
    truthy-spelling parse lives in ONE place: telemetry.env_enabled."""
    from deepspeed_tpu import telemetry

    return telemetry.env_enabled()


def _telemetry_section(engine, batch, steps=5):
    """5-step instrumented run + trace export.

    The phase breakdown comes from the telemetry registry — the SAME numbers
    the engine's spans recorded, not a second ad-hoc timing pass (single
    source of truth). The loop uses the reference-style
    forward/backward/step API so the trace holds real fwd/bwd/step spans
    (train_batch's fused program has no separable phases); a tiny facade
    all_reduce probe guarantees at least one comm collective span with
    payload-bytes metadata even on a single-chip mesh."""
    import os

    import jax
    import numpy as np

    from deepspeed_tpu import telemetry

    tr = telemetry.get_tracer()
    tr.configure(enabled=True)
    # drop spans/counters from the timed headline loop: the section's
    # breakdown must describe exactly this 5-step run (the fused-dispatch
    # 'step' spans recorded by train_batch would otherwise blend with the
    # optimizer-only parity 'step' spans below into a meaningless mix)
    tr.reset()

    # comm probe: one facade collective over all local devices (ds_bench's
    # smallest sibling) — records op/axis/dtype/bytes/world tags at trace time
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    import deepspeed_tpu.comm as dist

    # one resolution of the moved/renamed shard_map API for the whole tree
    from deepspeed_tpu.utils.compat import shard_map
    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("dp",))
    probe = shard_map(lambda v: dist.all_reduce(v, "dp"),
                      mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    np.asarray(jax.jit(probe)(jnp.ones((len(devs), 256), jnp.float32)))
    # algorithmic sibling: one hop-composed quantized all-reduce so the trace
    # also holds per-hop coll:* spans + the algorithm/codec routing tags
    # (collectives/ subsystem; harmless single tiny collective)
    probe2 = shard_map(
        lambda v: dist.all_reduce(v[0], "dp", algorithm="ring2d", codec="int8",
                                  block_size=128)[None],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
    np.asarray(jax.jit(probe2)(jnp.ones((len(devs), 256), jnp.float32)))

    gas = engine.config.gradient_accumulation_steps
    micro = {k: np.asarray(v)[: max(1, np.asarray(v).shape[0] // gas)]
             for k, v in batch.items()}
    for _ in range(steps):
        engine.forward(micro)            # "fwd" span (eval forward)
        for _ in range(gas):
            engine.backward(batch=micro)  # "bwd" span (fwd+bwd grad program)
        engine.step()                     # "step" span (optimizer update)
    engine.flush_monitor()

    out_dir = telemetry.default_output_dir()
    trace_path = telemetry.export_chrome_trace(os.path.join(out_dir, "bench_trace.json"))
    jsonl_path = telemetry.export_jsonl(os.path.join(out_dir, "bench_events.jsonl"))
    comm = {k: v for k, v in tr.registry.counters().items() if k.startswith("comm/")}
    return {
        "phases": tr.phase_summary(),
        "comm": comm,
        "memory": tr.sample_memory(),
        "trace": trace_path,
        "events": jsonl_path,
    }


def _autotune_overrides():
    """Model-level knobs from a committed AUTOTUNE.json (tools/run_autotune.py
    on real hardware). None when the file is absent, CPU-smoke-only, or
    recorded for different model dims — the caller then uses its own
    hand-picked values. Only a COMMITTED file can reach a checkout."""
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "AUTOTUNE.json")
    try:
        with open(path) as f:
            art = json.load(f)
        if (isinstance(art, dict) and art.get("backend") == "tpu"
                and not art.get("plumbing_smoke_only")
                and art.get("model_dims", GPT2_HEADLINE_DIMS) == GPT2_HEADLINE_DIMS):
            ov = dict(art.get("best_model_overrides") or {})
            micro = art.get("best_config", {}).get("train_micro_batch_size_per_gpu")
            return ov, micro
    except (OSError, ValueError, TypeError, AttributeError):
        pass
    return None, None


def bench_train_gpt2(peak_flops):
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    # scan_layers=False: the per-layer scan's activation stacking cost ~25%
    # of wall-clock at this depth when last measured; fused_ce=False: the
    # chunked-vocab CE is a memory lever, not a speed lever — the XLA logits
    # path is faster whenever the fp32 logits fit. A committed AUTOTUNE.json
    # (tuner-chosen on hardware) overrides both.
    overrides, tuned_micro = _autotune_overrides()
    autotuned = overrides is not None
    if overrides is None:
        overrides = {"scan_layers": False, "fused_ce": False}
    cfg = TransformerConfig(
        **GPT2_HEADLINE_DIMS, dtype=jax.numpy.bfloat16, **overrides,
    )
    micro, seq, steps, warmup, gas = (tuned_micro or 4), 1024, 10, 3, 8

    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 1},
            "hbm_guard": {"enabled": True},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
            # opt-in (DSTPU_TELEMETRY=1): span tracing through the engine's
            # config block; disabled (default) the hooks are attribute checks
            **({"telemetry": {"enabled": True}} if _telemetry_enabled() else {}),
            # flight recorder + recompile/step-time watch: a hung or crashed
            # bench run leaves telemetry_out/flight_record.jsonl behind (dump
            # on unhandled exception / SIGTERM). health probes stay OFF so
            # the headline timed loop compiles the identical step program.
            "diagnostics": {"enabled": True, "health": {"enabled": False}},
        },
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    tok_per_sec = _train_tokens_per_sec(engine, batch, steps, warmup)
    mfu = tok_per_sec * cfg.flops_per_token(seq) / peak_flops
    telem = _telemetry_section(engine, batch) if _telemetry_enabled() else None
    # provenance: a tuned micro changes the workload shape — stamp it so
    # trend tooling never attributes the delta to a code change
    stamp = {"overrides": overrides, "micro": micro} if autotuned else None
    return tok_per_sec, mfu, seq, stamp, telem


def bench_train_llama_z3(peak_flops):
    """Largest-fitting Llama-style config: ZeRO-3 placement + remat.

    Single chip, so ZeRO-3 is placement-only (fsdp=1) — this measures the
    dense-model step the Llama-3-8B multi-chip config is built from. Sizing:
    ~550M params keeps master+Adam fp32 states (12 bytes/param) + grads +
    bf16 compute + remat activations + fp32 logits ([4,2048,32000] = 1 GiB;
    the XLA CE path is faster than the chunked fused CE whenever the logits
    fit — PERF.md round 3) inside 16G HBM."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=6144,
        num_layers=14, num_heads=16, num_kv_heads=8, head_dim=96,
        max_seq_len=2048, norm="rmsnorm", activation="silu_glu", position="rope",
        remat=True, dtype=jax.numpy.bfloat16, scan_layers=False, fused_ce=False,
    )
    seq = 2048
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": 4,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 3},
            "hbm_guard": {"enabled": True},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
        },
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    tok_per_sec = _train_tokens_per_sec(engine, batch, steps=5, warmup=2)
    return {
        "tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "mfu": round(tok_per_sec * cfg.flops_per_token(seq) / peak_flops, 4),
        "params_m": round(cfg.num_params() / 1e6),
    }


def bench_train_moe(peak_flops):
    """Mixtral-style expert-parallel step (8 experts, top-2) on one chip."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=8, num_heads=16, num_kv_heads=8, max_seq_len=1024,
        norm="rmsnorm", activation="silu_glu", position="rope",
        num_experts=8, moe_top_k=2, remat=True, dtype=jax.numpy.bfloat16,
        scan_layers=False, fused_ce=False,
    )
    seq = 1024
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "hbm_guard": {"enabled": True},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
        },
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    tok_per_sec = _train_tokens_per_sec(engine, batch, steps=5, warmup=2)
    return {
        "tokens_per_sec_per_chip": round(tok_per_sec, 1),
        # flops_per_token uses ACTIVE params (top-2 of 8 experts) for MoE
        "mfu_active": round(tok_per_sec * cfg.flops_per_token(seq) / peak_flops, 4),
        "total_params_m": round(cfg.num_params() / 1e6),
    }


def _bench_train_dense(peak_flops, *, hidden, inter, layers, heads, kv_heads,
                       seq, micro, zero, steps=4, warmup=2, bf16_accum=False):
    """Shared harness for the >=1B dense configs (round-3 verdict item 2).

    bf16_accum: carry the grad accumulator in bf16 — for the offload configs
    this HALVES the D2H gradient transfer, the dominant offload cost (the
    reference's CPU optimizer likewise receives 16-bit gradients)."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=heads, num_kv_heads=kv_heads,
        max_seq_len=seq, norm="rmsnorm", activation="silu_glu", position="rope",
        remat=True, dtype=jax.numpy.bfloat16, scan_layers=False, fused_ce=True,
    )
    bf16_section = {"enabled": True}
    if bf16_accum:
        bf16_section["accumulate_grads_in_fp32"] = False
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": zero or {"stage": 3},
            "hbm_guard": {"enabled": True},
            "bf16": bf16_section,
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
            # post-mortem artifact for the big/novel configs
            "diagnostics": {"enabled": True, "health": {"enabled": False}},
        },
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    tok_per_sec = _train_tokens_per_sec(engine, batch, steps=steps, warmup=warmup)
    return {
        "tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "mfu": round(tok_per_sec * cfg.flops_per_token(seq) / peak_flops, 4),
        "params_m": round(cfg.num_params() / 1e6),
    }


def bench_train_dense_1b(peak_flops):
    """Largest dense model whose FULL fp32 Adam state fits the 16G chip:
    12 layers (~890M) put ~14.2 GiB of optimizer/weight state on it and ran
    out of memory; 10 layers (~760M) leaves ~3.8 GiB of headroom for remat
    activations + fused-CE chunks."""
    return _bench_train_dense(
        peak_flops, hidden=2048, inter=8192, layers=10, heads=16, kv_heads=8,
        seq=2048, micro=1, zero={"stage": 3})


def bench_train_dense_2b_offload(peak_flops):
    """~2B params: does NOT fit on-chip with Adam states (~31 GiB), DOES fit
    with ZeRO-Offload — bf16 weights+grads (~7.8 GiB) on chip, fp32 master +
    moments on host, optimizer update as a compiled CPU program (the
    DeepSpeedCPUAdam analog; reference swap_tensor/partitioned_optimizer_swapper.py:29).
    First on-chip evidence for the offload path (round-3 verdict weak item 2)."""
    return _bench_train_dense(
        peak_flops, hidden=2560, inter=10240, layers=18, heads=20, kv_heads=10,
        seq=2048, micro=1, steps=3, warmup=1, bf16_accum=True,
        zero={"stage": 3, "offload_optimizer": {"device": "cpu"}})


def bench_train_dense_2b_twinflow(peak_flops):
    """Twin-Flow partial offload (reference ZeRO-Offload++,
    blogs/deepspeed-offloadpp claims 3x/6x over full offload): same ~2B model
    as ``dense_2b_offload_host`` but with ratio=0.75 — the hottest 25% of
    master bytes update on-chip in a fused program and skip the host
    round-trip. HBM math: bf16 w+g ~7.8 GiB + 0.5B on-chip fp32 states
    ~6 GiB + remat activations.

    bf16_accum stays False here: the Twin-Flow stats/partition programs
    require fp32 gradient accumulation (the engine warns and keeps fp32 if
    asked otherwise), so unlike ``dense_2b_offload_host`` the D2H gradient
    transfer is NOT halved — Twin-Flow's win is moving less state, not
    thinner gradients."""
    return _bench_train_dense(
        peak_flops, hidden=2560, inter=10240, layers=18, heads=20, kv_heads=10,
        seq=2048, micro=1, steps=3, warmup=1, bf16_accum=False,
        zero={"stage": 3, "offload_optimizer": {"device": "cpu", "ratio": 0.75}})


def _nvme_swap_dir():
    """A directory on REAL storage for the swap bench.

    tempfile.mkdtemp() lands on /tmp, which is tmpfs on many hosts — swapping
    there measures RAM, not NVMe. Honor an explicit override, else probe
    candidates and take the first that is not memory-backed; report the fs
    type alongside the numbers either way so a RAM-backed run is visible."""
    import os
    import tempfile

    def fstype(path):
        try:
            import subprocess

            out = subprocess.run(["stat", "-f", "-c", "%T", path],
                                 capture_output=True, text=True, timeout=10)
            return out.stdout.strip() or "unknown"
        except Exception:
            return "unknown"

    override = os.environ.get("DSTPU_BENCH_NVME_DIR")
    if override:
        os.makedirs(override, exist_ok=True)
        return tempfile.mkdtemp(prefix="dstpu_bench_nvme_", dir=override), fstype(override)
    for cand in (tempfile.gettempdir(), os.path.dirname(os.path.abspath(__file__))):
        t = fstype(cand)
        if t not in ("tmpfs", "ramfs"):
            return tempfile.mkdtemp(prefix="dstpu_bench_nvme_", dir=cand), t
    d = tempfile.mkdtemp(prefix="dstpu_bench_nvme_")
    return d, fstype(d)


def bench_train_nvme_offload(peak_flops):
    """ZeRO-Infinity step: optimizer moments swapped to NVMe between steps
    through the AIO pool, plus the raw disk bandwidth the swapper rides on
    (comparable against the reference's 10/5 GB/s DeepNVMe claim).

    Model dims are deliberately IDENTICAL to ``llama_550m_zero3_remat`` so the
    extras pair reads as on-chip-optimizer vs NVMe-swapped-optimizer overhead
    for the same network."""
    import shutil

    folder, fs = _nvme_swap_dir()
    try:
        out = _bench_train_dense(
            peak_flops, hidden=1536, inter=6144, layers=14, heads=16, kv_heads=8,
            seq=2048, micro=1, steps=3, warmup=1,
            zero={"stage": 3,
                  "offload_optimizer": {"device": "nvme", "nvme_path": folder}},
            bf16_accum=True)
        from deepspeed_tpu.nvme.perf import run_io_benchmark

        io = run_io_benchmark(folder, size_mb=256, num_threads=4)
        out["disk_write_gbps"] = round(io["write_gbps"], 2)
        out["disk_read_gbps"] = round(io["read_gbps"], 2)
        out["swap_dir_fstype"] = fs
        return out
    finally:
        shutil.rmtree(folder, ignore_errors=True)


def bench_inference():
    """v1 engine generate: p50 TTFT (prefill) + steady decode tok/s."""
    import numpy as np

    import deepspeed_tpu

    cfg, params = _gpt2_inference_model()
    engine = deepspeed_tpu.init_inference(
        cfg, params=params,
        config={"dtype": "bfloat16", "seq_bucket": 256, "max_out_tokens": 256},
    )
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, 200), dtype=np.int32)

    # warm BOTH compiled programs (the generate cache keys on max_new_tokens)
    n_new = 128
    engine.generate(prompt, max_new_tokens=1, do_sample=False)
    engine.generate(prompt, max_new_tokens=n_new, do_sample=False)

    # TTFT proxy: 1-new-token generate (prefill + 1 decode), p50 of 7
    ttfts = []
    for _ in range(7):
        t0 = time.perf_counter()
        engine.generate(prompt, max_new_tokens=1, do_sample=False)
        ttfts.append(time.perf_counter() - t0)
    p50_ttft = sorted(ttfts)[len(ttfts) // 2]

    # decode throughput: long generation minus the TTFT part
    t0 = time.perf_counter()
    engine.generate(prompt, max_new_tokens=n_new, do_sample=False)
    dt = time.perf_counter() - t0
    decode_tok_s = (n_new - 1) / max(dt - p50_ttft, 1e-6)
    return {"p50_ttft_ms": round(p50_ttft * 1e3, 2),
            "decode_tokens_per_sec": round(decode_tok_s, 1)}


def _gpt2_inference_model():
    import jax

    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=50304, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, max_seq_len=2048,
        norm="layernorm", activation="gelu", position="learned",
        tie_embeddings=True, dtype=jax.numpy.bfloat16,
    )
    module = CausalLM(cfg)
    example = {"input_ids": jax.numpy.zeros((1, 8), jax.numpy.int32)}
    params = module.init({"params": jax.random.PRNGKey(0)}, example,
                         train=False)["params"]
    return cfg, params


def bench_inference_llama():
    """Llama-family TTFT/decode evidence (BASELINE tracks the reference's
    llama serving numbers; same 550M geometry as the training extra so the
    pair reads together)."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=6144,
        num_layers=14, num_heads=16, num_kv_heads=8, head_dim=96,
        max_seq_len=2048, norm="rmsnorm", activation="silu_glu",
        position="rope", dtype=jax.numpy.bfloat16,
    )
    module = CausalLM(cfg)
    example = {"input_ids": jax.numpy.zeros((1, 8), jax.numpy.int32)}
    params = module.init({"params": jax.random.PRNGKey(0)}, example,
                         train=False)["params"]
    engine = deepspeed_tpu.init_inference(
        cfg, params=params,
        config={"dtype": "bfloat16", "seq_bucket": 256, "max_out_tokens": 256},
    )
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, 200), dtype=np.int32)
    n_new = 128
    engine.generate(prompt, max_new_tokens=1, do_sample=False)
    engine.generate(prompt, max_new_tokens=n_new, do_sample=False)
    ttfts = []
    for _ in range(7):
        t0 = time.perf_counter()
        engine.generate(prompt, max_new_tokens=1, do_sample=False)
        ttfts.append(time.perf_counter() - t0)
    p50_ttft = sorted(ttfts)[len(ttfts) // 2]
    t0 = time.perf_counter()
    engine.generate(prompt, max_new_tokens=n_new, do_sample=False)
    dt = time.perf_counter() - t0
    return {"params_m": round(cfg.num_params() / 1e6),
            "p50_ttft_ms": round(p50_ttft * 1e3, 2),
            "decode_tokens_per_sec": round((n_new - 1) / max(dt - p50_ttft, 1e-6), 1)}


def bench_inference_v2():
    """FastGen-analog serving evidence (reference claims its ragged/paged v2
    engine, not v1, for the TTFT/throughput headlines): continuous batching
    through the paged KV pool — single-sequence p50 TTFT + aggregate decode
    tokens/sec with 8 concurrent 200-token prompts."""
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    cfg, params = _gpt2_inference_model()
    # hbm_check="refuse": an oversized pool/params refuses BEFORE placement
    eng = InferenceEngineV2(cfg, params, {"dtype": "bf16", "hbm_check": "refuse"})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (200,), dtype=np.int32)
               for _ in range(8)]

    # warm every bucketed program this workload touches
    eng.generate(prompts[:1], max_new_tokens=1)
    eng.generate(prompts, max_new_tokens=8)

    ttfts = []
    for _ in range(5):
        t0 = time.perf_counter()
        eng.generate(prompts[:1], max_new_tokens=1)
        ttfts.append(time.perf_counter() - t0)
    p50_ttft = sorted(ttfts)[len(ttfts) // 2]

    n_new = 64
    t0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=n_new)
    dt = time.perf_counter() - t0
    # aggregate decode rate net of the (measured) prefill phase
    decode_tok_s = 8 * (n_new - 1) / max(dt - p50_ttft, 1e-6)
    return {"p50_ttft_ms": round(p50_ttft * 1e3, 2),
            "batch8_decode_tokens_per_sec": round(decode_tok_s, 1)}


def bench_train_long_context(peak_flops):
    """Long-sequence training on one chip: seq 8k, flash kernel + remat.

    The BASELINE-tracked long-context config (8B @ 32k Ulysses) needs a pod;
    this measures the single-chip building block it is made of — the causal
    flash kernel's triangle grid at long S, where attention grows to ~half
    the model flops."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    seq = 8192
    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, max_seq_len=seq,
        norm="rmsnorm", activation="silu_glu", position="rope",
        remat=True, dtype=jax.numpy.bfloat16, scan_layers=False, fused_ce=False,
    )
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "hbm_guard": {"enabled": True},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
        },
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    tok_per_sec = _train_tokens_per_sec(engine, batch, steps=5, warmup=2)
    return {
        "seq_len": seq,
        "tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "mfu": round(tok_per_sec * cfg.flops_per_token(seq) / peak_flops, 4),
    }


def bench_train_fpdt_long_context(peak_flops):
    """FPDT chunked-attention TRAINING at 32k on one chip (round 5; reference
    fpdt_layer.py claims training sequences past attention's memory wall).

    The custom-VJP chunked attention holds O(S*chunk) score state instead of
    O(S^2): 32k would need ~12 GB of fp32 scores per (layer, head) pair dense,
    and the flash kernel's backward still rematerializes full rows; FPDT's
    tile recompute keeps the whole 125M-geometry model + 32k tokens resident
    on one v5e chip."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    seq = 32768
    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, max_seq_len=seq,
        norm="rmsnorm", activation="silu_glu", position="rope",
        attn_impl="fpdt", fpdt_q_chunk=2048, fpdt_kv_chunk=2048,
        remat=True, dtype=jax.numpy.bfloat16, scan_layers=False, fused_ce=False,
    )
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "hbm_guard": {"enabled": True},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
        },
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    tok_per_sec = _train_tokens_per_sec(engine, batch, steps=3, warmup=1)
    return {
        "seq_len": seq,
        "attn_impl": "fpdt",
        "tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "mfu": round(tok_per_sec * cfg.flops_per_token(seq) / peak_flops, 4),
    }


def bench_train_fpdt_131k(peak_flops):
    """FPDT at 131072 tokens on ONE chip (stretch evidence for the
    reference's 16x-longer-sequences claim; fpdt_layer.py trains 2M tokens on
    four 40G GPUs with host offload — 131k on a single 16G v5e is the same
    regime). HBM math: 12 checkpointed [131k, 768] bf16 residuals ~2.4 GiB +
    fp32 Adam for 125M params ~1.5 GiB + per-chunk score state ~0.2 GiB."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    seq = 131072
    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, max_seq_len=seq,
        norm="rmsnorm", activation="silu_glu", position="rope",
        attn_impl="fpdt", fpdt_q_chunk=2048, fpdt_kv_chunk=2048,
        remat=True, dtype=jax.numpy.bfloat16, scan_layers=True, fused_ce=True,
    )
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "hbm_guard": {"enabled": True},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
        },
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (1, seq), dtype=np.int32)}
    tok_per_sec = _train_tokens_per_sec(engine, batch, steps=2, warmup=1)
    return {
        "seq_len": seq,
        "attn_impl": "fpdt",
        "tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "mfu": round(tok_per_sec * cfg.flops_per_token(seq) / peak_flops, 4),
    }


def bench_serving_overhead():
    """Host-side v2 serving overhead (tools/bench_serving.py): allocator,
    staged assembly, and host µs per decoded token at decode_chain 1 vs 8.
    Pure host work."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "bench_serving.py")
    spec = importlib.util.spec_from_file_location("bench_serving", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    host = mod.bench_host_path()
    return {
        "host_us_per_decode_token_k1":
            host["per_token_loop"]["host_us_per_decode_token"],
        "host_us_per_decode_token_k8":
            host["chained"]["host_us_per_decode_token"],
        "host_us_speedup": host["host_us_speedup"],
        "programs_per_decode_token_k8":
            host["chained"]["programs_per_decode_token"],
        "allocator": mod.bench_allocator(),
        "assembly": mod.bench_assembly(),
    }


def bench_snapshot_overhead():
    """Step-time overhead of cadenced async elastic snapshots
    (``checkpoint/snapshot.py``) on the CPU bench model — the <2% bound
    ISSUE 6 commits to. Two identical engines (snapshots off / cadence-5
    async) step in PAIRED alternation — one off-step, one on-step, repeated
    over whole cadence cycles — so the CPU-frequency/load drift that swamps
    block timings (±15% observed between 10-step blocks on a shared host)
    hits both sides of every pair equally and cancels. The step program is
    byte-identical with snapshots on; the only step-clock cost is the
    boundary device→host copy (serialize + checksum + fsync + commit run in
    the writer thread)."""
    import shutil
    import tempfile

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.checkpoint import snapshot as snap
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    # micro 4 x seq 256: the step must be non-trivial for the ratio to mean
    # anything — the snapshot's synchronous cost (the boundary D2H copy of
    # the fp32 state) is FIXED per snapshot, so a toy 2-ms step at cadence 2
    # would measure the copy, not the amortized overhead a real cadence sees
    seq, micro, pairs, warmup, every = 256, 4, 60, 5, 5
    snap_dir = tempfile.mkdtemp(prefix="dstpu_snap_bench_")

    def build(snapshot_block):
        engine, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(cfg, example_seq_len=seq),
            config={
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 1},
                "bf16": {"enabled": True},
                "steps_per_print": 10_000,
                **snapshot_block,
            })
        return engine

    try:
        e_off = build({})
        e_on = build({"snapshot": {"enabled": True, "dir": snap_dir,
                                   "every_n_steps": every, "keep": 2}})
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(
            0, cfg.vocab_size, (e_off.train_batch_size, seq), dtype=np.int32)}

        def one_step(engine):
            t0 = time.perf_counter()
            m = engine.train_batch(batch)
            np.asarray(m["loss"])  # paired timing needs the per-step sync
            return time.perf_counter() - t0

        for e in (e_off, e_on):  # compile + first write outside the clock
            for _ in range(warmup):
                m = e.train_batch(batch)
            np.asarray(m["loss"])

        t_off = t_on = 0.0
        for _ in range(pairs):  # pairs % every == 0: whole cadence cycles
            t_off += one_step(e_off)
            t_on += one_step(e_on)
        e_on.snapshot_manager.wait()  # durability barrier outside the clock

        ms_off = t_off / pairs * 1e3
        ms_on = t_on / pairs * 1e3
        overhead_pct = (ms_on - ms_off) / ms_off * 100.0
        return {
            "model": "gpt2_cpu_bench_2L_128h_seq256_micro4",
            "snapshot_every_n_steps": every,
            "ms_per_step_snapshots_off": round(ms_off, 3),
            "ms_per_step_snapshots_on": round(ms_on, 3),
            "overhead_pct": round(overhead_pct, 2),
            "bound_pct": 2.0,
            "within_bound": bool(overhead_pct < 2.0),
            "snapshots_committed": len(snap.list_snapshots(snap_dir)),
        }
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)


def bench_compile_observability():
    """Host overhead of the compiled-program registry + telemetry
    (``telemetry/programs.py``) — the <2% bound ISSUE 7 commits to.

    One engine, built with telemetry AND program capture enabled, steps in
    PAIRED alternation with the process-global tracer flipped off/on around
    each step (same drift-cancelling discipline as the snapshot bench; the
    compiled program is identical either way, so the pair isolates exactly
    the host-side span/metric/watcher work). Program capture itself is paid
    once per compile — during warmup here — and is reported separately as
    ``capture_ms_total`` rather than smeared into the steady-state ratio."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu import telemetry as telemetry_mod
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.telemetry.programs import get_program_registry

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq, micro, pairs, warmup = 256, 4, 50, 5
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
            "telemetry": {"enabled": True, "programs": True},
        })
    tracer = telemetry_mod.get_tracer()
    registry = get_program_registry()
    # the registry is process-global: earlier benches' captures must not
    # inflate this bench's reported counts/capture totals
    registry.reset()
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}

    for _ in range(warmup):  # compile + one-time program capture off the clock
        m = engine.train_batch(batch)
    np.asarray(m["loss"])

    def one_step(enabled):
        tracer.enabled = enabled
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        np.asarray(m["loss"])  # paired timing needs the per-step sync
        return time.perf_counter() - t0

    t_off = t_on = 0.0
    try:
        for _ in range(pairs):
            t_off += one_step(False)
            t_on += one_step(True)
    finally:
        tracer.enabled = True

    records = registry.history("train_step")
    ms_off = t_off / pairs * 1e3
    ms_on = t_on / pairs * 1e3
    overhead_pct = (ms_on - ms_off) / ms_off * 100.0
    return {
        "model": "gpt2_cpu_bench_2L_128h_seq256_micro4",
        "ms_per_step_telemetry_off": round(ms_off, 3),
        "ms_per_step_telemetry_on": round(ms_on, 3),
        "overhead_pct": round(overhead_pct, 2),
        "bound_pct": 2.0,
        "within_bound": bool(overhead_pct < 2.0),
        "programs_captured": len(registry.records()),
        "capture_ms_total": round(sum(r.capture_s for r in registry.records()) * 1e3, 1),
        "train_step_flops": records[-1].flops if records else 0.0,
        "train_step_peak_hbm_bytes": records[-1].peak_hbm_bytes if records else 0,
        "hbm_estimate_ratio": records[-1].hbm_estimate_ratio if records else None,
    }


def bench_coll_observability():
    """Host overhead of the collective observatory's timing mode
    (``collectives/observatory.py``) — the <2% bound ISSUE 11 commits to,
    same paired-step discipline as the PR-5/PR-7 overhead guards.

    ONE engine built with the ``collectives.observe`` block enabled steps in
    PAIRED alternation with the observatory flipped off/on around each step.
    A routed collective signature is registered on the engine's mesh before
    the clock (the PR-1 comm-probe idiom), so enabled steps pay the real
    ``on_step`` hook INCLUDING sampled probe dispatches at the configured
    cadence; probe compiles happen during warmup (``sample_now``), never on
    the clock. ``pairs`` is a whole number of cadence cycles so off/on see
    identical probe phases."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist_mod
    from deepspeed_tpu.collectives import observatory
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.utils.compat import shard_map

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq, micro, sample_every, pairs, warmup = 256, 4, 4, 48, 5
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
            "collectives": {"enabled": True,
                            "observe": {"enabled": True,
                                        "sample_every": sample_every,
                                        "persist": False,
                                        "refit_every": 0}},
        })
    obs = engine._coll_observatory
    assert obs is not None
    # register one routed signature on the engine's mesh (the GSPMD step
    # has no explicit facade collective to observe — PR-8 note), so probes
    # have something real to time
    axis = "dp"
    n = int(engine.mesh.shape[axis])
    probe = jax.jit(shard_map(
        lambda v: dist_mod.all_reduce(v, axis, algorithm="ring", codec="int8",
                                      block_size=256),
        mesh=engine.mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False))
    probe(jnp.ones((n * n * 256,), jnp.float32)).block_until_ready()
    probes_warm = obs.sample_now()  # probe compiles off the clock

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    for _ in range(warmup):
        m = engine.train_batch(batch)
    np.asarray(m["loss"])

    def one_step(enabled):
        obs.config.enabled = enabled
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        np.asarray(m["loss"])  # paired timing needs the per-step sync
        return time.perf_counter() - t0

    t_off = t_on = 0.0
    try:
        for _ in range(pairs):
            t_off += one_step(False)
            t_on += one_step(True)
    finally:
        obs.config.enabled = True

    s = obs.summary()
    ms_off = t_off / pairs * 1e3
    ms_on = t_on / pairs * 1e3
    overhead_pct = (ms_on - ms_off) / ms_off * 100.0
    return {
        "model": "gpt2_cpu_bench_2L_128h_seq256_micro4",
        "sample_every": sample_every,
        "ms_per_step_observatory_off": round(ms_off, 3),
        "ms_per_step_observatory_on": round(ms_on, 3),
        "overhead_pct": round(overhead_pct, 2),
        "bound_pct": 2.0,
        "within_bound": bool(overhead_pct < 2.0),
        "probes_warmup": probes_warm,
        "probes_merged": s["merged_samples"],
        "table_rows": s["table_rows"],
        "routes": s["routes"],
    }


def bench_fleet_overhead():
    """Host overhead of fleet telemetry export (``telemetry/collector.py``)
    — the <2% bound ISSUE 13 commits to, same paired-step discipline as the
    PR-5/7/11 guards.

    ONE telemetry-enabled engine steps in paired off/on alternation against
    a live in-process :class:`FleetCollector`; every ``cadence``-th on-step
    pays a ``FleetClient.push_async`` on the clock — the hot-path push API:
    the registry dump + heartbeat snapshot happens synchronously (the cost
    a step actually sees) and the HTTP round-trip rides the client's worker
    thread, exactly like the production daemon-cadence wiring. Cadence 5
    per STEP is far denser than the config default (a 5-second wall-clock
    interval), so the bound holds with margin for any real deployment."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.telemetry.collector import FleetClient, FleetCollector

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq, micro, cadence, pairs, warmup = 256, 4, 5, 60, 5
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
            "telemetry": {"enabled": True},
        })
    collector = FleetCollector().start()
    client = FleetClient(collector.url, observatory=None)
    client.register()

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    for _ in range(warmup):
        m = engine.train_batch(batch)
    np.asarray(m["loss"])
    client.push(include_table=False)  # first push (lazy setup) off the clock

    on_steps = [0]

    def one_step(push):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        if push:
            on_steps[0] += 1
            if on_steps[0] % cadence == 0:
                client.push_async(include_table=False)
        np.asarray(m["loss"])  # paired timing needs the per-step sync
        return time.perf_counter() - t0

    try:
        t_off = t_on = 0.0
        for _ in range(pairs):  # pairs % cadence == 0: whole push cycles
            t_off += one_step(False)
            t_on += one_step(True)
        client.flush()  # drain the async worker off the clock
    finally:
        collector.stop()

    ms_off = t_off / pairs * 1e3
    ms_on = t_on / pairs * 1e3
    overhead_pct = (ms_on - ms_off) / ms_off * 100.0
    return {
        "model": "gpt2_cpu_bench_2L_128h_seq256_micro4",
        "push_every_n_steps": cadence,
        "ms_per_step_fleet_off": round(ms_off, 3),
        "ms_per_step_fleet_on": round(ms_on, 3),
        "overhead_pct": round(overhead_pct, 2),
        "bound_pct": 2.0,
        "within_bound": bool(overhead_pct < 2.0),
        "pushes": client.pushes,
        "push_failures": client.push_failures,
        "federated_metric_children": collector.federated_registry().size(),
    }


def bench_event_plane_overhead():
    """Host overhead of the incident plane (``telemetry/events.py`` +
    ``telemetry/alerts.py``) — the <2% bound ISSUE 20 commits to, same
    paired-step discipline as the PR-5/7/11/13/16 guards.

    On-steps emit one typed structured event right after the loss sync
    (lock + ring append + counter mints + per-subscriber fanout) and every
    ``cadence``-th on-step pays a full ``AlertEngine.evaluate()`` over the
    default rule pack on the clock — the exact host work a detector site
    and the alert cadence thread add to a production step. One event per
    STEP plus an evaluate every 5 steps is far denser than any real run
    (detectors only emit on anomalies; the cadence thread defaults to a
    5-second wall-clock interval), so the bound holds with margin. The
    step program itself never changes: emission is host-side only, which
    the jaxpr-identity pin in tests/unit/test_events_alerts.py enforces."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.telemetry import alerts as alerts_mod
    from deepspeed_tpu.telemetry import events as events_mod

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq, micro, cadence, pairs, warmup = 256, 4, 5, 60, 5
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
            "telemetry": {"enabled": True},
        })
    stream = events_mod.configure_events(capacity=4096, jsonl_path=None)
    stream.clear()
    alert_eng = alerts_mod.configure_alerts()  # default rule pack, no sinks
    emit = events_mod.emit_event

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    for _ in range(warmup):
        m = engine.train_batch(batch)
    np.asarray(m["loss"])
    alert_eng.evaluate()  # first evaluate (lazy rule state) off the clock

    on_steps = [0]

    def one_step(plane_on):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        if plane_on:
            on_steps[0] += 1
            emit("bench", "step_tick",
                 f"bench event-plane tick {on_steps[0]}", severity="info",
                 labels={"bench": "event_plane_overhead"}, step=on_steps[0])
            if on_steps[0] % cadence == 0:
                alert_eng.evaluate()
        np.asarray(m["loss"])  # paired timing needs the per-step sync
        return time.perf_counter() - t0

    t_off = t_on = 0.0
    for _ in range(pairs):  # pairs % cadence == 0: whole evaluate cycles
        t_off += one_step(False)
        t_on += one_step(True)

    ms_off = t_off / pairs * 1e3
    ms_on = t_on / pairs * 1e3
    overhead_pct = (ms_on - ms_off) / ms_off * 100.0
    return {
        "model": "gpt2_cpu_bench_2L_128h_seq256_micro4",
        "evaluate_every_n_steps": cadence,
        "ms_per_step_events_off": round(ms_off, 3),
        "ms_per_step_events_on": round(ms_on, 3),
        "overhead_pct": round(overhead_pct, 2),
        "bound_pct": 2.0,
        "within_bound": bool(overhead_pct < 2.0),
        "events_emitted": stream.total_emitted,
        "alert_rules": len(alert_eng.rules),
        "firing_alerts": [f["rule"] for f in alert_eng.firing()],
    }


def bench_perf_ledger_overhead():
    """Row-emission overhead of the unified perf ledger
    (``telemetry/perfledger.py``) — the <2% bound ISSUE 16 commits to, same
    paired-step discipline as the PR-5/7/11/13 guards.

    On-steps append one identity-stamped schema-v1 row to a REAL JSONL
    ledger (tempdir) right after the loss sync — the exact emit an
    instrumented bench or serving run pays per measurement: make_row's
    stamping (identity, git sha, backend) plus validate + lock + open +
    append + fsync-free write. One row per step is far denser than any real
    emitter (one row per whole run), so the bound holds with margin."""
    import os
    import tempfile

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.telemetry.perfledger import (
        PerfLedger, make_row, resolve_git_sha,
    )

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq, micro, pairs, warmup = 256, 4, 60, 5
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
            "steps_per_print": 10_000,
        })
    ledger = PerfLedger(tempfile.mkdtemp(prefix="perf_ledger_bench_"))
    resolve_git_sha()  # warm the one subprocess stamp off the clock

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}
    for _ in range(warmup):
        m = engine.train_batch(batch)
    np.asarray(m["loss"])
    ledger.append([make_row("perf", "ledger_probe/loss", 0.0, "nats",
                            direction="lower")])  # lazy mkdir off the clock

    def one_step(emit):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        loss = float(np.asarray(m["loss"]))  # paired timing needs the sync
        if emit:
            ledger.append([make_row("perf", "ledger_probe/loss", loss,
                                    "nats", direction="lower")])
        return time.perf_counter() - t0

    t_off = t_on = 0.0
    for _ in range(pairs):
        t_off += one_step(False)
        t_on += one_step(True)

    ms_off = t_off / pairs * 1e3
    ms_on = t_on / pairs * 1e3
    overhead_pct = (ms_on - ms_off) / ms_off * 100.0
    return {
        "model": "gpt2_cpu_bench_2L_128h_seq256_micro4",
        "rows_emitted": pairs + 1,
        "ledger_bytes": os.path.getsize(ledger.path_for("perf")),
        "ms_per_step_ledger_off": round(ms_off, 3),
        "ms_per_step_ledger_on": round(ms_on, 3),
        "overhead_pct": round(overhead_pct, 2),
        "bound_pct": 2.0,
        "within_bound": bool(overhead_pct < 2.0),
    }


def bench_numerics_overhead():
    """Step-time overhead of the numerics observatory
    (``telemetry/numerics.py``): in-jit divergence sentinel + sampled wire
    probes + host hook — the <2% bound ISSUE 17 commits to.

    Unlike the host-flag overhead benches, the sentinel is TRACED into the
    step, so off/on are two engines (identical config, numerics block
    absent vs enabled) stepping the same batch in paired alternation.
    Reported worst-of-three rounds: the bound must hold on the worst round,
    not a lucky mean. One routed lossy signature is registered before the
    clock so sampled steps pay real wire-probe dispatches (compiles happen
    during ``sample_now`` warmup, never on the clock)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist_mod
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.telemetry import numerics
    from deepspeed_tpu.utils.compat import shard_map

    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq, micro, sample_every, warmup = 256, 4, 4, 5
    rounds, pairs = 3, 16  # pairs per round: whole cadence cycles

    def build(numerics_block):
        engine, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(cfg, example_seq_len=seq),
            config={
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 1},
                "bf16": {"enabled": True},
                "steps_per_print": 10_000,
                **({"numerics": numerics_block} if numerics_block else {}),
            })
        return engine

    # baseline FIRST: a no-numerics engine resets the process-global
    # observatory on construction (hygiene), so the enabled engine must be
    # built after it
    eng_off = build(None)
    eng_on = build({"enabled": True, "sample_every": sample_every,
                    "sentinel_sample_every": sample_every})
    obs = numerics.get_observatory()
    # a routed lossy signature so sampled steps run a real fidelity probe
    axis = "dp"
    n = int(eng_on.mesh.shape[axis])
    probe = jax.jit(shard_map(
        lambda v: dist_mod.all_reduce(v, axis, algorithm="ring", codec="int8",
                                      block_size=256),
        mesh=eng_on.mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False))
    probe(jnp.ones((n * n * 256,), jnp.float32)).block_until_ready()
    obs.sample_now()  # probe compiles off the clock

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (eng_on.train_batch_size, seq), dtype=np.int32)}
    for _ in range(warmup):
        m_off = eng_off.train_batch(batch)
        m_on = eng_on.train_batch(batch)
    np.asarray(m_off["loss"]), np.asarray(m_on["loss"])

    def one_step(engine):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        np.asarray(m["loss"])  # paired timing needs the per-step sync
        return time.perf_counter() - t0

    round_pcts, ms_offs, ms_ons = [], [], []
    for _ in range(rounds):
        t_off = t_on = 0.0
        for _ in range(pairs):
            t_off += one_step(eng_off)
            t_on += one_step(eng_on)
        ms_offs.append(t_off / pairs * 1e3)
        ms_ons.append(t_on / pairs * 1e3)
        round_pcts.append((t_on - t_off) / t_off * 100.0)

    worst = max(round_pcts)
    return {
        "model": "gpt2_cpu_bench_2L_128h_seq256_micro4",
        "sample_every": sample_every,
        "sentinel_sample_every": sample_every,
        "rounds": rounds,
        "pairs_per_round": pairs,
        "ms_per_step_numerics_off": round(min(ms_offs), 3),
        "ms_per_step_numerics_on": round(min(ms_ons), 3),
        "overhead_pct": round(sum(round_pcts) / rounds, 2),
        "overhead_pct_max": round(worst, 2),
        "bound_pct": 2.0,
        "within_bound": bool(worst < 2.0),
        "divergence_events": obs.divergence_events_seen,
        "wire_drift_events": obs.wire_drift_events,
        "routes": len(obs.routes()),
    }


def bench_schedule_compiler():
    """ISSUE 19 evidence: the two ``schedule``-suite headline rows.

    1. ``compiled_vs_hand/pred_ratio`` — the schedule compiler's best
       synthesized program vs the best hand-written algorithm, both costed
       by the SAME (possibly refit-calibrated) cost model, at the
       representative int8 1 MB all_reduce query. Drifting UP means the
       search started losing to its own baseline — a compiler regression
       the noise-aware gate catches without any hardware in the loop.
    2. ``fused_gemm/step_time_ratio`` — fused all-gather+matmul forward+
       backward step vs the unfused composition on the live backend (the
       T3 payoff row; on TPU < 1.0 is the win, interpret-mode CPU values
       are per-backend trajectories only).

    Rows go straight to perf-ledger suite ``schedule``
    (``perfgate.HEADLINE_PATTERNS["schedule"]``), like the sweep's
    ``coll-sweep`` rows."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.collectives import fused_gemm, schedule, selector
    from deepspeed_tpu.collectives.algorithms import ALGORITHMS
    from deepspeed_tpu.parallel import zeropp
    from deepspeed_tpu.utils.compat import shard_map

    devs = jax.devices()
    n = max(len(devs), 1)
    nbytes, codec = 1 << 20, "int8"
    cm = selector.cost_model()
    hand = min(
        selector.estimate_us("all_reduce", alg, codec, nbytes, n)
        for alg in ALGORITHMS
        if not (alg == "rhd" and (n & (n - 1))))
    sched = schedule.compile_schedule("all_reduce", (("dp", n),), nbytes,
                                      codec, cm=cm)
    pred_ratio = (sched.est_us / hand) if (sched and hand > 0) else 1.0

    mesh = Mesh(np.array(devs), ("fsdp",))
    M, Ks, N = 64, 64, 128
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(M, n * Ks)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(n * Ks, N)).astype(np.float32))

    def step_fn(xv, wv):
        def loss(a, b):
            y = zeropp.sharded_matmul(a, b, "fsdp", False, 256)
            return jnp.sum(y * y)

        return jax.grad(loss, argnums=1)(xv, wv)

    def clock(fused):
        fused_gemm.configure(enabled=fused)
        f = jax.jit(shard_map(step_fn, mesh=mesh, in_specs=(P(), P("fsdp")),
                              out_specs=P("fsdp"), check_vma=False))
        np.asarray(f(x, w))  # compile off the clock
        t0 = time.perf_counter()
        for _ in range(5):
            out = f(x, w)
        np.asarray(out)
        return time.perf_counter() - t0

    try:
        t_unfused = clock(False)
        t_fused = clock(True)
    finally:
        fused_gemm.configure(enabled=False)
    step_ratio = t_fused / t_unfused if t_unfused > 0 else 1.0

    result = {
        "world": n,
        "compiled_signature": sched.signature if sched else None,
        "compiled_pred_us": round(sched.est_us, 3) if sched else None,
        "hand_pred_us": round(hand, 3),
        "pred_ratio": round(pred_ratio, 4),
        "ms_step_unfused": round(t_unfused / 5 * 1e3, 3),
        "ms_step_fused": round(t_fused / 5 * 1e3, 3),
        "step_time_ratio": round(step_ratio, 4),
    }
    try:
        from deepspeed_tpu.telemetry.perfledger import PerfLedger, make_row

        backend = jax.default_backend()
        PerfLedger().append([
            make_row("schedule", "compiled_vs_hand/pred_ratio", pred_ratio,
                     "ratio", direction="lower", backend=backend),
            make_row("schedule", "fused_gemm/step_time_ratio", step_ratio,
                     "ratio", direction="lower", backend=backend),
        ])
    except Exception as e:  # noqa: BLE001 — evidence plane, not the bench
        import sys

        print(f"[bench] schedule-suite ledger append skipped: {e}",
              file=sys.stderr)
    return result


# Cheapest first, the big/novel configs last.
# Each entry: name -> (fn(peak_flops)->dict, timeout_s).
EXTRA_BENCHES = {
    "serving_overhead_host": (lambda peak: bench_serving_overhead(), 420),
    "elastic_snapshot_overhead": (lambda peak: bench_snapshot_overhead(), 420),
    "compile_observability": (lambda peak: bench_compile_observability(), 420),
    "coll_observability": (lambda peak: bench_coll_observability(), 420),
    "fleet_export_overhead": (lambda peak: bench_fleet_overhead(), 420),
    "event_plane_overhead": (lambda peak: bench_event_plane_overhead(), 420),
    "perf_ledger_overhead": (lambda peak: bench_perf_ledger_overhead(), 420),
    "numerics_overhead": (lambda peak: bench_numerics_overhead(), 420),
    "schedule_compiler": (lambda peak: bench_schedule_compiler(), 420),
    "llama_550m_zero3_remat": (bench_train_llama_z3, 420),
    "mixtral_style_moe": (bench_train_moe, 420),
    "inference_v1_gpt2_125m": (lambda peak: bench_inference(), 420),
    "inference_v2_ragged_gpt2_125m": (lambda peak: bench_inference_v2(), 480),
    "inference_v1_llama_550m": (lambda peak: bench_inference_llama(), 480),
    "long_context_8k": (bench_train_long_context, 480),
    "fpdt_long_context_32k": (bench_train_fpdt_long_context, 600),
    "nvme_offload_550m": (bench_train_nvme_offload, 600),
    "dense_760m_zero3_remat": (bench_train_dense_1b, 600),
    "dense_2b_offload_host": (bench_train_dense_2b_offload, 600),
    "dense_2b_offload_twinflow": (bench_train_dense_2b_twinflow, 600),
    "fpdt_long_context_131k": (bench_train_fpdt_131k, 900),
}


def _child_main(name: str) -> None:
    """Child-process entry (``bench.py --one NAME``): run exactly one
    benchmark on the chip and print its result as the LAST stdout line. One
    process per benchmark: each starts from an empty device, and the chip
    belongs to one process at a time."""
    import sys

    import jax

    from deepspeed_tpu.profiling.flops_profiler import device_peaks
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no chip: jax reports platform {dev.platform!r}", file=sys.stderr)
        raise SystemExit(2)
    enable_compile_cache()
    peak_flops = device_peaks(dev.device_kind).bf16_flops  # unknown kind raises
    if name == "_headline":
        tok_per_sec, mfu, seq, stamp, telem = bench_train_gpt2(peak_flops)
        out = {"tok_per_sec": tok_per_sec, "mfu": mfu, "seq": seq,
               "autotuned": stamp,
               **({"telemetry": telem} if telem else {})}
    else:
        out = EXTRA_BENCHES[name][0](peak_flops)
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    print(json.dumps(out), flush=True)


def _run_isolated(name: str, timeout_s: float) -> dict:
    """Run one benchmark in a subprocess and return its parsed JSON; a
    child that times out, exits non-zero or prints no result raises."""
    import os
    import signal
    import subprocess
    import sys

    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--one", name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True,
    )
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        # the TPU runtime forks helpers: take the whole group down
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
        raise RuntimeError(f"{name}: timeout after {timeout_s:.0f}s") from None
    if proc.returncode == 0:
        for line in reversed((out or "").strip().splitlines()):
            try:
                parsed = json.loads(line)
            except ValueError:
                continue
            if isinstance(parsed, dict):  # a stray scalar print is not a result
                return parsed
    tail = " | ".join((err or "").strip().splitlines()[-4:])[-600:]
    raise RuntimeError(
        f"{name}: exit code {proc.returncode}: {tail or 'no JSON on stdout'}")

def _emit_perf_ledger(result: dict, backend: str) -> None:
    """Append this run's numbers to the unified perf ledger alongside the
    legacy JSON line (ISSUE 16): the headline into suite ``bench`` (the
    same two rows migration derives from a BENCH_rNN artifact), every
    numeric leaf of each successful extra into suite ``perf`` under
    ``<extra>/<path>`` — so the ``*overhead_pct`` rows land under the
    gate's absolute <2% bound automatically. Best-effort: the bench must
    never fail because the ledger dir is unwritable."""
    import sys

    try:
        from deepspeed_tpu.telemetry.perfledger import PerfLedger, make_row
        from deepspeed_tpu.telemetry.perfmigrate import (
            direction_for, flatten_numeric, unit_for,
        )

        rows = [make_row("bench", result["metric"], result["value"],
                         result["unit"], backend=backend)]
        if "vs_baseline" in result:
            rows.append(make_row("bench", f"{result['metric']}/vs_baseline",
                                 result["vs_baseline"], "ratio",
                                 backend=backend))
        for name, extra in (result.get("extras") or {}).items():
            if not isinstance(extra, dict) or "error" in extra:
                continue
            for path, value in flatten_numeric(extra):
                metric = f"{name}/{path}"
                rows.append(make_row("perf", metric, value, unit_for(metric),
                                     direction_for(metric), backend=backend))
        PerfLedger().append(rows)
    except Exception as e:  # noqa: BLE001 — evidence plane, not the bench
        print(f"[bench] perf-ledger append skipped: {e}", file=sys.stderr)


def main() -> None:
    """Orchestrator: the parent never imports jax (so it never holds the
    chip) — every benchmark runs in its own timeout-guarded child, and the
    first one that fails ends the run with a non-zero exit and no result."""
    import sys

    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        _child_main(sys.argv[2])
        return

    headline = _run_isolated("_headline", 900)
    extras = {name: _run_isolated(name, timeout_s)
              for name, (_, timeout_s) in EXTRA_BENCHES.items()}

    stamp = headline.get("autotuned")
    result = {
        "metric": f"tokens_per_sec_per_chip_gpt2_125m_bf16_seq{headline['seq']}",
        "value": round(headline["tok_per_sec"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(headline["mfu"] / 0.45, 4),
        "device": headline["device"],
        **({"autotuned": stamp} if stamp else {}),
        **({"telemetry": headline["telemetry"]} if headline.get("telemetry") else {}),
        "extras": extras,
    }
    print(json.dumps(result))
    from deepspeed_tpu.telemetry.perfledger import backend_stamp

    _emit_perf_ledger(result, backend=backend_stamp(headline["device"]["kind"]))


if __name__ == "__main__":
    main()
