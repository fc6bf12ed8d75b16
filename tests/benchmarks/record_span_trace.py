#!/usr/bin/env python3
"""Record the small device trace that ``tests/benchmarks/test_spans_scopes.py``
checks ``benchmarks/lib/spans.py``, ``scopes.py`` and the readers built on
them against.

``python tests/benchmarks/record_span_trace.py --out chiprun_out/v5e_1chip_spans``
needs a TPU. Under one profiler session with a ``bench:window`` span it runs a
few ``train_batch`` steps of a tiny trainer and one ``generate`` of a tiny v2
engine (a prefill and a few decode chains): the Pythia-410M file with every
size cut, through the same seam the runners use. So the file holds the
program's ``dstpu:`` host spans with their args, its named Pallas kernels
(``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, ``paged_attn``) and its
scopes (``embed``, ``layers``, ``lm_head_ce``, ``optimizer``, ``pool_scan``,
``layer``, ``kv_write``, ``lm_head``, ``sample``). It writes
the ``.xplane.pb`` gzipped to ``<out>.xplane.pb.gz`` (the HLO of three real
programs, which maps an instruction to its ``op_name``, is 1.9 MB of a 2.9 MB
file; gzipped it is 0.4 MB) and what a reader needs to see by hand to
``<out>.txt``: the spans, the idle and scope tables and the longest
instructions with their ``op_name``.
"""

from __future__ import annotations

import argparse
import gzip
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)))

from benchmarks.lib import harness, program, scopes, spans, xplane  # noqa: E402

TINY = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2, num_attention_heads=2,
            vocab_size=512, max_position_embeddings=256)
SEQ, SEQUENCES = 256, 4
TRAIN = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
         "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.1}},
         "zero_optimization": {"stage": 1}, "bf16": {"enabled": True},
         "gradient_clipping": 1.0, "steps_per_print": 1000000}
SERVE = {"dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 8, "decode_chain": 4,
         "kv_block_size": 16, "num_kv_blocks": 64, "row_bucket": 8, "chunk_bucket": 32,
         "hbm_check": "off"}
PROMPTS, PROMPT_LEN, NEW_TOKENS = 6, 24, 9  # one prefill, then two chains of 4


def describe(path: str, pool_shape) -> str:
    out = ["# the serving engine's KV pool (engine.pool.k.shape): %s" % list(pool_shape),
           "# dstpu: spans of the host planes, clipped to bench:window: name start_s seconds args"]
    t0 = None
    for s in spans.read_spans(path):
        t0 = s.start_s if t0 is None else t0
        out.append(f"{s.name} {s.start_s - t0:.6f} {s.seconds:.6f} {s.args}")
    out.append("# idle seconds of the device in gaps of 20 us or more, by innermost span")
    out += [f"{k!r} {v:.6f}" for k, v in spans.idle_by_span(path).items()]
    by_scope = scopes.scope_seconds(path)
    out.append("# device self seconds by innermost scope of ours (hlo_stats, whole trace): %.6f in all"
               % sum(by_scope.values()))
    out += [f"{k} {v:.6f}" for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])]
    out.append("# the 60 longest instructions: program | name | category | count | seconds | op_name")
    out += [f"{i.program} | {i.name} | {i.category} | {i.count} | {i.seconds:.6f} | {i.op_name}"
            for i in scopes.instructions(path)[:60]]
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: jax reports {jax.devices()[0].platform!r}", file=sys.stderr)
        return 1

    import deepspeed_tpu
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import causal_lm_spec
    from deepspeed_tpu.topology.mesh import build_mesh

    config = harness.load_config("pythia-410m")
    config.update(TINY)
    model_cfg = program.model_config(config, jnp.bfloat16)
    devices = jax.devices()[:1]
    rng = np.random.default_rng(0)

    trainer, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(model_cfg, example_seq_len=SEQ), config=dict(TRAIN),
        mesh=build_mesh(devices=devices, axis_sizes={"dp": 1}), seed=0)

    def train_step():
        tokens = rng.integers(0, TINY["vocab_size"], (SEQUENCES, SEQ), dtype=np.int32)
        return float(jax.block_until_ready(trainer.train_batch({"input_ids": tokens})["loss"]))

    server = InferenceEngineV2(
        model_cfg, harness.load_runner("serve").make_weights(model_cfg, 0), dict(SERVE),
        mesh=build_mesh(devices=devices, axis_sizes={"tp": 1, "dp": 1}))

    def generate():
        prompts = [rng.integers(0, TINY["vocab_size"], PROMPT_LEN, dtype=np.int32)
                   for _ in range(PROMPTS)]
        return server.generate(prompts, max_new_tokens=NEW_TOKENS)

    print("warm-up losses", [train_step() for _ in range(2)], "tokens", len(generate()))

    trace_dir = args.out + ".trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    # no Python function events: they are most of a host plane's bytes
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        for _ in range(args.steps):
            train_step()
        generate()
    jax.profiler.stop_trace()
    trace = xplane.find_xplane(trace_dir)
    text = describe(trace, server.pool.k.shape)
    with open(trace, "rb") as raw, \
            gzip.GzipFile(args.out + ".xplane.pb.gz", "wb", compresslevel=9, mtime=0) as packed:
        shutil.copyfileobj(raw, packed)
    shutil.rmtree(trace_dir)
    with open(args.out + ".txt", "w") as f:
        f.write(text + "\n")
    print(text[-12000:])
    print("bytes", os.path.getsize(args.out + ".xplane.pb.gz"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
