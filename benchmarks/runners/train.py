"""Training cells: ``deepspeed_tpu.initialize`` -> ``train_batch``.

The workload file gives the engine's own config (``engine``: micro-batch,
accumulation, optimizer, ZeRO stage, mesh) and the traffic (``sequences`` of
``seq_len`` tokens per step). Set-up: engine and weights from ``--seed``, the
reference's loss on the warm-up batch at the initial weights, three warm-up
steps on that batch (the first compiles). Window: fresh seeded batches, one
``train_batch`` each, every one ended by ``block_until_ready`` on its loss,
until ``--seconds`` have passed; the rate is all their tokens over all that
time. With a trace directory, steps 3 to 5 of the window run under the
profiler.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.lib import costs, harness, peaks, program, stats, traffic

WARMUP_STEPS = 3
TRACED_STEPS = (3, 6)  # [first, last) step of the window under the profiler
# The program computes in bf16 from fp32 master weights; the reference in
# fp32 throughout. Over 32k tokens the rounding of single logits averages
# out: the chip read the two mean losses (about 11.3 at random weights) 1e-6
# apart (PERF.md, Findings) and a CPU run at the tests' tiny size 3e-5. The
# bound is 100 times the chip's reading; the wrong activation function at the
# tiny size already moves the loss by 8e-4.
LOSS_REL_TOL = 2e-4


def run(*, workload, config, reference, seed, seconds, devices, trace_dir, compiles,
        t_process_start):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm_spec
    from deepspeed_tpu.topology.mesh import build_mesh

    tr = workload["traffic"]
    seq, sequences = int(tr["seq_len"]), int(tr["sequences"])
    chips = len(devices)
    phases = harness.Phases(t_process_start)
    model_cfg = program.model_config(config, jnp.bfloat16)
    engine_cfg = dict(workload["engine"])
    mesh = build_mesh(devices=devices, axis_sizes=engine_cfg.pop("mesh", {"dp": chips}))
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(model_cfg, example_seq_len=seq), config=engine_cfg,
        mesh=mesh, seed=seed)
    if engine.train_batch_size != sequences:
        raise ValueError(f"the engine's batch is {engine.train_batch_size} sequences, "
                         f"the traffic's {sequences}")
    batches = traffic.token_batches(tr, config["vocab_size"], seed)
    warm = next(batches)
    phases.done("engine_and_weights")

    ref_loss = float(jax.jit(lambda w, t: reference.loss(w, program.published(config), t))(
        program.reference_weights(engine.state.params), jnp.asarray(warm)))

    phases.done("reference_loss")

    def step(tokens):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:train_batch"):
            loss = jax.block_until_ready(engine.train_batch({"input_ids": tokens})["loss"])
        return float(loss), time.perf_counter() - t0

    warm_losses = [step(warm)[0] for _ in range(WARMUP_STEPS)]
    phases.done("warm_up_steps")
    loss_err = abs(warm_losses[0] - ref_loss) / abs(ref_loss)
    harness.say(reference_loss=ref_loss, program_loss=warm_losses[0], rel_err=loss_err,
                tol=LOSS_REL_TOL, warmup_losses=warm_losses)

    compiles.mark()
    setup_s = time.perf_counter() - t_process_start
    losses, step_s = [], []
    traced = harness.TraceWindow(trace_dir)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if trace_dir and len(losses) == TRACED_STEPS[0]:
            traced.start()
        loss, dt = step(next(batches))
        losses.append(loss)
        step_s.append(dt)
        if len(losses) == TRACED_STEPS[1]:
            traced.stop()
    elapsed = time.perf_counter() - t0
    traced.stop()
    in_window = compiles.since_mark()
    memory = harness.memory_held(devices)

    tokens = len(losses) * sequences * seq
    rate = stats.rate(tokens, elapsed) / chips
    flops_token = costs.train_flops_per_token(config, seq)
    peak = peaks.device_peaks(devices[0].device_kind)
    harness.say(steps=len(losses), window_s=elapsed, step_s=stats.describe(step_s),
                train_tokens_per_s_chip=rate, flops_per_token=flops_token,
                end_to_end_mfu_pct=100 * rate * flops_token / peak.bf16_flops_per_s,
                compiles_in_window=in_window, setup_s=setup_s)
    failed = sum(1 for x in losses if not np.isfinite(x))
    correct = bool(loss_err <= LOSS_REL_TOL and warm_losses[-1] < warm_losses[0]
                   and np.isfinite(warm_losses).all() and failed == 0)
    micro = int(workload["engine"]["train_micro_batch_size_per_gpu"])
    return {
        "correct": correct, "attempted": len(losses), "failed": failed,
        "end_to_end": {"setup_s": setup_s, "train_tokens_per_s_chip": rate},
        "step_s": step_s, "compiles_in_window": in_window, "chips": chips,
        "traced_steps": TRACED_STEPS[1] - TRACED_STEPS[0],
        "micro_batch": micro, "micro_batches_per_step": sequences // (micro * chips),
        "seq_len": seq, "memory": memory,
    }
