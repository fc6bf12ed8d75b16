"""Training cells: ``deepspeed_tpu.initialize`` -> ``train_batch``.

The workload file gives the engine's own config (``engine``: micro-batch,
accumulation, optimizer, ZeRO stage, mesh) and the traffic (``sequences`` of
``seq_len`` tokens per step). Set-up: engine and weights from ``--seed``, the
reference's loss on the warm-up batch at the initial weights, three warm-up
steps on that batch (the first compiles). Window: fresh seeded batches, one
``train_batch`` each, dispatched as long as the step is due to start on the
device before ``--seconds`` have passed, every one ended by
``block_until_ready`` on its loss; the rate is all their tokens over all the
time up to the last one's end. ``traffic.steps_in_flight`` is how many steps
the loop keeps dispatched before it waits for the oldest, as a trainer does
that fetches its loss every so many steps: the device then has work queued
while the host stalls, and a stall shorter than the queue costs no device time
(1 is a loop that waits for every step). With a trace directory, steps 3 to 5 of the
window run under the profiler, the queue drained before it starts and stops.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmarks.lib import costs, harness, peaks, program, stats, traffic

WARMUP_STEPS = 3
TRACED_STEPS = (3, 6)  # [first, last) step of the window under the profiler


def run(*, workload, config, reference, architecture, seed, seconds, devices, trace_dir,
        compiles, t_process_start):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm_spec
    from deepspeed_tpu.topology.mesh import build_mesh

    tr = workload["traffic"]
    seq, sequences = int(tr["seq_len"]), int(tr["sequences"])
    chips = len(devices)
    # relative error of the first loss against the fp32 reference's on the same
    # sequences; the configuration's own, stated with its readings, no default
    loss_tol = program.tolerance(config, "loss_rel_tol")
    in_flight = int(tr["steps_in_flight"])
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
        architecture.reference_weights(engine.state.params), jnp.asarray(warm)))

    phases.done("reference_loss")

    def dispatch(tokens):  # one step, not waited for: its loss, still on the device
        with jax.profiler.TraceAnnotation("bench:train_batch"):
            return engine.train_batch({"input_ids": tokens})["loss"]

    def step(tokens):
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(dispatch(tokens)))
        return loss, time.perf_counter() - t0

    warm_losses, warm_s = zip(*(step(warm) for _ in range(WARMUP_STEPS)))
    warm_losses = list(warm_losses)
    phases.done("warm_up_steps")
    loss_err = abs(warm_losses[0] - ref_loss) / abs(ref_loss)
    harness.say(reference_loss=ref_loss, program_loss=warm_losses[0], rel_err=loss_err,
                tol=loss_tol, warmup_losses=warm_losses)

    compiles.mark()
    setup_s = time.perf_counter() - t_process_start
    # step_s: from one step's end to the next one's (the first from the window's start)
    losses, step_s, pending = [], [], collections.deque()
    traced = harness.TraceWindow(trace_dir)
    t0 = last_end = time.perf_counter()

    def wait_for_oldest():
        nonlocal last_end
        losses.append(float(jax.block_until_ready(pending.popleft())))
        now = time.perf_counter()
        step_s.append(now - last_end)
        last_end = now

    while True:
        dispatched = len(losses) + len(pending)
        if trace_dir and dispatched in TRACED_STEPS:
            if pending:  # the profiler starts and stops on an empty queue
                wait_for_oldest()
                continue
            if dispatched == TRACED_STEPS[0]:
                traced.start()
            else:
                traced.stop()
        # a step goes out only if it is due to start on the device inside the window
        due_s = (time.perf_counter() - t0) + len(pending) * warm_s[-1]
        if len(pending) < in_flight and due_s < seconds:
            pending.append(dispatch(next(batches)))
        elif pending:
            wait_for_oldest()
        else:
            break
    elapsed = last_end - t0
    traced.stop()
    in_window = compiles.since_mark()
    memory = harness.memory_held(devices)

    tokens = len(losses) * sequences * seq
    rate = stats.rate(tokens, elapsed) / chips
    flops_token = costs.train_flops_per_token(
        architecture.matmul_params(config), architecture.layers(config), architecture.heads(config),
        architecture.head_dim(config), seq)
    peak = peaks.device_peaks(devices[0].device_kind)
    # a run whose rate reads far off says here how far apart the ends of two steps came at most
    harness.say(steps=len(losses), steps_in_flight=in_flight, window_s=elapsed,
                step_s=stats.describe(step_s), longest_step_s=max(step_s),
                train_tokens_per_s_chip=rate, flops_per_token=flops_token,
                end_to_end_mfu_pct=100 * rate * flops_token / peak.bf16_flops_per_s,
                compiles_in_window=in_window, setup_s=setup_s)
    failed = sum(1 for x in losses if not np.isfinite(x))
    correct = bool(loss_err <= loss_tol and warm_losses[-1] < warm_losses[0]
                   and np.isfinite(warm_losses).all() and failed == 0)
    micro = int(workload["engine"]["train_micro_batch_size_per_gpu"])
    return {
        "correct": correct, "attempted": len(losses), "failed": failed,
        "end_to_end": {"setup_s": setup_s, "train_tokens_per_s_chip": rate},
        "step_s": step_s, "compiles_in_window": in_window, "chips": chips,
        "traced_steps": TRACED_STEPS[1] - TRACED_STEPS[0],
        "micro_batch": micro, "micro_batches_per_step": sequences // (micro * chips),
        "seq_len": seq, "memory": memory, "compared": {"loss_rel_err": [loss_err, loss_tol]},
    }
