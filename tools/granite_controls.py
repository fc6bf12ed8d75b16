#!/usr/bin/env python3
"""The planted faults that the ``granitemoehybrid`` cell's ``check`` has to
refuse, run through ``benchmarks/run.py`` itself on the chip (the readings
behind ``check.readings.logit_rel_tol.control_min`` of ``benchmarks/configs/
granite-4.0-h-micro.json``), and the decode over the traffic's own length that
the runner's check, which decodes 12 tokens, does not reach.

    python3 tools/granite_controls.py --control e4m3_out_proj|bf16_state|e4m3_state|no_dt_bias|no_D|residual_1|scores_eighth|pad_moves_state \\
        --workload granite-4.0-h-micro.serve.long-output-batch --seed <n> --seconds 5 --trace 0
    python3 tools/granite_controls.py --control drift --workload granite-4.0-h-micro.serve.long-output-batch --seed <n>

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.
The reference always runs the configuration as it is written.

- ``e4m3_out_proj`` (the nearest precision below the bf16 the WEIGHTS are kept
  in): every mixer's ``ssm_out_proj`` goes into the engine through float8_e4m3fn,
  planted on the host (``tools/routed_controls.py::plant_e4m3``); the reference
  is given the weights as they were.
- ``bf16_state`` (the nearest precision below the float32 the state is kept in):
  every state a layer writes to the pool goes through bfloat16 on its way. The
  check CANNOT see it (PERF.md, section 6, PR 42: 0.00779 beside sound runs'
  0.0078-0.0080), nor ``e4m3_state``, the next precision below (through
  float8_e4m3fn: 0.00800): two tokens after a prompt, a state's rounding is
  damped by what a mixer adds to the residual. Both are kept as readings.
- ``no_dt_bias``: the step is ``softplus(dt)`` without its bias.
- ``no_D``: ``y = S C`` without ``D x``.
- ``residual_1``: the program adds every sublayer's output whole
  (``residual_multiplier`` 1 for 0.22).
- ``scores_eighth``: the attention scores are scaled by ``head_dim ** -0.5`` =
  1/8, where the configuration says ``attention_multiplier`` 1/64.
- ``pad_moves_state``: the tokens a prompt is padded with are left to move the
  state (``ssd_chunked`` is not told which tokens are live): the same prompt at
  another padding leaves another state.

Of these the last line is ``run.py``'s: ``correct`` has to read false.

- ``drift``: 8 prompts of the traffic's lengths through the fused prefill and
  then 511 tokens of decode chains (the timed path's own greedy tokens); the
  last token is fed through ``put`` and its logits, which rest on every state
  update before them, are compared with the reference's FULL forward of the
  same 512-token continuation, as is every token generated (its gap under the
  reference's best logit). One JSON line; ``ok`` by the configuration's own
  tolerance.
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = ("e4m3_out_proj", "bf16_state", "e4m3_state", "no_dt_bias", "no_D", "residual_1", "scores_eighth", "pad_moves_state")
DRIFT_ROWS, DRIFT_TOKENS = 8, 512


def plant_config(**changed):
    """The program is built from the configuration with ``changed`` replaced."""
    from deepspeed_tpu.checkpoint import hf

    honest = hf.config_from_hf
    hf.config_from_hf = lambda hf_config: dataclasses.replace(honest(hf_config), **changed)


def plant_state_dtype(dtype):
    import jax.numpy as jnp

    from deepspeed_tpu.ops import ssm

    step, put = ssm.ssm_pool_step, ssm.put_pool_rows

    def rounded(states):
        return states.astype(dtype).astype(jnp.float32)

    def pool_step(pool, layer, x, *args, **kw):
        y, pool = step(pool, layer, x, *args, **kw)
        row = ssm.PoolRow(pool, layer, jnp.zeros(x.shape[:1], bool))
        return y, put(row, rounded(ssm.pool_rows(row, *x.shape)))

    ssm.ssm_pool_step = pool_step
    ssm.put_pool_rows = lambda row, states: put(row, rounded(states))


def plant_in_mix(change):
    """``ops/ssm.py::mix`` is handed the mixer's leaves as ``change(leaves)``."""
    from deepspeed_tpu.ops import ssm

    honest = ssm.mix
    ssm.mix = lambda zxbcdt, p, *args, **kw: honest(zxbcdt, change(dict(p)), *args, **kw)


def plant_pad_moves_state():
    from deepspeed_tpu.ops import ssm

    honest = ssm.ssd_chunked
    ssm.ssd_chunked = lambda *args: honest(*args[:-1], None)  # ``live`` comes last


def drift(workload_name: str, seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import harness, program
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.topology.mesh import build_mesh

    workload = harness.load_workload(workload_name)
    config = harness.load_config(workload["config"])
    devices = harness.require_devices(1)
    harness.enable_compile_cache()
    runner = harness.load_runner("serve")
    reference = harness.load_reference(config["architecture"])
    architecture = harness.load_architecture(config["architecture"])
    model_cfg = program.model_config(config, jnp.bfloat16)
    engine = InferenceEngineV2(model_cfg, runner.make_weights(model_cfg, seed), dict(workload["engine"]),
                               mesh=build_mesh(devices=devices, axis_sizes={"tp": 1, "dp": 1}))
    cfg, weights = program.published(config), architecture.reference_weights(engine.params)
    forward = jax.jit(lambda w, t: reference.forward(w, cfg, t)[0])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 11])
    lo, hi = workload["traffic"]["prompt_len"]["min"], workload["traffic"]["prompt_len"]["max"]
    prompts = [rng.integers(0, config["vocab_size"], int(n), dtype=np.int32)
               for n in rng.integers(lo, hi + 1, DRIFT_ROWS)]
    uids, k = list(range(DRIFT_ROWS)), engine.config.decode_chain
    # the timed path's own programs: the fused prefill, then chains kept ahead
    key = jax.device_put(jax.random.PRNGKey(0), engine._replicated)
    greedy = (("do_sample", False), ("temperature", 1.0), ("top_k", 0), ("top_p", 1.0))
    first, key = engine._put_sample(uids, prompts, key, greedy)
    seqs = [list(p) + [int(t)] for p, t in zip(prompts, first)]
    left = DRIFT_TOKENS - 2  # the last of the 512 is fed by hand
    while left > 0:
        out, emitted, key = engine.decode_chain(uids, [s[-1] for s in seqs], [left] * DRIFT_ROWS, k, key,
                                                sample_kw=greedy, ahead=left > k)
        for s, row, n in zip(seqs, out, emitted):
            s.extend(int(t) for t in row[:n])
        left -= int(emitted[0])
    logits = np.asarray(engine.put(uids, [np.asarray(s[-1:], np.int32) for s in seqs]), np.float32)
    errs, worst_gap = [], 0.0
    for p, s, got in zip(prompts, seqs, logits):
        want = np.asarray(forward(weights, jnp.asarray(np.asarray(s, np.int32)[None])))
        errs.append(program.relative_error(got, want[-1]))
        for pos in range(len(p), len(s)):
            row = want[pos - 1]
            worst_gap = max(worst_gap, float((row.max() - row[s[pos]]) / np.sqrt(np.mean(row ** 2))))
    tol = program.tolerance(config, "logit_rel_tol")
    ok = max(errs) <= tol and all(len(s) - len(p) == DRIFT_TOKENS - 1 for p, s in zip(prompts, seqs))
    print(json.dumps({"ok": bool(ok), "control": "drift", "rows": DRIFT_ROWS, "decoded": DRIFT_TOKENS,
                      "context": [len(s) for s in seqs], "drift_logit_rel_err": errs, "tol": tol,
                      "token_gap": worst_gap, "chains_ahead": engine.chains_ahead,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=CONTROLS + ("drift",))
    args, rest = ap.parse_known_args()
    if args.control == "drift":
        run = argparse.ArgumentParser()
        run.add_argument("--workload", required=True)
        run.add_argument("--seed", type=int, default=0)
        asked, _ = run.parse_known_args(rest)
        return drift(asked.workload, asked.seed)
    if args.control == "e4m3_out_proj":
        import routed_controls

        routed_controls.plant_e4m3(lambda path: "'ssm_out_proj'" in path)
    elif args.control in ("bf16_state", "e4m3_state"):
        import jax.numpy as jnp

        plant_state_dtype(jnp.bfloat16 if args.control == "bf16_state" else jnp.float8_e4m3fn)
    elif args.control == "no_dt_bias":
        plant_in_mix(lambda p: dict(p, dt_bias=0 * p["dt_bias"]))
    elif args.control == "no_D":
        plant_in_mix(lambda p: dict(p, D=0 * p["D"]))
    elif args.control == "residual_1":
        plant_config(residual_multiplier=1.0)
    elif args.control == "scores_eighth":
        plant_config(attention_multiplier=0.125)
    else:
        plant_pad_moves_state()
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
