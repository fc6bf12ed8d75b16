#!/usr/bin/env python
"""Numerics observatory smoke: detection + quiet, exit-gated BOTH ways.

The proof that ISSUE 17's sentinel actually fires and actually stays
quiet:

  1. **Clean run MUST be quiet** — a 20-step train run with the sentinel
     sampling every step raises ZERO divergence events. A sentinel that
     cries wolf gets ignored; a noisy round fails the stage.
  2. **Injected corruption MUST be detected within one sampled step** —
     ``diagnostics.faultinject.FaultInjector.flip_param_bit`` flips one
     mantissa bit in ONE dp replica's copy of one replicated fp32 param
     (the classic silent-data-corruption fault), and the next sampled
     train step must latch a divergence event. No detection => exit 1
     (the inverted gate: green is evidence of a working sentinel, not a
     silent one).
  3. **Abort policy MUST raise** — with ``divergence_policy="abort"`` the
     same injected flip must surface as ``TrainingHealthError``.

Prints one JSON line of evidence.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

CLEAN_STEPS = 20


def _engine(policy: str = "log", sentinel_every: int = 1):
    import deepspeed_tpu

    eng, *_ = deepspeed_tpu.initialize(
        model=_model_spec(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            "numerics": {
                "enabled": True,
                "sample_every": 4,
                "sentinel_sample_every": sentinel_every,
                "divergence_policy": policy,
            },
        },
    )
    return eng


def _model_spec():
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from unit.simple_model import simple_model_spec

    return simple_model_spec()


def _batch(eng, seed):
    from unit.simple_model import random_batch

    return random_batch(eng.train_batch_size, seed=seed)


def run_smoke() -> dict:
    import jax

    from deepspeed_tpu.diagnostics.faultinject import FaultInjector
    from deepspeed_tpu.diagnostics.manager import TrainingHealthError
    from deepspeed_tpu.telemetry import numerics

    evidence: dict = {"clean": {}, "inject": {}, "abort": {}}
    gates: dict = {}

    # ---- gate 1: clean 20-step run stays quiet -------------------------
    eng = _engine()
    for s in range(CLEAN_STEPS):
        eng.train_batch(batch=_batch(eng, seed=s))
    obs = numerics.get_observatory()
    evidence["clean"] = {
        "steps": CLEAN_STEPS,
        "divergence_events": obs.divergence_events_seen,
        "checked": int(jax.device_get(eng.state.numerics.checked)),
    }
    gates["clean_quiet"] = (obs.divergence_events_seen == 0
                            and evidence["clean"]["checked"] == CLEAN_STEPS)

    # ---- gate 2: injected bit flip detected within one sampled step ----
    leaf = FaultInjector().flip_param_bit(eng)
    before = obs.divergence_events_seen
    detect_steps = -1
    for extra in range(1, 4):
        eng.train_batch(batch=_batch(eng, seed=100 + extra))
        if obs.divergence_events_seen > before:
            detect_steps = extra
            break
    evidence["inject"] = {"leaf": leaf, "detect_steps": detect_steps,
                          "sentinel_sample_every": 1}
    gates["inject_detected_within_one_sampled_step"] = detect_steps == 1

    # ---- gate 3: abort policy raises ----------------------------------
    eng2 = _engine(policy="abort")
    eng2.train_batch(batch=_batch(eng2, seed=0))
    FaultInjector().flip_param_bit(eng2)
    raised = False
    try:
        eng2.train_batch(batch=_batch(eng2, seed=1))
    except TrainingHealthError as e:
        raised = True
        evidence["abort"] = {"raised": True, "step": e.step,
                             "dump": bool(e.dump_path)}
    gates["abort_policy_raises"] = raised

    evidence["gates"] = gates
    evidence["pass"] = all(gates.values())
    return evidence


def main() -> None:
    argparse.ArgumentParser(description=__doc__).parse_args()
    evidence = run_smoke()
    print(json.dumps(evidence, sort_keys=True))
    sys.exit(0 if evidence["pass"] else 1)


if __name__ == "__main__":
    main()
