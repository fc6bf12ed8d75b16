#!/usr/bin/env python
"""Fault-injection smoke: prove the resilience stack end to end.

One run on a tiny causal LM on the CPU with BOTH headline faults injected (``diagnostics/faultinject.py``):

  - **NaN at step K** — params poisoned on device (a causal LM batch is
    integer-only, so the injection point is the model, not the data); the
    in-step health probe fires ``nonfinite`` under the ``abort`` policy and
    ``elasticity.run_resilient`` must rewind to the last-good snapshot and
    complete to the target step anyway.
  - **writer killed mid-save** — the async snapshot writer dies between two
    shard writes; the ``latest`` pointer must keep naming the previous
    durable snapshot (crash-mid-save atomicity) and training must keep going
    forward (a save failure never rewinds healthy state).

Prints one JSON line and exits 0 iff every claim held (ISSUE 6; see
docs/elastic.md).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NAN_STEP = 5
TARGET_STEPS = 8
SNAPSHOT_EVERY = 2


def main() -> int:
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.checkpoint import snapshot as snap
    from deepspeed_tpu.diagnostics import FaultInjector
    from deepspeed_tpu.elasticity import run_resilient
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    tmp = tempfile.mkdtemp(prefix="dstpu_fault_smoke_")
    cfg = TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, max_seq_len=256,
    )
    seq = 128
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=seq),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            "diagnostics": {
                "enabled": True,
                "health": {"nonfinite_policy": "abort"},
                "flight_recorder": {"dump_dir": f"{tmp}/fr",
                                    "install_signal_handlers": False,
                                    "dump_on_exception": False},
            },
            # blocking=True surfaces the injected writer crash deterministically
            # at its own boundary (as a logged save failure, never a rewind)
            "snapshot": {"enabled": True, "dir": tmp,
                         "every_n_steps": SNAPSHOT_EVERY, "blocking": True},
            "recovery": {"backoff_base_s": 0.0},
        })

    def batch_fn(step: int):
        r = np.random.default_rng(1000 + step)
        return {"input_ids": r.integers(0, cfg.vocab_size,
                                        (engine.train_batch_size, seq),
                                        dtype=np.int32)}

    fi = FaultInjector()
    # step-0 anchor BEFORE arming the writer kill: the injected crash must
    # hit a cadenced mid-run save, not the supervisor's anchor snapshot
    engine.snapshot_manager.snapshot(blocking=True)
    fi.kill_writer(engine.snapshot_manager, after_shards=1, times=1)
    rewound_to = []
    report = run_resilient(
        engine,
        fi.nan_params_fn(engine, batch_fn, at_steps=[NAN_STEP]),
        num_steps=TARGET_STEPS,
        on_rewind=lambda entry: rewound_to.append(entry["step"]),
    )

    latest = snap.latest_tag(tmp)
    checks = {
        "completed_to_target": report.steps_completed == TARGET_STEPS
                               and engine.global_steps == TARGET_STEPS,
        "nan_fired_at_k": fi.nan_steps_fired == [NAN_STEP],
        "rewound_once_below_k": report.rewinds == 1
                                and rewound_to and rewound_to[0] < NAN_STEP,
        "writer_kill_fired": fi.writer_kills_fired == 1,
        "save_failure_no_rewind": report.save_failures >= 1,
        "latest_still_loads": False,
        "flight_record_dumped": bool(report.flight_record),
    }
    try:
        atoms, _manifest = snap.load_latest_atoms(tmp, fallback=False)
        checks["latest_still_loads"] = latest is not None and bool(atoms)
    except snap.SnapshotError:
        pass

    ok = all(checks.values())
    print(json.dumps({
        "fault_smoke": "nan_inject+kill_mid_save",
        "ok": ok,
        "target_steps": TARGET_STEPS,
        "nan_step": NAN_STEP,
        "checks": checks,
        "rewind_log": report.rewind_log,
        "save_failures": report.save_failures,
        "latest": latest,
        "injections": fi.summary(),
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
