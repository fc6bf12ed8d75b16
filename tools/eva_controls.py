#!/usr/bin/env python3
"""The planted faults that an EVA cell's ``check`` has to refuse, run through
``benchmarks/run.py`` itself on the chip: the readings behind
``check.readings.logit_rel_tol.control_min`` of ``benchmarks/configs/evabyte.json``.

    python3 tools/eva_controls.py --control e4m3_kv|mean_pooling|no_mu|own_chunks_visible \\
        --workload evabyte.serve.long-batch --seed <n> --seconds 5 --trace 0
    python3 tools/eva_controls.py --control boundary --workload evabyte.serve.long-batch --seed <n>

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.

- ``e4m3_kv`` (the nearest precision below bf16 for what the pool holds):
  every layer's ``wk`` and ``wv`` are rounded through float8_e4m3fn and back
  (one scale a stacked leaf) before the engine is built, so the cached rows and
  the summaries pooled from them carry e4m3's error; the reference is given
  the matrices as they were. The engine refuses a quantized pool of summaries,
  so the fault cannot be planted inside it.
- ``mean_pooling``: ``phi`` is zero in the program's copy alone, so a chunk's
  summary is the mean of its rows.
- ``no_mu``: ``mu`` is zero in the program's copy alone.
- ``own_chunks_visible``: a prompt's queries see, beside what they should, the
  summaries of the whole chunks of their OWN window that lie before them
  (``ops/eva.py::_summaries_seen`` wrapped).

The last line is ``run.py``'s: ``correct`` has to read false, by ``logit_rel_err``.

``boundary`` is no fault but the comparison the runner's check makes only by
chance: four rows whose next tokens cross a window boundary, fed through
``put`` (a prompt, then eight single tokens, the closing at steps 3, 0, 5 and
7) and through ``generate`` (ten tokens over the same boundaries, closings
inside a decode chain), against the reference; it prints one JSON line.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BOUNDARY_LENS = (3 * 2048 - 4, 3 * 2048 - 1, 2 * 2048 - 6, 4 * 2048 - 8)
BOUNDARY_STEPS = 8


def zeroed(w):
    import jax.numpy as jnp

    return jnp.zeros_like(w)


def through_e4m3(w):
    from tools.routed_controls import through_e4m3 as on_the_host  # its reasons are there

    return on_the_host(w)


LEAF_CONTROLS = {
    "e4m3_kv": (lambda path: "'wk'" in path or "'wv'" in path, through_e4m3),
    "mean_pooling": (lambda path: "'phi'" in path, zeroed),
    "no_mu": (lambda path: "'mu'" in path, zeroed),
}


def plant_leaves(picked, changed):
    """The leaves ``picked(path)`` names go into the engine as ``changed(leaf)``;
    the reference's relabelling is given the tree with them as they were."""
    import jax

    from benchmarks.lib import harness

    kept = {}
    keystr = jax.tree_util.keystr
    load_runner, load_architecture = harness.load_runner, harness.load_architecture

    def runner(kind, *args):
        module = load_runner(kind, *args)
        make = module.make_weights

        def make_weights(model_cfg, seed):
            params = make(model_cfg, seed)
            kept.update((keystr(path), leaf) for path, leaf in jax.tree_util.tree_leaves_with_path(params)
                        if picked(keystr(path)))
            print("changed in the program's copy:", " ".join(kept), flush=True)
            return jax.tree_util.tree_map_with_path(
                lambda path, leaf: changed(leaf) if keystr(path) in kept else leaf, params)

        module.make_weights = make_weights
        return module

    def architecture(name, *args):
        module = load_architecture(name, *args)
        relabel = module.reference_weights
        module.reference_weights = lambda params: relabel(jax.tree_util.tree_map_with_path(
            lambda path, leaf: kept.get(keystr(path), leaf), params))
        return module

    harness.load_runner, harness.load_architecture = runner, architecture


def plant_own_chunks():
    """``ops/eva.py::_summaries_seen`` with one more set of keys a window: the
    summaries of its own whole chunks that end before the query."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops import eva

    honest = eva._summaries_seen

    def faulty(q, ks, vs, w, per):
        chunk = q.shape[1] // per
        ended = (jnp.arange(per)[None, :] + 1) * chunk <= jnp.arange(q.shape[1])[:, None]
        own = eva._attend(q, ks[:, w * per:(w + 1) * per], vs[:, w * per:(w + 1) * per], keep=ended)
        return honest(q, ks, vs, w, per) + [own]

    eva._summaries_seen = faulty


def boundary(workload_name: str, seed: int) -> int:
    """Rows that cross a window boundary, token by token and inside a chain."""
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
    forward = jax.jit(lambda w, t: reference.forward(w, cfg, t))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 11])
    lens, steps, generated = BOUNDARY_LENS, BOUNDARY_STEPS, 10
    seqs = rng.integers(0, config["vocab_size"], (len(lens), max(lens) + generated + 1), dtype=np.int32)
    uids = list(range(len(lens)))
    got = [engine.put(uids, [seqs[i, :n] for i, n in enumerate(lens)])]
    got += [engine.put(uids, [seqs[i, n + s:n + s + 1] for i, n in enumerate(lens)]) for s in range(steps)]
    closed_by_put = engine.windows_closed
    for uid in uids:
        engine.flush(uid)
    want = np.asarray(forward(weights, jnp.asarray(seqs)))
    errs = [program.relative_error(g, np.stack([want[i, n + s - 1] for i, n in enumerate(lens)]))
            for s, g in enumerate(got)]
    prompts = [seqs[i, :n] for i, n in enumerate(lens)]
    outs = engine.generate(prompts, max_new_tokens=generated)
    full = seqs.copy()
    for i, (p, o) in enumerate(zip(prompts, outs)):
        full[i, len(p):len(p) + len(o)] = o
    want = np.asarray(forward(weights, jnp.asarray(full)))
    gap = max(float((want[i, len(p) + j - 1].max() - want[i, len(p) + j - 1][tok])
                    / np.sqrt(np.mean(want[i, len(p) + j - 1] ** 2)))
              for i, (p, o) in enumerate(zip(prompts, outs)) for j, tok in enumerate(o))
    tol = program.tolerance(config, "logit_rel_tol")
    print(json.dumps({"boundary_logit_rel_err": errs, "tol": tol, "generated_token_gap": gap,
                      "gap_tol": runner.TOKEN_GAP_PER_LOGIT_TOL * tol, "lens": list(lens),
                      "windows_closed_by_put": closed_by_put, "windows_closed": engine.windows_closed,
                      "ok": bool(max(errs) <= tol and gap <= runner.TOKEN_GAP_PER_LOGIT_TOL * tol),
                      "device": devices[0].device_kind}), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=(*LEAF_CONTROLS, "own_chunks_visible", "boundary"))
    args, rest = ap.parse_known_args()
    if args.control == "boundary":
        bp = argparse.ArgumentParser()
        bp.add_argument("--workload", required=True)
        bp.add_argument("--seed", type=int, default=0)
        b, _ = bp.parse_known_args(rest)
        return boundary(b.workload, b.seed)
    if args.control in LEAF_CONTROLS:
        plant_leaves(*LEAF_CONTROLS[args.control])
    else:
        plant_own_chunks()
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
