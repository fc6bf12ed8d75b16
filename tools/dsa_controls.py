#!/usr/bin/env python3
"""The planted faults that an indexed latent-attention cell's ``check`` has to
refuse, run through ``benchmarks/run.py`` itself on the chip (the readings
behind ``check.readings.*.control_min`` of ``benchmarks/configs/glm-5.json``),
and the comparison PINNED to the program's own selection, which ``check``
cannot make.

    python3 tools/dsa_controls.py --control recent|no_relu|e4m3_latent|e4m3_pool \\
        --workload glm-5.serve.long-prompt-wave8 --seed <n> --seconds 5 --trace 0
    python3 tools/dsa_controls.py --control pinned --workload glm-5.serve.long-prompt-wave8 --seed <n>

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.
The reference always selects by its own float32 scores.

- ``recent``: the program keeps the most recent ``index_topk`` tokens of a
  query where its indexer would choose (``ops/dsa.py::select_mask`` and
  ``select_positions`` wrapped: a sliding window, the cheapest selection that
  is not the model's).
- ``no_relu``: the index scores without their ``relu`` (``I[t, s] = sum_j w_j
  qI_j . kI_s``; ``ops/dsa.py::index_scores`` replaced by a plain XLA form
  without it, for the kernel and the XLA form alike).
- ``e4m3_latent`` (the nearest precision below bf16 for what the page pool
  holds): ``tools/routed_controls.py``'s, every layer's ``wkv_a`` through
  float8_e4m3fn on the host, the reference given the matrices as they were.
- ``e4m3_pool``: BOTH things a page holds of a token through e4m3, the latent
  (``wkv_a``) and the index key (``idx_wk``, which the published inference code
  keeps in fp8): the index key's rounding moves the SELECTION, which a
  comparison that selects by its own scores can see where the latent's alone
  hides under the flips that bf16 itself makes.

The routers' controls (``no_bias``, ``ranks_2_to_k1``: the readings behind
``route_shortfall_tol``) are ``tools/routed_controls.py``'s own, given this
cell's ``--workload``. The last line is ``run.py``'s: ``correct`` has to read
false.

``--control pinned`` builds the cell's engine as the runner does, feeds
``--prompts`` prompts of the check's lengths through ``put`` and two further
tokens through the cache, takes the program's own picks AND its own selection
(``benchmarks/architectures/glm_moe_dsa.py::put_with_selected``: every query's
kept positions in every layer) and prints one JSON line: the logits' relative
error against the reference PINNED there (``forward(..., picks, selected)``),
against the reference at the picks alone (what ``check`` compares: it selects
by its own scores), ``select_shortfall`` (how far the worst kept token lies
under the reference's own ``index_topk``-th score, in sigmas of the query's
scores) and the share of (query, layer) pairs where it is over 0: a fault in
the indexer reads sigmas, a flip at the boundary thousandths of one.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def plant_recent():
    import jax.numpy as jnp

    from deepspeed_tpu.ops import dsa

    positions = dsa.select_positions

    def by_position(scores):
        return jnp.where(scores > -jnp.inf, jnp.arange(scores.shape[-1], dtype=jnp.float32), -jnp.inf)

    def select_mask(scores, topk, dtype=jnp.bool_, q_positions=None):
        candidate = scores > -jnp.inf
        seen = candidate.sum(-1, keepdims=True)
        return (candidate & (jnp.arange(scores.shape[-1]) >= seen - topk)).astype(dtype)

    dsa.select_mask = select_mask
    dsa.select_positions = lambda scores, topk: positions(by_position(scores), topk)


def plant_no_relu():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import dsa

    def index_scores(q, k, w, q_positions, impl="auto"):
        N, C, H, D = q.shape

        def tile(args):
            q, w, pos = args
            scores = (w[..., None] * jnp.einsum("nchd,nsd->nchs", q, k, preferred_element_type=jnp.float32)).sum(2)
            return jnp.where(jnp.arange(k.shape[1])[None, None, :] <= pos[:, :, None], scores, -jnp.inf)

        c = 128
        if C <= c or C % c:
            return tile((q, w, q_positions))
        tiles = (q.reshape(N, C // c, c, H, D), w.reshape(N, C // c, c, H), q_positions.reshape(N, C // c, c))
        out = jax.lax.map(tile, tuple(jnp.moveaxis(a, 1, 0) for a in tiles))
        return jnp.moveaxis(out, 0, 1).reshape(N, C, -1)

    dsa.index_scores = index_scores


def pinned(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import harness, program
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.topology.mesh import build_mesh

    workload = harness.load_workload(args.workload)
    config = harness.load_config(workload["config"])
    arch, reference = harness.load_architecture(config["architecture"]), harness.load_reference(config["architecture"])
    runner = harness.load_runner(workload["kind"])
    devices = jax.devices()[:1] if args.cpu else harness.require_devices(1)
    harness.enable_compile_cache()
    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    model_cfg = program.model_config(config, dtype)
    params = runner.make_weights(model_cfg, args.seed)
    if args.cpu:  # a rehearsal at a toy size: float32 throughout
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    engine = InferenceEngineV2(model_cfg, params, dict(workload["engine"]),
                               mesh=build_mesh(devices=devices, axis_sizes={"tp": 1, "dp": 1}))
    del params
    cfg = program.published(config)
    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, 7])
    bucket, n, steps = engine.config.chunk_bucket, args.prompts, 2
    lens = rng.integers(bucket // 2, bucket - steps, n)
    seqs = rng.integers(0, config["vocab_size"], (n, bucket), dtype=np.int32)
    layers, routed, k = arch.layers(cfg), arch.routed_layers(cfg), arch.experts_per_token(cfg)
    words = -(-bucket // 32)
    picks = np.broadcast_to(np.arange(k, dtype=np.int32), (n, bucket, routed, k)).copy()
    selected = np.zeros((n, bucket, layers, words), np.int32)
    uids, got = list(range(20_000, 20_000 + n)), []
    for step in range(steps + 1):
        starts = [0 if step == 0 else lens[i] + step - 1 for i in range(n)]
        fed = [seqs[i, starts[i]:lens[i] + step] for i in range(n)]
        logits, p, s = arch.put_with_selected(engine, uids, fed)
        if s is None:
            raise SystemExit("the engine's block table holds no more tokens than a query keeps: nothing is selected")
        for i in range(n):
            picks[i, starts[i]:starts[i] + len(fed[i])] = p[i]
            selected[i, starts[i]:starts[i] + len(fed[i])] = s[i][..., :words]
        got.append(np.asarray(logits, np.float32))
    weights = arch.reference_weights(engine.params)
    # two programs, one after the other: the two passes in one do not fit beside the engine
    run = jax.jit(lambda w, t, p, s: (reference.forward(w, cfg, t, p, s), reference.select_shortfall(w, cfg, t, p, s)))
    at_picks = jax.jit(lambda w, t, p: (reference.forward(w, cfg, t, p), reference.route_shortfall(w, cfg, t, p)))
    want, short = (np.asarray(a) for a in run(weights, jnp.asarray(seqs), jnp.asarray(picks), jnp.asarray(selected)))
    unpinned, route = (np.asarray(a) for a in at_picks(weights, jnp.asarray(seqs), jnp.asarray(picks)))
    fed_to = np.arange(bucket)[None, :] < (lens + steps)[:, None]

    def errs(ref):
        return [program.relative_error(got[step], np.stack([ref[i, lens[i] + step - 1] for i in range(n)]))
                for step in range(steps + 1)]

    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "prompt_lens": [int(x) for x in lens],
        "logit_rel_err_pinned_to_the_selection": errs(want), "logit_rel_err_at_the_picks_alone": errs(unpinned),
        "select_shortfall_max": float(short[fed_to].max()),
        "select_flip_share": float((short[fed_to] > 0).mean()),
        "select_shortfall_by_layer": [float(short[fed_to][:, layer].max()) for layer in range(layers)],
        "route_shortfall_max": float(route[fed_to].max()),
        "device": jax.devices()[0].device_kind}), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=("recent", "no_relu", "e4m3_latent", "e4m3_pool", "pinned"))
    args, rest = ap.parse_known_args()
    if args.control == "pinned":
        ap.add_argument("--workload", required=True)
        ap.add_argument("--seed", type=int, default=0)
        ap.add_argument("--prompts", type=int, default=2)
        ap.add_argument("--cpu", action="store_true", help="a rehearsal off the chip, at a toy size")
        return pinned(ap.parse_args())
    if args.control in ("e4m3_latent", "e4m3_pool"):
        import routed_controls

        leaves = ("'wkv_a'",) if args.control == "e4m3_latent" else ("'wkv_a'", "'idx_wk'")
        routed_controls.plant_e4m3(lambda path: any(leaf in path for leaf in leaves))
    else:
        {"recent": plant_recent, "no_relu": plant_no_relu}[args.control]()
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
