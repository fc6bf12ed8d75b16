#!/usr/bin/env python3
"""The planted faults that a routed, latent-attention cell's ``check`` has to
refuse, run through ``benchmarks/run.py`` itself on the chip: the readings
behind ``check.readings.*.control_min`` of ``benchmarks/configs/glm-4.7-flash.json``.

    python3 tools/routed_controls.py --control e4m3_latent|e4m3_ffn|no_bias|ranks_2_to_k1 \\
        --workload glm-4.7-flash.serve.batch --seed <n> --seconds 5 --trace 0

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.

- ``e4m3_latent`` (the logits' control, the nearest precision below bf16 for
  what the latent pool holds): every layer's ``wkv_a`` is rounded through
  float8_e4m3fn and back (one scale a stacked leaf) before the engine is
  built, so the cached latents and rotary keys carry e4m3's error; the
  reference is given the matrices as they were. The engine itself refuses a
  quantized latent pool, so the fault cannot be planted inside it.
- ``e4m3_ffn`` (a second one): the matrices of the leading dense layers' MLP
  and of every routed layer's SHARED expert likewise. A subset of the
  feed-forward weights: the originals of all 64 experts a layer (8.5 GB) do
  not fit beside the engine, these 258 MB do.
- ``no_bias``: the router picks by the scores alone (no correction bias).
- ``ranks_2_to_k1``: the router takes ranks 2..k+1 of ``s + b`` for 1..k.

The last line is ``run.py``'s: ``correct`` has to read false, by
``logit_rel_err`` for the first two and by ``route_shortfall`` for the routers.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def through_e4m3(w):
    """On the host, through ml_dtypes: inside a jitted program XLA may keep
    the excess precision and fold the round trip away (it did, PR 33)."""
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    x = np.asarray(w.astype(jnp.float32))
    scale = np.abs(x).max() / 448.0
    return jnp.asarray((x / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale, dtype=w.dtype)


E4M3_LEAVES = {
    "e4m3_latent": lambda path: "'wkv_a'" in path,
    "e4m3_ffn": lambda path: "'kernel'" in path and ("'mlp'" in path or "'shared'" in path),
}


def plant_e4m3(rounded):
    """The leaves ``rounded(path)`` picks go into the engine through e4m3;
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
                        if rounded(keystr(path)))
            print("through e4m3:", " ".join(kept), flush=True)
            return jax.tree_util.tree_map_with_path(
                lambda path, leaf: through_e4m3(leaf) if keystr(path) in kept else leaf, params)

        module.make_weights = make_weights
        return module

    def architecture(name, *args):
        module = load_architecture(name, *args)
        relabel = module.reference_weights
        module.reference_weights = lambda params: relabel(jax.tree_util.tree_map_with_path(
            lambda path, leaf: kept.get(keystr(path), leaf), params))
        return module

    harness.load_runner, harness.load_architecture = runner, architecture


def plant_router(control):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.parallel import moe

    honest = moe.route

    def route(logits, top_k, *, kind="softmax", bias=None, renormalize=True, scale=1.0):
        if control == "no_bias":
            return honest(logits, top_k, kind=kind, bias=None, renormalize=renormalize, scale=scale)
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        picks = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k + 1)[1][:, 1:]
        weights = jnp.take_along_axis(scores, picks, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return weights * scale, picks.astype(jnp.int32)

    moe.route = route


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=(*E4M3_LEAVES, "no_bias", "ranks_2_to_k1"))
    args, rest = ap.parse_known_args()
    if args.control in E4M3_LEAVES:
        plant_e4m3(E4M3_LEAVES[args.control])
    else:
        plant_router(args.control)
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
