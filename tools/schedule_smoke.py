#!/usr/bin/env python
"""Collective schedule compiler + fused GEMM smoke, exit-gated (ISSUE 19).

The proof that the GC3/T3 stack holds its contracts:

  1. **Compiled programs MUST execute bit-identically** — the synthesized
     hop programs (``algorithm="compiled[:sig]"``) round-trip through the
     facade onto the CPU mesh and match ``jax.lax`` exactly on exact
     wires, on a 1D world-8 ring AND a (4,2) two-axis mesh (the sub-ring
     factorization path).
  2. **Compiled MUST be >= parity with the best hand-written pick under
     the calibrated model** — at the representative query (int8 1 MB
     all_reduce, world 30) both sides are costed by THE selector's own
     refit-calibrated :class:`CostModel`; ``pred_ratio`` > 1 means the
     search started losing to its own baseline. Under the alpha-dominant
     refit the compiled [2,3,5] program must strictly WIN (14 hops vs
     ring2d's 18 / bidir's 58) and the selector must route to it.
  3. **A refit MUST be able to flip the pick** — recalibrating the SAME
     model to beta-dominant constants flips the SAME query to ``bidir``
     (half per-link wire beats single-direction sub-rings). The cost
     model the compiler consumed is observably the live calibrated
     object, not a frozen copy.
  4. **Fused ZeRO-3 trajectory MUST track unfused** — a multi-step SGD
     loop through ``zeropp.sharded_matmul`` (fused all-gather+matmul
     forward, fused matmul+reduce-scatter backward, batch-sharded x)
     must keep its loss trajectory within tolerance of the config-off
     lax composition over every step.

Prints one JSON line of evidence.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

TRAJ_STEPS = 10
TRAJ_RTOL = 1e-4


def _gate_compiled_bit_identity(evidence: dict, gates: dict) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.collectives import algorithms
    from deepspeed_tpu.utils.compat import shard_map

    devs = np.array(jax.devices()[:8])
    rng = np.random.default_rng(0)
    checks: dict = {}

    # 1D world-8 ring: searched program + a forced deep factorization,
    # including a non-divisible payload (L=333 exercises the pad path).
    mesh1 = Mesh(devs, ("dp",))

    def run1(f, x, outs):
        return jax.jit(shard_map(f, mesh=mesh1, in_specs=P("dp"),
                                 out_specs=outs, check_vma=False))(x)

    for L in (1000, 333):
        x = jnp.asarray(rng.integers(-8, 8, size=(8 * L,)).astype(np.float32))
        for alg in ("compiled", "compiled:dp*2.none/dp*2.none/dp*2.none"):
            got = run1(lambda v, a=alg: algorithms.all_reduce(
                v, "dp", algorithm=a), x, P("dp"))
            want = run1(lambda v: jax.lax.psum(v, "dp"), x, P("dp"))
            checks[f"ar_1d_L{L}_{alg}"] = bool(
                (np.asarray(got) == np.asarray(want)).all())

    # (4,2) two-axis mesh: the sub-ring factorization path (tuple axes).
    mesh2 = Mesh(devs.reshape(4, 2), ("a", "b"))

    def run2(f, x, outs):
        return jax.jit(shard_map(f, mesh=mesh2, in_specs=P(("a", "b")),
                                 out_specs=outs, check_vma=False))(x)

    x = jnp.asarray(rng.integers(-8, 8, size=(8 * 96,)).astype(np.float32))
    got = run2(lambda v: algorithms.all_reduce(
        v, ("a", "b"), algorithm="compiled"), x, P(("a", "b")))
    want = run2(lambda v: jax.lax.psum(v, ("a", "b")), x, P(("a", "b")))
    checks["ar_2d_compiled"] = bool(
        (np.asarray(got) == np.asarray(want)).all())

    got = run2(lambda v: algorithms.all_gather(
        v, ("a", "b"), algorithm="compiled:b*2.none/a*4.none"), x, P())
    want = run2(lambda v: jax.lax.all_gather(
        v, ("a", "b"), tiled=True), x, P())
    checks["ag_2d_compiled"] = bool(
        (np.asarray(got) == np.asarray(want)).all())

    got = run2(lambda v: algorithms.reduce_scatter(
        v, ("a", "b"), algorithm="compiled:b*2.none/a*4.none"),
        x, P(("a", "b")))
    want = run2(lambda v: jax.lax.psum_scatter(
        v, ("a", "b"), tiled=True), x, P(("a", "b")))
    checks["rs_2d_compiled"] = bool(
        (np.asarray(got) == np.asarray(want)).all())

    evidence["bit_identity"] = checks
    gates["compiled_bit_identical_vs_lax"] = all(checks.values())


def _gate_parity_and_refit(evidence: dict, gates: dict) -> None:
    from deepspeed_tpu.collectives import schedule, selector
    from deepspeed_tpu.collectives.algorithms import ALGORITHMS

    op, nbytes, codec, world = "all_reduce", 1 << 20, "int8", 30
    axes_sig = (("dp", world),)
    try:
        selector.configure(compiled_search=True, codecs=(codec,))

        # alpha-dominant refit: hop count decides; compiled [2,3,5]
        # (14 hops) must beat every hand algorithm at world 30.
        selector.calibrate("ppermute", 10.0, 0.1)
        cm = selector.cost_model()
        hand = min(
            selector.estimate_us(op, alg, codec, nbytes, world)
            for alg in ALGORITHMS
            if not (alg == "rhd" and (world & (world - 1))))
        sched = schedule.compile_schedule(op, axes_sig, nbytes, codec, cm=cm)
        pred_ratio = sched.est_us / hand if hand > 0 else 1.0
        pick = selector.select(op, nbytes, world, codec=codec,
                               axes_sig=axes_sig)
        evidence["parity"] = {
            "world": world, "codec": codec, "nbytes": nbytes,
            "compiled_signature": sched.signature,
            "compiled_pred_us": round(sched.est_us, 4),
            "hand_pred_us": round(hand, 4),
            "pred_ratio": round(pred_ratio, 6),
            "selector_pick": pick.algorithm,
        }
        gates["compiled_parity_with_hand"] = pred_ratio <= 1.0 + 1e-9
        gates["selector_routes_to_compiled"] = (
            pick.algorithm.startswith("compiled:"))

        # beta-dominant refit of the SAME model object flips the SAME
        # query to the hand-written bidir pick.
        selector.calibrate("ppermute", 0.01, 100.0)
        flipped = selector.select(op, nbytes, world, codec=codec,
                                  axes_sig=axes_sig)
        evidence["refit"] = {"flipped_pick": flipped.algorithm,
                             "same_model": cm is selector.cost_model()}
        gates["refit_flips_pick"] = (flipped.algorithm == "bidir"
                                     and cm is selector.cost_model())
    finally:
        # configure() rebuilds the model around default constants — the
        # refits above don't leak into the fused-trajectory gate
        selector.configure()


def _gate_fused_trajectory(evidence: dict, gates: dict) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.collectives import fused_gemm
    from deepspeed_tpu.parallel import zeropp
    from deepspeed_tpu.utils.compat import shard_map

    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("fsdp",))
    Mb, Ks, N = 8, 8, 16
    K = n * Ks
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(n * Mb, K)).astype(np.float32))
    w0 = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32) * 0.1)
    t = jnp.asarray(rng.normal(size=(n * Mb, N)).astype(np.float32))
    lr = 1e-3

    def sgd_step(xv, wv, tv):
        # ZeRO-3 shape: batch-sharded x, parameter shard wv; the fused
        # forward gathers w on the fly, the fused backward reduce-scatters
        # dw so each rank updates only its own shard.
        def loss(a, b):
            y = zeropp.sharded_matmul(a, b, "fsdp", False, 64)
            return jnp.sum((y - tv) * (y - tv))

        lval, dw = jax.value_and_grad(loss, argnums=1)(xv, wv)
        return wv - lr * dw, jnp.reshape(lval, (1,))

    def trajectory(fused):
        fused_gemm.configure(enabled=fused)
        f = jax.jit(shard_map(
            sgd_step, mesh=mesh,
            in_specs=(P("fsdp"), P("fsdp"), P("fsdp")),
            out_specs=(P("fsdp"), P("fsdp")), check_vma=False))
        w, losses = w0, []
        np.asarray(f(x, w, t)[0])  # compile off the clock
        t0 = time.perf_counter()
        for _ in range(TRAJ_STEPS):
            w, lv = f(x, w, t)
            losses.append(float(np.asarray(lv).sum()))
        wall = time.perf_counter() - t0
        return np.asarray(losses), np.asarray(w), wall

    try:
        l_unfused, w_unfused, t_unfused = trajectory(False)
        l_fused, w_fused, t_fused = trajectory(True)
    finally:
        fused_gemm.configure(enabled=False)

    rel = np.abs(l_fused - l_unfused) / (np.abs(l_unfused) + 1e-12)
    w_rel = float(np.abs(w_fused - w_unfused).max()
                  / (np.abs(w_unfused).max() + 1e-12))
    step_ratio = t_fused / t_unfused if t_unfused > 0 else 1.0
    evidence["fused_traj"] = {
        "steps": TRAJ_STEPS, "world": n, "rtol": TRAJ_RTOL,
        "loss_first": round(float(l_unfused[0]), 6),
        "loss_last_unfused": round(float(l_unfused[-1]), 6),
        "loss_last_fused": round(float(l_fused[-1]), 6),
        "max_loss_rel_err": float(rel.max()),
        "final_w_rel_err": w_rel,
        "loss_decreased": bool(l_unfused[-1] < l_unfused[0]),
        "step_time_ratio": round(step_ratio, 4),
    }
    gates["fused_traj_within_tolerance"] = bool(
        rel.max() < TRAJ_RTOL and w_rel < TRAJ_RTOL
        and l_unfused[-1] < l_unfused[0])


def run_smoke() -> dict:
    evidence: dict = {}
    gates: dict = {}
    _gate_compiled_bit_identity(evidence, gates)
    _gate_parity_and_refit(evidence, gates)
    _gate_fused_trajectory(evidence, gates)
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
