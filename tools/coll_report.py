#!/usr/bin/env python
"""Collective observatory report (collectives/observatory.py).

Two uses:

  - **library**: ``render_report()`` formats whatever the process-global
    observatory + selector hold — the measured-vs-model latency curves per
    hop backend, the calibrated alpha/beta constants, drift counters, and a
    staleness check on the persisted decision table.
  - **CLI**: run standalone it forces an 8-device CPU mesh,
    routes the four algorithmic collectives (all_to_all included) through the comm facade,
    drains the observatory's probe queue (real timed hop-scope dispatches),
    refits alpha/beta, injects one deliberately slow sample to prove the
    drift alarm arms, and persists the online table — proving that the
    selector's feedback loop closes end to end.

Exit 0 iff probes ran for every op, the table holds at least two algorithm
families per op, the refit produced finite constants the selector consumes,
the injected slow sample fired the drift alarm (without poisoning the
table), and the persisted table round-trips through the versioned loader.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def render_report(max_table_age_hours: Optional[float] = None,
                  table: Optional[str] = None) -> str:
    """Text report of the process-global observatory + selector state;
    ``table`` overrides which file the staleness section inspects (the CLI
    passes ``--table`` so the printed verdict and the exit gate agree)."""
    from deepspeed_tpu.collectives import observatory, selector

    obs = observatory.get_observatory()
    cfg = selector.get_config()
    rows = obs.table_rows()
    lines = ["# collective observatory report", ""]

    s = obs.summary()
    lines.append(f"routes={s['routes']} probes_merged={s['merged_samples']} "
                 f"table_rows={s['table_rows']} drift_events={s['drift_events']}")
    lines.append("")

    lines.append("## calibrated cost model (alpha us/hop, beta us/MB)")
    if not s["calibration"]:
        lines.append("  (no refit ran)")
    for backend, (a, b) in sorted(s["calibration"].items()):
        bw = 1e3 / b if b > 0 else float("inf")
        lines.append(f"  {backend:<10} alpha={a:10.3f}  beta={b:10.3f}"
                     f"  (~{bw:.2f} GB/s effective)")
    lines.append("")

    lines.append("## measured vs model, per backend")
    hdr = (f"  {'op':<15} {'alg':<12} {'codec':<6} {'backend':<9} "
           f"{'world':>5} {'size_mb':>8} {'meas_ms':>9} {'model_ms':>9} {'ratio':>7}")
    lines.append(hdr)
    for r in sorted(rows, key=lambda r: (r.get("backend", ""), r["op"],
                                         float(r["size_mb"]), r["algorithm"])):
        nbytes = int(float(r["size_mb"]) * 1e6)
        try:
            model_ms = selector.estimate_us(
                r["op"], r["algorithm"], r.get("codec", "none"), nbytes,
                int(r["world"]), cfg, int(r.get("itemsize", 4))) / 1e3
        except ValueError:
            model_ms = float("nan")
        meas = float(r["latency_ms"])
        ratio = meas / model_ms if model_ms > 0 else float("nan")
        lines.append(f"  {r['op']:<15} {r['algorithm']:<12} "
                     f"{r.get('codec', 'none'):<6} {r.get('backend', '?'):<9} "
                     f"{int(r['world']):>5} {float(r['size_mb']):>8.4f} "
                     f"{meas:>9.4f} {model_ms:>9.4f} {ratio:>7.2f}")
    lines.append("")

    path = table or obs.table_path()
    if os.path.exists(path):
        age_h = (time.time() - os.path.getmtime(path)) / 3600.0
        stale = (max_table_age_hours is not None
                 and age_h > max_table_age_hours)
        lines.append(f"## table: {path} age={age_h:.2f}h"
                     + (f"  ** STALE (> {max_table_age_hours}h): re-sweep or "
                        "re-run with the observatory enabled **" if stale
                        else ""))
    else:
        lines.append(f"## table: {path} (not persisted yet)")
    return "\n".join(lines)


def table_age_hours(path: str) -> Optional[float]:
    if not os.path.exists(path):
        return None
    return (time.time() - os.path.getmtime(path)) / 3600.0


def _drive_probes(table_path: str, rounds: int) -> dict:
    """Route the four algorithmic ops on an 8-device CPU mesh, drain the
    observatory probe queue, refit, and fire the injected-drift check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import deepspeed_tpu.comm as dist
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.collectives import observatory, selector, table
    from deepspeed_tpu.utils.compat import shard_map

    telemetry.configure(enabled=True)
    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    obs = observatory.configure(
        enabled=True, sample_every=1, persist=True, table_path=table_path,
        refit_every=4, drift_ratio=3.0)
    obs.install(mesh=mesh)

    def route(fn, out_specs):
        f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("dp"),
                              out_specs=out_specs, check_vma=False))
        # flat payload, local length divisible by the world (reduce_scatter)
        f(jnp.ones((8 * 4096,), jnp.float32)).block_until_ready()

    route(lambda v: dist.all_reduce(v, "dp", algorithm="ring", codec="int8",
                                    block_size=64), P("dp"))
    route(lambda v: dist.all_gather(v, "dp", algorithm="ring", codec="none"),
          P("dp"))
    route(lambda v: dist.reduce_scatter(v, "dp", algorithm="ring",
                                        codec="none"), P("dp"))
    # all_to_all (ISSUE 15): the MoE dispatch wire enters the same feedback
    # loop — quantized ring route + a second family via the probe queue
    route(lambda v: dist.all_to_all(v, "dp", split_axis=0, concat_axis=0,
                                    algorithm="ring", codec="int8",
                                    block_size=64), P("dp"))

    step = 0
    for _ in range(rounds):
        # sample_now drains the PENDING queue (bounded); a subsequent
        # on_step refills it for the next round (the queue re-arms itself so
        # steady state keeps re-measuring — an unbounded `while ran` here
        # would spin forever)
        obs.sample_now()
        step += 1
        obs.on_step(step)
    obs.refit()

    # injected slow sample: 100x a routed row's measured latency must trip
    # the drift alarm — WITHOUT merging into the table (merge=False)
    drift_before = obs.drift_events
    rows = obs.table_rows()
    routed = next((r for r in rows if r["algorithm"] == "ring"
                   and r["op"] == "all_reduce"), None)
    pre_latency = float(routed["latency_ms"]) if routed else None
    if routed is not None:
        obs.record_sample(
            op=routed["op"], algorithm=routed["algorithm"],
            codec=routed["codec"], backend=routed["backend"],
            world=routed["world"], size_mb=float(routed["size_mb"]),
            latency_ms=float(routed["latency_ms"]) * 100.0,
            itemsize=int(routed.get("itemsize", 4)),
            check_drift=True, merge=False)
    drift_fired = obs.drift_events > drift_before

    persisted = obs.persist()
    loaded = table.load_table(persisted) if persisted else []
    post = next((r for r in loaded
                 if table.row_key(r) == table.row_key(routed)), None
                ) if routed else None
    drift_clean = (post is not None and pre_latency is not None
                   and float(post["latency_ms"]) == pre_latency)
    per_op_algs = {}
    for r in obs.table_rows():
        per_op_algs.setdefault(r["op"], set()).add(r["algorithm"])
    calib = dict(obs.calibration)
    return {
        "probes_per_op": {op: len(a) for op, a in per_op_algs.items()},
        "ops_probed": sorted(per_op_algs),
        "multi_algorithm_coverage": all(len(a) >= 2 for a in per_op_algs.values()),
        "refit_finite": bool(calib) and all(
            all(abs(v) < float("inf") for v in ab) for ab in calib.values()),
        "selector_calibrated": bool(selector.get_config().backend_ab),
        "drift_fired": drift_fired,
        # the injected (merge=False) slow sample must NOT have moved the
        # persisted routed row — the alarm path never poisons the table
        "drift_kept_out_of_table": drift_clean,
        "table_roundtrip_rows": len(loaded),
    }


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--table", default=os.path.join(
        "telemetry_out", "coll_table.json"))
    ap.add_argument("--rounds", type=int, default=1,
                    help="full probe-queue drains to run")
    ap.add_argument("--max-table-age-hours", type=float, default=None,
                    help="flag (and gate on) a persisted table older than this")
    ap.add_argument("--no-probe", action="store_true",
                    help="report only what the process already observed")
    args = ap.parse_args(argv)

    if not args.no_probe:
        # 8 virtual CPU devices BEFORE jax initializes (the probe mesh)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

    gates = {}
    if not args.no_probe:
        gates = _drive_probes(args.table, args.rounds)

    print(render_report(args.max_table_age_hours, table=args.table), flush=True)

    if args.no_probe:
        age = table_age_hours(args.table)
        stale = (args.max_table_age_hours is not None and age is not None
                 and age > args.max_table_age_hours)
        return 1 if stale else 0

    ok = {
        "ops_probed": set(gates.get("ops_probed", ())) == {
            "all_reduce", "all_gather", "reduce_scatter", "all_to_all"},
        "multi_algorithm_coverage": gates.get("multi_algorithm_coverage", False),
        "refit_finite": gates.get("refit_finite", False),
        "selector_calibrated": gates.get("selector_calibrated", False),
        "drift_fired": gates.get("drift_fired", False),
        "drift_kept_out_of_table": gates.get("drift_kept_out_of_table", False),
        "table_roundtrip": gates.get("table_roundtrip_rows", 0) > 0,
    }
    print(json.dumps({"coll_report": {**gates, **{f"ok_{k}": v for k, v in ok.items()}},
                      "ok": all(ok.values())}), flush=True)
    return 0 if all(ok.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
