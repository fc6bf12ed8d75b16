#!/usr/bin/env python
"""Serving-fabric smoke: REAL replica-daemon processes, exit-gated.

The multi-process proof of ISSUE 18's cross-process serving fabric; its
``--smoke`` subset is run by the tier-1 integration test
(``tests/unit/test_fabric.py``). The parent drives an UNCHANGED
:class:`ServingRouter` whose roster is :class:`RemoteReplica` proxies over
``fabric/replica_daemon.py`` processes; every daemon builds the same
deterministic tiny model (flax init from PRNGKey(0) is bit-identical across
processes), so token comparisons against a local reference engine are exact.

CPU-ONLY: the parent touches jax and then starts children that do too, so
every process here pins ``JAX_PLATFORMS=cpu``. On a TPU a chip belongs to one
process at a time — a fleet on chips is one daemon process per chip with a
parent that stays off jax; this tool never asks for a device.

``--smoke`` legs (tier-1):
  1. disagg serve, bf16 AND int8 KV: admit → prefill on one process →
     wire-migrate across the process boundary → decode on another; greedy
     outputs token-identical to a single LOCAL reference engine;
  2. migration fidelity: export on daemon A → import on daemon B → the
     per-block blake2b digests (``/block_hashes``) are identical, byte for
     byte, after the KV crossed the wire;
  3. drain/handoff: ``request_drain`` mid-burst quiesces one daemon; its
     admitted requests hand off to the peer through the ordinary migration
     tickets and EVERY request completes (zero drops);
  4. merged trace: daemon ``/dump_trace`` streams + the parent's join via
     ``tools/trace_merge.py`` — at least one request flow links >= 2 pids
     and ``serve:dispatch`` spans appear from >= 2 pids.

Without ``--smoke`` it adds:
  5. SIGKILL mid-burst (``faultinject.kill_replica_daemon``): the router
     detects the death (heartbeat / dispatch failure), re-admits the dead
     replica's admitted requests on the survivor, and completes ALL of them;
  6. elastic training: a trainer child self-preempts (SIGTERM) at a step
     boundary, exits ``EXIT_PREEMPTED`` with a durable snapshot; the
     relaunched process auto-restores and the finished trajectory is
     BIT-IDENTICAL to an uninterrupted run; a second relaunch under a
     CHANGED mesh shape restores and completes (fp32 reduction order
     differs across dp widths, so that leg gates on restore+completion).

Prints one JSON line of evidence (the committed-log artifact); exit 0/1.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PROMPT_SEED = 7
N_PROMPTS = 4
MAX_NEW = 16


# ---------------------------------------------------------------- daemons
class Daemon:
    """A spawned replica-daemon process + its announced URL."""

    def __init__(self, proc: subprocess.Popen, port: int, index: int):
        self.proc = proc
        self.port = port
        self.index = index
        self.url = f"http://127.0.0.1:{port}"


def spawn_daemon(index: int, run_id: str, engine_config: dict, out_dir: str,
                 boot_timeout_s: float = 240.0) -> Daemon:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepspeed_tpu.fabric.replica_daemon",
         "--index", str(index), "--run-id", run_id,
         "--engine-config", json.dumps(engine_config), "--out", out_dir],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env, cwd=REPO)
    # the daemon prints {"port": N, "pid": ...} once the engine is built;
    # scan past the repo's stdout log lines for it, and bound the wait via
    # an event so a wedged boot fails loudly. The reader thread then keeps
    # DRAINING stdout for the daemon's lifetime — a full 64K pipe would
    # block the daemon on its next log write
    box: dict = {}
    booted = threading.Event()

    def read():
        for line in proc.stdout:
            s = line.strip()
            if not booted.is_set() and s.startswith("{") and '"port"' in s:
                box["line"] = s
                booted.set()
        booted.set()  # EOF: boot failed if the line never appeared

    threading.Thread(target=read, daemon=True).start()
    booted.wait(boot_timeout_s)
    line = box.get("line", "")
    if not line:
        proc.kill()
        raise RuntimeError(f"daemon {index} did not announce a port "
                           f"within {boot_timeout_s:.0f}s")
    return Daemon(proc, int(json.loads(line)["port"]), index)


def shutdown_daemon(d: Daemon, timeout: float = 30.0) -> None:
    try:
        from deepspeed_tpu.fabric.remote import _post

        _post(d.url, "/shutdown", {}, timeout=5.0)
    except Exception:
        pass
    try:
        d.proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        d.proc.kill()


def _prompts(vocab: int = 512, n: int = N_PROMPTS):
    import numpy as np

    rng = np.random.default_rng(PROMPT_SEED)
    return [rng.integers(1, vocab, size=int(ln)).astype(np.int32)
            for ln in rng.integers(6, 24, size=n)]


def _engine_cfg(kv_cache_dtype=None, role="mixed"):
    cfg = {"dtype": "bf16", "kv_block_size": 16, "num_kv_blocks": 96,
           "max_seqs": 4, "role": role}
    if kv_cache_dtype:
        cfg["kv_cache_dtype"] = kv_cache_dtype
    return cfg


# ------------------------------------------------------------ serving legs
def leg_disagg_tokens(run_id: str, out_dir: str, kv_cache_dtype=None) -> dict:
    """Prefill on one PROCESS, decode on another; tokens must equal a local
    single-engine reference exactly (greedy is placement-independent)."""
    import numpy as np

    from deepspeed_tpu.fabric.remote import RemoteReplica
    from deepspeed_tpu.fabric.replica_daemon import _build_model
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.router import ServingRouter

    tag = kv_cache_dtype or "bf16"
    da = spawn_daemon(1, run_id, _engine_cfg(kv_cache_dtype, "prefill"), out_dir)
    db = spawn_daemon(2, run_id, _engine_cfg(kv_cache_dtype, "decode"), out_dir)
    remotes = []
    try:
        remotes = [RemoteReplica(da.url), RemoteReplica(db.url)]
        router = ServingRouter(remotes, roles=["prefill", "decode"])
        prompts = _prompts()
        outs = router.serve(prompts, max_new_tokens=MAX_NEW)

        mc, params = _build_model()
        ref = InferenceEngineV2(mc, params, _engine_cfg(kv_cache_dtype))
        ref_outs = ref.generate(prompts, max_new_tokens=MAX_NEW)
        identical = (all(o is not None for o in outs)
                     and all(np.array_equal(a, b)
                             for a, b in zip(outs, ref_outs)))
        return {f"tokens_identical_{tag}": bool(identical),
                f"migrations_{tag}": int(router.migrations),
                f"ok_{tag}": bool(identical and router.migrations >= 1)}
    finally:
        for r in remotes:
            r.close()
        shutdown_daemon(da)
        shutdown_daemon(db)


def leg_migration_digests(run_id: str, out_dir: str) -> dict:
    """Export a live request from daemon A, import on daemon B: the pool
    bytes crossed the wire verbatim iff every per-block blake2b digest
    matches."""
    import jax

    from deepspeed_tpu.fabric.remote import RemoteReplica

    da = spawn_daemon(3, run_id, _engine_cfg(), out_dir)
    db = spawn_daemon(4, run_id, _engine_cfg(), out_dir)
    ra = rb = None
    try:
        ra = RemoteReplica(da.url, start_heartbeat=False)
        rb = RemoteReplica(db.url, start_heartbeat=False)
        prompt = _prompts(n=1)[0]
        suffix = ra.try_admit(11, prompt, [], [])
        rng = jax.random.PRNGKey(0)
        toks, rng = ra._put_sample([11], [suffix.tolist()], rng,
                                   (("do_sample", False),))
        ra.decode_chain([11], [int(toks[0])], [8], 4, rng)
        h_src = ra.block_hashes(11)
        export = ra.export_request(11)
        assert rb.import_request(12, export)
        h_dst = rb.block_hashes(12)
        ra.flush(11)
        rb.flush(12)
        return {"digest_blocks": len(h_src),
                "digests_identical": bool(h_src and h_src == h_dst)}
    finally:
        for r in (ra, rb):
            if r is not None:
                r.close()
        shutdown_daemon(da)
        shutdown_daemon(db)


def leg_drain(run_id: str, out_dir: str) -> dict:
    """Drain one daemon mid-burst: admitted requests hand off to the peer
    and every output completes."""
    from deepspeed_tpu.fabric.remote import RemoteReplica
    from deepspeed_tpu.inference.router import ServingRouter

    da = spawn_daemon(5, run_id, _engine_cfg(), out_dir)
    db = spawn_daemon(6, run_id, _engine_cfg(), out_dir)
    remotes = []
    try:
        remotes = [RemoteReplica(da.url), RemoteReplica(db.url)]
        router = ServingRouter(remotes)
        prompts = _prompts()
        box: dict = {}

        def run():
            box["outs"] = router.serve(prompts, max_new_tokens=32)

        t = threading.Thread(target=run)
        t.start()
        # drain replica 0 while its first admissions are still decoding
        # (the first chain compile alone outlasts this poll)
        deadline = time.time() + 120.0
        while time.time() < deadline and t.is_alive():
            if router.replicas[0].active:
                break
            time.sleep(0.02)
        drained = False
        if t.is_alive():
            router.request_drain(0)
            drained = True
        t.join(600.0)
        outs = box.get("outs", [])
        complete = len(outs) == len(prompts) and all(
            o is not None for o in outs)
        return {"drain_requested": drained,
                "drain_complete": bool(complete),
                "drain_handoffs": int(router.migrations),
                "drain_ok": bool(complete and drained
                                 and router.drains >= 1)}
    finally:
        for r in remotes:
            r.close()
        shutdown_daemon(da)
        shutdown_daemon(db)


def leg_merged_trace(run_id: str, out_dir: str) -> dict:
    """One roster serve, then join the parent + daemon trace streams: the
    request flows must link >= 2 pids through ``serve:dispatch``."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.fabric.remote import RemoteReplica
    from deepspeed_tpu.inference.router import ServingRouter

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_merge

    da = spawn_daemon(7, run_id, _engine_cfg(), out_dir)
    db = spawn_daemon(8, run_id, _engine_cfg(), out_dir)
    remotes = []
    try:
        remotes = [RemoteReplica(da.url), RemoteReplica(db.url)]
        router = ServingRouter(remotes)
        outs = router.serve(_prompts(), max_new_tokens=8)
        streams = [os.path.join(out_dir, "events.p0.jsonl")]
        telemetry.export_jsonl(streams[0])
        for r, idx in ((remotes[0], 7), (remotes[1], 8)):
            p = os.path.join(out_dir, f"events.p{idx}.jsonl")
            r.dump_trace(p)
            streams.append(p)
        merged = trace_merge.merge_streams(
            [s for s in streams if os.path.exists(s)])
        merged_path = os.path.join(out_dir, "merged_trace.json")
        with open(merged_path, "w") as f:
            json.dump(merged, f)
        links = {f: p for f, p in trace_merge.linked_flow_pids(merged).items()
                 if len(p) > 1}
        dispatch_pids = sorted({ev["pid"] for ev in merged["traceEvents"]
                                if ev.get("name") == "serve:dispatch"})
        return {"trace_flow_links": len(links),
                "trace_dispatch_pids": len(dispatch_pids),
                "trace_ok": bool(links) and len(dispatch_pids) >= 2
                and all(o is not None for o in outs),
                "merged_trace": merged_path}
    finally:
        for r in remotes:
            r.close()
        shutdown_daemon(da)
        shutdown_daemon(db)


def leg_sigkill(run_id: str, out_dir: str) -> dict:
    """SIGKILL a daemon mid-burst: admitted-but-unfinished requests must
    complete on the survivor (the fabric's never-drop contract)."""
    from deepspeed_tpu.diagnostics import FaultInjector
    from deepspeed_tpu.fabric.remote import RemoteReplica
    from deepspeed_tpu.inference.router import ServingRouter

    da = spawn_daemon(9, run_id, _engine_cfg(), out_dir)
    db = spawn_daemon(10, run_id, _engine_cfg(), out_dir)
    remotes = []
    try:
        remotes = [RemoteReplica(da.url), RemoteReplica(db.url)]
        router = ServingRouter(remotes)
        prompts = _prompts(n=6)
        box: dict = {}

        def run():
            box["outs"] = router.serve(prompts, max_new_tokens=32)

        t = threading.Thread(target=run)
        t.start()
        deadline = time.time() + 120.0
        while time.time() < deadline and t.is_alive():
            if router.replicas[1].active:
                break
            time.sleep(0.02)
        killed = False
        if t.is_alive():
            FaultInjector().kill_replica_daemon(db.proc)
            killed = True
        t.join(600.0)
        outs = box.get("outs", [])
        complete = len(outs) == len(prompts) and all(
            o is not None for o in outs)
        return {"sigkill_fired": killed,
                "sigkill_complete": bool(complete),
                "sigkill_dead_replicas": int(router.dead_replicas),
                "sigkill_ok": bool(complete and killed
                                   and router.dead_replicas >= 1)}
    finally:
        for r in remotes:
            r.close()
        shutdown_daemon(da)
        shutdown_daemon(db)


# ------------------------------------------------------------- elastic leg
def trainer_main(args) -> int:
    """Trainer child: N resilient steps; optionally self-preempt (SIGTERM to
    OWN pid from the step-``preempt_at`` batch_fn — the guard honors it at
    the next step boundary with a blocking snapshot + exit 143)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import signal

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.elasticity import run_resilient
    from tests.unit.simple_model import random_batch, simple_model_spec

    # mesh shape = however many virtual devices the parent forced via
    # XLA_FLAGS (--dp in the parent): dp defaults to the full device count,
    # so the changed-mesh relaunch is a genuinely different mesh shape
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 1000,
        "snapshot": {"enabled": True, "dir": args.snapshot_dir,
                     "every_n_steps": 2, "fsync": False, "blocking": True},
    }
    engine, *_ = deepspeed_tpu.initialize(
        model=simple_model_spec(), config=cfg, seed=3)

    preempt_at = int(args.preempt_at)

    def batch_fn(step):
        if preempt_at >= 0 and step == preempt_at:
            os.kill(os.getpid(), signal.SIGTERM)
        return random_batch(engine.train_batch_size, seed=step)

    report = run_resilient(engine, batch_fn, num_steps=int(args.steps),
                           preemptible=True)
    import hashlib

    import jax

    digest = hashlib.sha256()
    host = jax.device_get(engine.state.params)
    leaves, _ = jax.tree_util.tree_flatten_with_path(host)
    for path, leaf in leaves:
        digest.update(str(path).encode())
        digest.update(np.ascontiguousarray(
            np.asarray(leaf, dtype=np.float32)).tobytes())
    print(json.dumps({"ok": True, "steps": int(engine.global_steps),
                      "rewinds": report.rewinds,
                      "params_digest": digest.hexdigest()}), flush=True)
    return 0


def _run_trainer(snapshot_dir: str, steps: int, dp: int, preempt_at: int,
                 timeout: float = 600.0):
    import re

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # the child's mesh width IS its virtual device count: strip any
    # inherited forcing (the test harness pins 8) and pin the leg's own
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={dp}").strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--trainer",
         "--snapshot-dir", snapshot_dir, "--steps", str(steps),
         "--dp", str(dp), "--preempt-at", str(preempt_at)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout)
    doc = None
    for line in reversed(proc.stdout.strip().splitlines() or [""]):
        try:
            doc = json.loads(line)
            break
        except ValueError:
            continue
    return proc.returncode, doc


def leg_elastic(out_dir: str) -> dict:
    from deepspeed_tpu.elasticity.resilience import EXIT_PREEMPTED

    res: dict = {}
    # preempt at step 3 of 8, same-mesh relaunch: trajectory bit-identical
    snap_a = os.path.join(out_dir, "snap_resume")
    rc1, _ = _run_trainer(snap_a, steps=8, dp=2, preempt_at=3)
    res["preempt_exit_code"] = rc1
    rc2, resumed = _run_trainer(snap_a, steps=8, dp=2, preempt_at=-1)
    snap_ref = os.path.join(out_dir, "snap_ref")
    rc3, ref = _run_trainer(snap_ref, steps=8, dp=2, preempt_at=-1)
    res["resumed_steps"] = (resumed or {}).get("steps")
    res["elastic_bit_identical"] = bool(
        rc1 == EXIT_PREEMPTED and rc2 == 0 and rc3 == 0
        and resumed and ref and resumed["steps"] == 8
        and resumed["params_digest"] == ref["params_digest"])
    # changed mesh shape on restart: restore + completion (fp32 reduction
    # order differs across dp widths, so no bit-identity gate here)
    snap_b = os.path.join(out_dir, "snap_remesh")
    rc4, _ = _run_trainer(snap_b, steps=8, dp=2, preempt_at=3)
    rc5, remesh = _run_trainer(snap_b, steps=8, dp=4, preempt_at=-1)
    res["elastic_remesh_ok"] = bool(
        rc4 == EXIT_PREEMPTED and rc5 == 0
        and remesh and remesh["steps"] == 8)
    res["elastic_ok"] = bool(res["elastic_bit_identical"]
                             and res["elastic_remesh_ok"])
    return res


# ------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 subset: serving legs only, no kill/elastic")
    ap.add_argument("--out", default=None)
    # trainer mode (internal): the elastic leg's child process
    ap.add_argument("--trainer", action="store_true")
    ap.add_argument("--snapshot-dir", default=None)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--preempt-at", dest="preempt_at", type=int, default=-1)
    args = ap.parse_args()
    if args.trainer:
        return trainer_main(args)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import fleet

    out_dir = args.out or tempfile.mkdtemp(prefix="fabric_smoke_")
    os.makedirs(out_dir, exist_ok=True)
    # children share one persistent XLA compile cache (env-inherited):
    # daemons 2..N and every trainer relaunch reuse daemon 1's compiles.
    # The directory is the package's one fixed place, never under out_dir —
    # a path that moves is a cache that never hits
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = enable_compile_cache()
    run_id = f"fabric-smoke-{os.getpid():x}"
    fleet.configure_identity(run_id=run_id, process_index=0, role="router")
    telemetry.get_tracer().configure(enabled=True)

    gates: dict = {}
    failures = []
    legs = [
        ("disagg_bf16", lambda: leg_disagg_tokens(run_id, out_dir)),
        ("disagg_int8", lambda: leg_disagg_tokens(run_id, out_dir,
                                                  kv_cache_dtype="int8")),
        ("digests", lambda: leg_migration_digests(run_id, out_dir)),
        ("drain", lambda: leg_drain(run_id, out_dir)),
        ("trace", lambda: leg_merged_trace(run_id, out_dir)),
    ]
    if not args.smoke:
        legs.append(("sigkill", lambda: leg_sigkill(run_id, out_dir)))
        legs.append(("elastic", lambda: leg_elastic(out_dir)))
    for name, fn in legs:
        try:
            gates.update(fn())
        except Exception as e:  # noqa: BLE001 - a leg crash IS the finding
            failures.append(f"{name}: {type(e).__name__}: {e}")

    ok_keys = [k for k in gates
               if k.startswith("ok_") or k.endswith("_ok")
               or k in ("digests_identical",)]
    ok = not failures and bool(ok_keys) and all(gates[k] for k in ok_keys)
    print(json.dumps({"ok": ok, "mode": "smoke" if args.smoke else "full",
                      "leg_failures": failures, **gates,
                      "out_dir": out_dir}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
