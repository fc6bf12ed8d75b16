"""Replica daemon: a v2 engine behind stdlib HTTP in its own OS process.

``ReplicaDaemon`` exposes the router's replica protocol (admission, fused
prefill, decode chains, preemption, KV export/import, drain) as POST
routes on a :class:`~deepspeed_tpu.telemetry.exposition.RouteServer` —
the same one daemon-thread/bind/handler implementation behind the fleet
collector, so the fabric adds no new transport machinery.

Observability joins the existing planes end to end:

- the daemon configures ``fleet.ProcessIdentity`` (``role="replica"``) so
  its heartbeats and trace stream carry the fleet identity;
- every dispatched batch row re-enters the sender's trace through
  ``fleet.dispatch_span`` with the request's ``TraceContext`` — the flow
  STEP lands inside this process's ``serve:dispatch`` slice, so
  ``tools/trace_merge.py`` draws the router→replica arrow across pids;
- ``/block_hashes`` exposes ``_block_content_hash`` digests so the smoke
  can prove wire migration moved the quantized pool bytes verbatim.

Run as a subprocess via ``python -m deepspeed_tpu.fabric.replica_daemon``
(one JSON line ``{"port": N}`` on stdout once serving), or embed
``ReplicaDaemon(engine).start()`` in-process for tests.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.fabric.wire import (
    export_from_wire,
    export_to_wire,
    key_from_wire,
    key_to_wire,
)
from deepspeed_tpu.telemetry import fleet
from deepspeed_tpu.telemetry.exposition import RouteServer
from deepspeed_tpu.telemetry.tracer import get_tracer

__all__ = ["ReplicaDaemon", "main"]


def _sample_kw(doc: Any) -> Tuple:
    """Wire sample_kw (list of [k, v] pairs) -> the hashable tuple-of-pairs
    form the engine's jit-static step cache keys on."""
    if doc is None:
        return (("do_sample", False),)
    return tuple((str(k), v) for k, v in doc)


class ReplicaDaemon:
    """One engine, one process, one route table.

    All engine-touching handlers serialize on a single lock: the v2 engine
    mutates ``self.pool`` with donated buffers, so two concurrent RPCs must
    never interleave inside it. The router already serializes per-replica
    traffic (one dispatch thread per replica), so the lock is contention-
    free in the steady state and purely a safety net for control RPCs
    (drain, export) landing mid-dispatch.
    """

    def __init__(self, engine: Any, host: str = "127.0.0.1", port: int = 0,
                 config_doc: Optional[Dict[str, Any]] = None):
        self.engine = engine
        self.draining = False
        self._lock = threading.RLock()
        self._shutdown = threading.Event()
        self._tracer = get_tracer()
        self._requests = 0
        self._preempts = 0
        self._migrations_in = 0
        self._migrations_out = 0
        if config_doc is None:
            dump = getattr(engine.config, "model_dump", None) or getattr(
                engine.config, "dict", None)
            config_doc = json.loads(json.dumps(dump(), default=str)) if dump else {}
        self._config_doc = config_doc
        self.server = RouteServer(
            get_routes={
                "/healthz": self._get_healthz,
                "/spec": self._get_spec,
                "/stats": self._get_stats,
            },
            post_routes={
                path: self._timed(path.lstrip("/"), fn)
                for path, fn in {
                    "/admit": self._post_admit,
                    "/prefill": self._post_prefill,
                    "/chain_round": self._post_chain_round,
                    "/can_schedule": self._post_can_schedule,
                    "/query": self._post_query,
                    "/flush": self._post_flush,
                    "/preempt": self._post_preempt,
                    "/insert_prefix": self._post_insert_prefix,
                    "/export_request": self._post_export_request,
                    "/import_request": self._post_import_request,
                    "/can_import": self._post_can_import,
                    "/block_hashes": self._post_block_hashes,
                    "/drain": self._post_drain,
                    "/dump_trace": self._post_dump_trace,
                    "/shutdown": self._post_shutdown,
                }.items()
            },
            port=port, host=host, name="dstpu-replica-daemon")

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "ReplicaDaemon":
        self.server.start()
        return self

    def stop(self) -> None:
        self.server.stop()

    @property
    def url(self) -> str:
        return f"http://{self.server._host}:{self.server.port}"

    def wait_shutdown(self, timeout: Optional[float] = None) -> bool:
        return self._shutdown.wait(timeout)

    def _count(self, name: str, n: int = 1) -> None:
        if self._tracer.enabled:
            self._tracer.registry.counter(name).add(n)

    def _timed(self, endpoint: str, fn):
        """Per-endpoint server-side RPC accounting. Distinct metric names
        from the client's ``fabric/rpc_ms{endpoint=}`` so federation never
        merges client round-trip and server handler time into one
        histogram. Failures re-raise unchanged (RouteServer's 400/500
        mapping is the protocol) after counting + an event."""
        def handler(doc: Dict) -> Dict:
            t0 = time.perf_counter()
            try:
                out = fn(doc)
            except Exception as e:
                if self._tracer.enabled:
                    self._tracer.registry.counter(
                        "fabric/rpc_server_failures", endpoint=endpoint).add(1)
                from deepspeed_tpu.telemetry.events import emit_event

                emit_event(
                    "fabric", "rpc_server_failure",
                    f"replica daemon RPC {endpoint} raised "
                    f"{type(e).__name__}: {e}",
                    severity="warn", labels={"endpoint": endpoint},
                    dedup_key=f"fabric:rpc_server_failure:{endpoint}")
                raise
            if self._tracer.enabled:
                self._tracer.registry.histogram(
                    "fabric/rpc_server_ms", endpoint=endpoint).observe(
                    (time.perf_counter() - t0) * 1e3)
            return out
        return handler

    # ------------------------------------------------------------------ GET
    def _get_healthz(self) -> Tuple[bytes, str]:
        ident = fleet.get_identity()
        # deliberately LOCK-FREE: a jit compile inside /prefill can hold the
        # daemon lock for tens of seconds, and a heartbeat blocked behind it
        # would read as 4+ consecutive misses — a spurious death verdict on a
        # healthy replica. len() of a dict is GIL-atomic and XLA releases
        # the GIL while compiling, so the read here is always safe and fast.
        active = len(self.engine.state._seqs)
        body = {
            "ok": True,
            "identity": {"run_id": ident.run_id,
                         "process_index": ident.process_index,
                         "host": ident.host, "role": ident.role,
                         "pid": ident.pid},
            "draining": self.draining,
            "queue_depth": active,
            # the daemon serves whatever the router dispatches; SLO goodput
            # is tracked router-side per replica, so the heartbeat reports
            # capacity pressure (pool occupancy), not SLO attainment
            "goodput": 1.0,
            "time_unix": time.time(),
        }
        return json.dumps(body).encode(), "application/json"

    def _get_spec(self) -> Tuple[bytes, str]:
        eng = self.engine
        body = {
            "config": self._config_doc,
            "num_kv_blocks": int(eng.num_kv_blocks),
            "max_seq_len": int(eng.max_seq_len),
            "kv_dtype": str(eng.pool.k.dtype),
            "quant": eng.pool.quant,
            "prefix_cache": eng.prefix_cache is not None,
        }
        return json.dumps(body).encode(), "application/json"

    def _get_stats(self) -> Tuple[bytes, str]:
        eng = self.engine
        body = {
            "requests": self._requests,
            "preempts": self._preempts,
            "migrations_in": self._migrations_in,
            "migrations_out": self._migrations_out,
            "tokens_decoded": int(getattr(eng, "tokens_decoded", 0)),
            "dispatch_count": int(getattr(eng, "dispatch_count", 0)),
            "prefill_tokens_total": int(getattr(eng, "prefill_tokens_total", 0)),
            "prefill_tokens_cached": int(getattr(eng, "prefill_tokens_cached", 0)),
            "prefix_hit_rate": float(getattr(eng.prefix_cache, "hit_rate", 0.0))
            if eng.prefix_cache is not None else 0.0,
        }
        return json.dumps(body).encode(), "application/json"

    # ----------------------------------------------------------- dispatches
    def _span_stack(self, ctxs: Optional[Sequence], stack: contextlib.ExitStack,
                    **args: Any) -> None:
        """Open one ``fleet.dispatch_span`` per request context in the batch:
        each flow STEP lands inside this process's dispatch slice, binding
        the router-side admission arrows into this pid in the merged trace."""
        for wire_ctx in ctxs or ():
            if wire_ctx:
                ctx = fleet.TraceContext.from_wire(wire_ctx)
                stack.enter_context(
                    fleet.dispatch_span(ctx, tracer=self._tracer, **args))

    def _post_admit(self, doc: Dict) -> Dict:
        self._requests += 1
        self._count("fabric/rpcs")
        if self.draining:
            return {"ok": True, "suffix": None, "draining": True}
        with self._lock:
            suffix = self.engine.try_admit(
                int(doc["uid"]), np.asarray(doc["cand"], np.int32),
                [int(u) for u in doc.get("other_uids", ())],
                [int(c) for c in doc.get("other_counts", ())])
        return {"ok": True, "draining": False,
                "suffix": None if suffix is None else [int(t) for t in suffix]}

    def _post_prefill(self, doc: Dict) -> Dict:
        self._requests += 1
        self._count("fabric/rpcs")
        uids = [int(u) for u in doc["uids"]]
        token_lists = [np.asarray(t, np.int32) for t in doc["token_lists"]]
        rng = key_from_wire(doc["rng"])
        with self._lock, contextlib.ExitStack() as stack:
            self._span_stack(doc.get("ctxs"), stack, kind="prefill",
                             rows=len(uids))
            toks, rng = self.engine._put_sample(
                uids, token_lists, rng, _sample_kw(doc.get("sample_kw")))
        return {"ok": True, "toks": [int(t) for t in toks],
                "rng": key_to_wire(rng)}

    def _post_chain_round(self, doc: Dict) -> Dict:
        self._requests += 1
        self._count("fabric/rpcs")
        uids = [int(u) for u in doc["uids"]]
        last = [int(t) for t in doc["last_tokens"]]
        budgets = [int(b) for b in doc["budgets"]]
        k = int(doc["k"])
        rng = key_from_wire(doc["rng"])
        eos = doc.get("eos_id")
        eos = None if eos is None else int(eos)
        with self._lock, contextlib.ExitStack() as stack:
            self._span_stack(doc.get("ctxs"), stack, kind="chain",
                             rows=len(uids), k=k)
            if doc.get("spec"):
                hist = [np.asarray(h, np.int32) for h in doc["histories"]]
                out, emitted, rng = self.engine.decode_spec_chain(
                    uids, last, budgets, k, rng, hist, eos_id=eos)
            else:
                out, emitted, rng = self.engine.decode_chain(
                    uids, last, budgets, k, rng, eos_id=eos,
                    sample_kw=_sample_kw(doc.get("sample_kw")))
        return {"ok": True, "out": np.asarray(out).tolist(),
                "emitted": np.asarray(emitted).tolist(),
                "rng": key_to_wire(rng)}

    # ----------------------------------------------------------- scheduling
    def _post_can_schedule(self, doc: Dict) -> Dict:
        with self._lock:
            ok = self.engine._can_schedule_evicting(
                [int(u) for u in doc["uids"]],
                [int(c) for c in doc["counts"]])
        return {"ok": bool(ok)}

    def _post_query(self, doc: Dict) -> Dict:
        with self._lock:
            seen, free = self.engine.query(int(doc["uid"]))
        return {"ok": True, "seen": int(seen), "free": int(free)}

    def _post_flush(self, doc: Dict) -> Dict:
        with self._lock:
            self.engine.flush(int(doc["uid"]))
        return {"ok": True}

    def _post_preempt(self, doc: Dict) -> Dict:
        """Preemption = flush; the router re-queues and re-admits (the KV
        pages are rebuilt by re-prefill, exactly the in-process semantics)."""
        self._preempts += 1
        self._count("fabric/preempts")
        with self._lock:
            self.engine.flush(int(doc["uid"]))
        return {"ok": True}

    def _post_insert_prefix(self, doc: Dict) -> Dict:
        with self._lock:
            self.engine._insert_prefix(
                int(doc["uid"]), np.asarray(doc["tokens"], np.int32))
        return {"ok": True}

    # ------------------------------------------------------------ migration
    def _post_export_request(self, doc: Dict) -> Dict:
        self._migrations_out += 1
        self._count("fabric/rpcs")
        with self._lock:
            export = self.engine.export_request(int(doc["uid"]))
        wire = export_to_wire(export)
        self._count("fabric/wire_bytes",
                    sum(len(w["data"]) for w in wire["buffer"].values()
                        if w is not None))
        return dict(wire, ok=True)

    def _post_import_request(self, doc: Dict) -> Dict:
        # a layout mismatch raises ValueError -> RouteServer answers 400
        # -> RemoteReplica re-raises ValueError, the in-process contract
        self._migrations_in += 1
        self._count("fabric/rpcs")
        export = export_from_wire(doc["export"])
        with self._lock, contextlib.ExitStack() as stack:
            wire_ctx = doc.get("ctx")
            if wire_ctx:
                stack.enter_context(fleet.dispatch_span(
                    fleet.TraceContext.from_wire(wire_ctx),
                    name="serve:migrate", tracer=self._tracer,
                    blocks=int(export["n_blocks"])))
            ok = self.engine.import_request(int(doc["uid"]), export)
        return {"ok": bool(ok)}

    def _post_can_import(self, doc: Dict) -> Dict:
        with self._lock:
            ok = self.engine.can_import(int(doc["n_blocks"]))
        return {"ok": bool(ok)}

    def _post_block_hashes(self, doc: Dict) -> Dict:
        """Per-block blake2b digests of a live request's pool bytes — the
        fabric's migration-fidelity witness (compared across processes)."""
        with self._lock:
            seq = self.engine.state.get(int(doc["uid"]))
            if seq is None:
                raise ValueError(f"unknown uid {doc['uid']}")
            hashes = [self.engine._block_content_hash(int(b))
                      for b in seq.blocks]
        return {"ok": True, "hashes": hashes}

    # -------------------------------------------------------------- control
    def _post_drain(self, doc: Dict) -> Dict:
        """Quiesce: refuse new admissions. In-flight requests keep serving;
        the router's drain path exports their KV and hands them to a peer
        through the ordinary migration-ticket machinery."""
        self.draining = True
        self._count("fabric/drains")
        with self._lock:
            active = [int(u) for u in self.engine.state._seqs]
        return {"ok": True, "draining": True, "active_uids": active}

    def _post_dump_trace(self, doc: Dict) -> Dict:
        from deepspeed_tpu.telemetry.exporters import export_jsonl

        path = export_jsonl(str(doc["path"]), tracer=self._tracer)
        return {"ok": True, "path": path}

    def _post_shutdown(self, doc: Dict) -> Dict:
        self._shutdown.set()
        return {"ok": True}


def _build_model(name: str = "tiny"):
    """Deterministic test model shared by every fabric process: flax init
    from PRNGKey(0) is bit-identical across processes, so daemons and the
    parent's reference engine agree on params BY CONSTRUCTION — no weight
    shipping on the wire (real deployments load a checkpoint instead)."""
    import jax

    from deepspeed_tpu.models import CausalLM, TransformerConfig

    if name == "tiny":
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256)
    else:
        raise ValueError(f"unknown fabric model {name!r}")
    module = CausalLM(cfg)
    params = module.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
        {"input_ids": np.zeros((1, 8), np.int32)}, train=False)["params"]
    return cfg, params


def main(argv: Optional[List[str]] = None) -> int:
    """Subprocess entrypoint: build the deterministic model + engine, serve,
    print ``{"port": N}`` on stdout, block until ``/shutdown`` (or until the
    parent dies), export the trace stream, exit 0."""
    import argparse
    import os
    import sys

    p = argparse.ArgumentParser(description="serving-fabric replica daemon")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--index", type=int, default=0)
    p.add_argument("--run-id", default=None)
    p.add_argument("--model", default="tiny")
    p.add_argument("--engine-config", default="{}",
                   help="RaggedInferenceConfig fields as JSON")
    p.add_argument("--out", default=None,
                   help="directory for the trace JSONL export on shutdown")
    args = p.parse_args(argv)

    # no platform default: a daemon started with no JAX_PLATFORMS runs on the
    # accelerator jax finds (one daemon per chip); CPU fleets pass it in env
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    fleet.configure_identity(run_id=args.run_id, process_index=args.index,
                             role="replica")
    tracer = get_tracer()
    tracer.configure(enabled=True)

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    eng_cfg = json.loads(args.engine_config)
    cfg, params = _build_model(args.model)
    engine = InferenceEngineV2(cfg, params, eng_cfg)
    # no config_doc override: /spec advertises the engine's FULL validated
    # config (model_dump), not just the fields the caller set — the remote
    # proxy's RaggedInferenceConfig then matches the daemon's exactly
    daemon = ReplicaDaemon(engine, host=args.host, port=args.port).start()
    print(json.dumps({"port": daemon.server.port, "pid": os.getpid()}),
          flush=True)
    # serve until asked to stop; bail out if the parent process died (ppid
    # reparented to init) so orphaned daemons never outlive a crashed smoke
    while not daemon.wait_shutdown(timeout=0.5):
        if os.getppid() == 1:
            break
    if args.out:
        from deepspeed_tpu.telemetry.exporters import export_jsonl

        os.makedirs(args.out, exist_ok=True)
        export_jsonl(os.path.join(args.out, f"events.p{args.index}.jsonl"),
                     tracer=tracer)
    daemon.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
