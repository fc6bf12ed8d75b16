"""The training engine.

TPU-native analog of ``DeepSpeedEngine`` (reference ``runtime/engine.py:189``).
Where the reference wraps a torch module with Python-side hooks, streams, and
bucketed collectives, this engine compiles ONE SPMD program per train step:

  - master fp32 params + optimizer state placed per ZeRO stage (see zero.py)
  - micro-batch gradient accumulation via ``lax.scan`` (grad buffers sharded
    for stage >= 2, i.e. reduce-scatter per micro-batch)
  - mixed precision (bf16/fp16 compute, fp32 master) with a dynamic loss
    scaler and overflow-skip folded into the compiled step
  - gradient clipping by global norm
  - LR schedule evaluated inside the step

API parity: ``forward/backward/step`` (reference :2041/:2204/:2338) are
provided for drop-in ergonomics, and ``train_batch`` is the fused fast path
(one dispatch per global batch, as ``PipelineEngine.train_batch`` does).
"""

from __future__ import annotations

import functools
import os
import sys
import time
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.config.config import DeepSpeedTPUConfig
from deepspeed_tpu.runtime import zero as zero_mod
from deepspeed_tpu.runtime.lr_schedules import Schedule, constant_schedule, get_lr_schedule
from deepspeed_tpu.runtime.model import ModelSpec
from deepspeed_tpu.runtime.optimizers import get_optimizer
from deepspeed_tpu.runtime.precision import (
    LossScaleState,
    all_finite,
    cast_floating,
    clip_by_global_norm,
    global_norm,
    make_loss_scale_state,
    update_loss_scale,
)
from deepspeed_tpu.topology.mesh import (
    batch_pspec,
    build_mesh,
    dot_general_context,
    get_data_parallel_world_size,
    set_mesh,
)
from deepspeed_tpu.telemetry.fleet import note_step as _fleet_note_step
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import ThroughputTimer

# Device-computed MoE dispatch gauges (parallel/moe.py gating stats): keys in
# the step metrics dict, monitor scalars, and registry gauges alike.
_MOE_METRIC_KEYS = ("moe/capacity_factor", "moe/token_drop_rate",
                    "moe/expert_load_balance", "moe/capacity_factor_applied")

# /metrics HTTP servers, one per configured port for the process lifetime
# (daemon threads over the process-global registry — engines come and go,
# the exposition endpoint stays; port 0 always binds a fresh free port).
_METRICS_SERVERS: dict = {}


# Fleet push clients, one per collector URL for the process lifetime (the
# registry and identity they push are process-global — a second engine with
# the same fleet_url must reuse the cadence thread, not double the traffic).
_FLEET_CLIENTS: dict = {}


def _get_fleet_client(url: str, interval_s: float):
    """Start (or reuse) the process-global fleet push client for ``url``."""
    from deepspeed_tpu.telemetry.collector import FleetClient

    client = _FLEET_CLIENTS.get(url)
    if client is not None:
        return client
    client = _FLEET_CLIENTS[url] = FleetClient(url)
    client.start(interval_s=interval_s)
    return client


def _get_metrics_server(port: int):
    """Start (or reuse) the process-global /metrics server for ``port``.
    Never raises — an unbindable port logs a warning and returns None."""
    from deepspeed_tpu import telemetry as telemetry_mod

    srv = _METRICS_SERVERS.get(port)
    if srv is not None and srv.port is not None:
        return srv
    try:
        srv = telemetry_mod.serve_metrics(port=port)
    except OSError as e:  # port taken by something that is not ours
        logger.warning(f"telemetry: could not bind /metrics on port {port}: {e}")
        return None
    if port != 0:  # every port-0 request gets its own fresh server
        _METRICS_SERVERS[port] = srv
    return srv


def _facade_grad_mean(g, live):
    """Mean-reduce an unsharded gradient leaf over the data axes of the
    ZeRO++ / LoCo shard_map grad paths through the comm facade, so the mean
    is recorded like every other stated collective. The loss pmean stays
    native: a scalar control value is not worth a record."""
    from deepspeed_tpu.comm import comm as comm_mod

    return comm_mod.all_reduce(g, live, op="mean")


class TrainState(NamedTuple):
    """Entire training state — one pytree, placed once on the mesh."""

    step: jax.Array  # i32 global step (optimizer steps taken)
    params: Any  # fp32 master params
    opt_state: Any
    loss_scale: LossScaleState
    rng: jax.Array  # uint32 key data
    # 1-bit gradient compression error-feedback buffers (None unless
    # gradient_compression / a OneBit optimizer is active): per-dp-rank
    # residuals, leaves shaped [dp_world, *param.shape] sharded on dim 0
    # (reference runtime/comm/nccl.py worker_error).
    comm_error: Any = None
    # Training-health EMA state (diagnostics/health.py HealthState) — None
    # unless the diagnostics block enables in-step health probes, so the
    # disabled path compiles the identical program.
    health: Any = None
    # Cross-replica divergence-sentinel state (telemetry/numerics.py
    # NumericsState) — None unless the numerics block enables the in-jit
    # sentinel; same disabled-path identity contract as ``health``.
    numerics: Any = None


class DeepSpeedTPUEngine:
    """Training engine (reference ``DeepSpeedEngine`` runtime/engine.py:189)."""

    def __init__(
        self,
        model: ModelSpec,
        config: DeepSpeedTPUConfig,
        mesh: Optional[Mesh] = None,
        optimizer: Optional[optax.GradientTransformation] = None,
        lr_scheduler: Optional[Schedule] = None,
        model_parameters: Any = None,
        training_data: Any = None,
        seed: Optional[int] = None,
    ):
        self.model = model
        self.mesh = mesh if mesh is not None else self._build_engine_mesh(config)
        set_mesh(self.mesh)

        # Re-resolve the batch triad now that the true dp world is known.
        self.config = DeepSpeedTPUConfig(config.raw, dp_world_size=get_data_parallel_world_size(self.mesh))
        self.zero_config = self.config.zero_config
        self.compute_dtype = self.config.compute_dtype
        self.fp16 = self.config.fp16_enabled
        # Gradient-accumulation dtype (reference bf16_optimizer grad-accum
        # dtype knob): bf16.accumulate_grads_in_fp32=false carries the
        # micro-step accumulator in bf16 — half the grad-buffer HBM during
        # the scan; the optimizer math still runs fp32 (_update_math upcasts
        # at the accumulation boundary). fp16 keeps fp32 accumulation
        # (overflow detection semantics).
        bf16_cfg = self.config.model.bf16
        self._accum_dtype = (
            jnp.bfloat16
            if bf16_cfg.enabled and not bf16_cfg.accumulate_grads_in_fp32
            else jnp.float32)
        seed = seed if seed is not None else self.config.model.seed
        self._configure_offload()

        # ---- optimizer + schedule ----------------------------------------
        self.lr_scheduler_fn, self._client_lr_scheduler = self._build_lr_schedule(lr_scheduler)
        if optimizer is not None:
            self.tx = optimizer
        else:
            opt_cfg = self.config.model.optimizer
            if opt_cfg is None:
                raise ValueError(
                    "No optimizer: pass an optax GradientTransformation to initialize() "
                    "or add an 'optimizer' section to the config"
                )
            self.tx, _ = get_optimizer(opt_cfg.type, opt_cfg.params, learning_rate=self.lr_scheduler_fn)

        # ZeRO++ knobs validate at construction (dead/lying knobs are worse
        # than errors); quantized collectives do not compose with the
        # split-backend offload step.
        if (self.config.model.prescale_gradients
                or self.config.model.gradient_predivide_factor != 1.0):
            # The compiled step computes the exact gradient mean inside ONE
            # fused program — there is no separate allreduce to pre/post-scale
            # around, so these knobs cannot change anything. Raising beats a
            # lying no-op (fp16 headroom is covered by dynamic loss scaling).
            raise NotImplementedError(
                "prescale_gradients / gradient_predivide_factor have no effect "
                "in the fused SPMD step; remove them (dynamic loss scaling "
                "handles fp16 overflow headroom)")
        self._zpp = self._zpp_config()
        if self._zpp and self.offload_mode in ("host-jit", "nvme"):
            raise NotImplementedError(
                "ZeRO++ quantized collectives (zero_quantized_weights/gradients) "
                "are not supported together with optimizer offload's split-"
                "backend step; drop one of the two"
            )
        self._onebit = self._onebit_config()

        # ---- sparse embedding gradients (must precede step compilation) --
        self._resolve_sparse_gradients()

        # ---- MoE dispatch gauges (must precede step compilation: the stats
        # are computed inside the jitted step) ------------------------------
        self._resolve_moe_metrics()
        # ---- capacity-factor autotuning (feeds on those gauges; must also
        # precede step compilation: it rebuilds the spec with the padded
        # static capacity ceiling the traced cutoff moves within) -----------
        self._resolve_moe_autotune()

        mcfg = getattr(self.model, "transformer_config", None)
        if (getattr(mcfg, "fpdt_offload", False)
                and int(np.prod(list(self.mesh.shape.values()))) > 1):
            raise NotImplementedError(
                "fpdt_offload on a multi-device mesh: XLA's SPMD partitioner "
                "rejects host-memory placement annotations (\"Side-effect HLO "
                "must have sharding\") in this version — run fpdt_offload "
                "single-chip, or use attn_impl='fpdt' without offload (or "
                "sp_impl='ring') for multi-chip long context")

        if getattr(mcfg, "hc_mult", 0) and dict(self.mesh.shape).get("tp", 1) > 1:
            raise NotImplementedError(
                f"hyper-connections (hc_mult={mcfg.hc_mult}) with tp={self.mesh.shape['tp']}: the "
                "partition rules replicate their leaves, and the mix is a statistic and a product "
                "over the whole hidden width of every stream, which nothing has needed split yet")

        # MoE × TP (ISSUE 15): ep×tp meshes route the MoE block through the
        # explicit collective dispatch (parallel/moe.py collective_moe_apply
        # — the reference moe/mappings.py token gather/drop across the tp
        # group, with the [E, C, M] reshard as facade all_to_all over ep).
        # The old loud refusal is gone; an unservable shape (non-divisible
        # tokens/experts) still fails loudly at trace time inside
        # resolve_dispatch_mode rather than silently mis-routing.
        if (dict(self.mesh.shape).get("ep", 1) > 1
                and dict(self.mesh.shape).get("tp", 1) > 1
                and getattr(mcfg, "has_moe", False)):
            log_dist(
                f"MoE ep={self.mesh.shape['ep']} × tp={self.mesh.shape['tp']}: "
                "token dispatch/combine routed through the collective "
                "all_to_all (cross-tp gather/drop; moe_dispatch="
                f"{getattr(mcfg, 'moe_dispatch', 'auto')!r})", ranks=[0])

        # ---- pre-flight HBM-fit guard (BEFORE any device materialization:
        # an over-budget config is refused with the estimate in the error
        # instead of running out of memory mid-placement) -----------------
        self._check_hbm_budget(mcfg)

        # ---- state init + placement --------------------------------------
        self._init_state(model_parameters, seed)

        # ---- diagnostics (before step compilation: the health probes trace
        # into the step and the recompile detector wraps the jitted fns) ----
        self._setup_diagnostics()

        # ---- numerics observatory (after diagnostics: the drift/divergence
        # alarms arm its profiler capture; before step compilation: the
        # divergence sentinel traces into the step) -----------------------
        self._setup_numerics()

        # ---- elastic snapshots (checkpoint/snapshot.py): cadenced async
        # sharded saves off the step clock; restore works onto any mesh ----
        self.snapshot_manager = None
        if self.config.model.snapshot.enabled:
            from deepspeed_tpu.checkpoint.snapshot import SnapshotManager

            self.snapshot_manager = SnapshotManager(self, self.config.model.snapshot)

        # ---- data --------------------------------------------------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # ---- compiled steps ----------------------------------------------
        if self.offload_mode in ("host-jit", "nvme"):
            # Split program: device grad accumulation + compiled host update
            # (the DeepSpeedCPUAdam analog). ``_train_step`` stays None.
            self._train_step = None
            self._offload_grad_step = self._wrap_jit(
                "offload_grad_step", self._build_offload_grad_step(),
                ("params", "batch", "scale", "rng"))
            if self._twin_ratio is not None:
                self._build_twin_flow_steps()
            else:
                self._offload_update_step = self._wrap_jit(
                    "offload_update_step", self._build_offload_update_step(),
                    ("state", "grads"))
        else:
            self._train_step = self._wrap_jit(
                "train_step", self._build_train_step(), ("state", "batch"))
        self._grad_step = None  # built lazily for the forward/backward/step path
        self._apply_step = None
        self._eval_step = None
        self._pending_grads = None
        self._pending_losses: list = []
        self._micro_steps = 0

        # wall_clock_breakdown (reference engine timers): the fused TPU step
        # has no separable fwd/bwd/step phases, so the honest analog is a
        # per-step wall-clock window (note: with async dispatch an individual
        # window captures dispatch; true device rates appear at sync points)
        self.throughput_timer = ThroughputTimer(
            batch_size=self.config.train_batch_size,
            steps_per_output=(1 if self.config.model.wall_clock_breakdown
                              else self.config.model.steps_per_print),
        )
        self.losses = None
        self.monitor = None  # wired by engine_builder when monitoring configured
        # Host-side batch counter: drives print/profile gating and monitor
        # x-axis without reading device state (``int(self.state.step)`` blocks
        # the dispatch pipeline — the round-2 verdict's per-step-sync finding).
        # Equal to ``global_steps`` except under fp16 overflow skips.
        self._batch_count = 0
        # Buffered monitor writes: (batch_idx, device-metrics) pairs fetched in
        # one bulk transfer at flush time so logging never stalls the step.
        self._monitor_pending: list = []

        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler

        self.flops_profiler = FlopsProfiler(engine=self)
        if self.config.model.memory_breakdown:
            # reference engine.py:257 logs phased see_memory_usage when the
            # memory_breakdown knob is set
            from deepspeed_tpu.utils.memory import see_memory_usage

            see_memory_usage("engine state initialized", force=True)
        if self.config.model.comms_logger.enabled:
            # reference comm/config.py CommsConfig -> comm logger wiring
            from deepspeed_tpu.comm import comm as comm_mod

            cl = self.config.model.comms_logger
            comm_mod.configure(enabled=True, verbose=cl.verbose, debug=cl.debug)
        # Telemetry (telemetry/): the config block configures the process-
        # global tracer; the engine keeps a direct handle for its hot-path
        # spans. When the block is absent the env var (DSTPU_TELEMETRY=1) may
        # still have enabled the tracer — every span call is a single
        # attribute check when it hasn't.
        from deepspeed_tpu import telemetry as telemetry_mod

        tcfg = self.config.model.telemetry
        self._metrics_server = None
        if tcfg.enabled:
            telemetry_mod.configure(
                enabled=True,
                max_events=tcfg.max_events,
                memory_watermarks=tcfg.memory_watermarks,
                trace_path=tcfg.trace_path, jsonl_path=tcfg.jsonl_path,
                prometheus_path=tcfg.prometheus_path)
            # the process-global program registry follows the tracer unless
            # pinned; honor this engine's knob (last-constructed wins)
            from deepspeed_tpu.telemetry import programs as programs_mod

            programs_mod.configure(enabled=None if tcfg.programs else False)
            if tcfg.http_port is not None:
                # scrapeable /metrics for the whole registry (training scalars
                # ride the same exposition the serving SLO metrics use). The
                # server is PROCESS-global state like the tracer it exposes:
                # one per configured port, reused by later engines (tests
                # build dozens; a second bind would EADDRINUSE).
                self._metrics_server = _get_metrics_server(tcfg.http_port)
                if self._metrics_server is not None:
                    log_dist(
                        f"telemetry: /metrics on port {self._metrics_server.port}",
                        ranks=[0])
        # Incident plane (telemetry/events.py + alerts.py): size the typed
        # event ring and wire the JSONL export next to the trace stream;
        # the alert engine's daemon-thread evaluation is its own opt-in.
        from deepspeed_tpu.telemetry import events as events_mod

        events_mod.configure_events(
            capacity=tcfg.events_capacity,
            dedup_window_s=tcfg.events_dedup_window_s,
            jsonl_path=(tcfg.events_jsonl_path
                        if tcfg.events_jsonl_path is not None
                        else (os.path.join(
                            telemetry_mod.default_output_dir(),
                            "event_log.jsonl") if tcfg.enabled else None)))
        self._alert_engine = None
        if tcfg.alerts_enabled:
            from deepspeed_tpu.telemetry import alerts as alerts_mod

            self._alert_engine = alerts_mod.configure_alerts(
                jsonl_path=tcfg.alerts_jsonl_path,
                webhook_url=tcfg.alerts_webhook_url,
                interval_s=tcfg.alerts_interval_s)
        self._fleet_client = None
        if tcfg.fleet_url:
            # fleet federation: register with the collector (identity +
            # clock handshake) and push snapshots/heartbeats on a daemon
            # cadence — push failures never reach the training step. The
            # client is PROCESS-global per URL like the /metrics server:
            # engines come and go, one cadence thread pushes the one
            # process-global registry.
            from deepspeed_tpu.telemetry import fleet as fleet_mod

            if tcfg.fleet_role is not None:
                fleet_mod.configure_identity(role=tcfg.fleet_role)
            self._fleet_client = _get_fleet_client(
                tcfg.fleet_url, tcfg.fleet_push_interval_s)
        self._tracer = telemetry_mod.get_tracer()
        if self.config.model.dump_state:
            # reference engine.py dump_state: print the resolved config once
            log_dist(f"engine config: {self.config.model.model_dump()}", ranks=[0])
        log_dist(
            f"engine ready: mesh={dict(self.mesh.shape)} zero_stage={self.zero_config.stage} "
            f"dtype={self.compute_dtype.__name__} batch={self.config.train_batch_size} "
            f"micro={self.config.train_micro_batch_size_per_gpu} gas={self.config.gradient_accumulation_steps}",
            ranks=[0],
        )

    # ------------------------------------------------------------------ init
    def _resolve_sparse_gradients(self) -> None:
        """Honor ``sparse_gradients: true`` (reference runtime/sparse_tensor.py:69
        + engine sparse-grad allreduce paths, engine.py:2104): when the size
        heuristic says sparse sync wins, rebuild the model spec with the
        sparse-backward embedding lookup (``runtime/sparse_grad.sparse_lookup``)
        so the compiled step all-gathers compact (ids, rows) pairs instead of
        psum-ing the dense [V, H] embedding gradient."""
        if not self.config.model.sparse_gradients:
            return
        from deepspeed_tpu.runtime.sparse_grad import should_use_sparse_embedding_grad

        def keep_dense(why: str) -> None:
            log_dist(f"sparse_gradients: dense embedding-grad sync kept — {why}",
                     ranks=[0])

        mcfg = getattr(self.model, "transformer_config", None)
        if mcfg is None:
            return keep_dense("model spec carries no transformer_config")
        tokens = self.config.train_batch_size * mcfg.max_seq_len
        if not should_use_sparse_embedding_grad(mcfg.vocab_size, tokens):
            return keep_dense(
                f"heuristic: vocab={mcfg.vocab_size} vs global batch tokens "
                f"<={tokens}; sparse rows would not shrink the wire")
        if getattr(mcfg, "tie_embeddings", False):
            return keep_dense("tie_embeddings: the tied LM head grad is dense anyway")
        if getattr(mcfg, "sparse_embedding_grads", False):
            log_dist("sparse_gradients: model already built with sparse "
                     "embedding grads", ranks=[0])
            return
        if self.model.rebuild is None:
            return keep_dense(
                "model spec has no rebuild hook; construct the model with "
                "TransformerConfig(sparse_embedding_grads=True) to opt in")
        import dataclasses as _dc

        self.model = self.model.rebuild(
            _dc.replace(mcfg, sparse_embedding_grads=True))
        log_dist(
            f"sparse_gradients: sparse embedding-grad sync ENGAGED "
            f"(vocab={mcfg.vocab_size}, global batch tokens<={tokens}) — "
            "backward all-gathers (ids, rows) pairs, no dense [V, H] psum",
            ranks=[0])

    def _resolve_moe_metrics(self) -> None:
        """With telemetry on and an MoE model, rebuild the spec with
        ``moe_metrics=True`` so the gating math also emits its dispatch
        stats (capacity occupancy, token drops, expert load balance — ROADMAP
        item 4's instrumentation). The stats ride the step's metrics dict as
        ``moe/*`` scalars: device-computed, fetched only at the existing
        monitor/print sync points. Telemetry off ⇒ untouched spec ⇒
        byte-identical program."""
        self._moe_metrics = False
        if not self.config.model.telemetry.enabled:
            return
        mcfg = getattr(self.model, "transformer_config", None)
        if mcfg is None or not getattr(mcfg, "has_moe", False):
            return
        if self._zpp or self._onebit or self.offload_mode in ("host-jit", "nvme"):
            # those step builders compute their losses inside their own
            # micro fns — the stats side channel is not threaded through.
            # A silently-dead gauge is worse than a log line.
            log_dist(
                "moe metrics: not wired into the zero++/1-bit/offload step "
                "builders; moe/* gauges stay absent for this engine", ranks=[0])
            return
        if int(self.mesh.shape.get("pp", 1)) > 1:
            # the pipelined loss threads a scalar aux through the pp ring;
            # the stats dict can't ride it (pipelined_causal_lm_loss raises)
            log_dist("moe metrics: skipped on pp>1 meshes (stats side channel "
                     "not threaded through the pipeline ring)", ranks=[0])
            return
        if getattr(mcfg, "moe_metrics", False):
            self._moe_metrics = True
            return
        if self.model.rebuild is None:
            log_dist(
                "moe metrics: model spec has no rebuild hook; construct with "
                "TransformerConfig(moe_metrics=True) to opt in", ranks=[0])
            return
        import dataclasses as _dc

        self.model = self.model.rebuild(_dc.replace(mcfg, moe_metrics=True))
        self._moe_metrics = True
        log_dist("moe metrics: dispatch gauges ENGAGED "
                 "(moe/capacity_factor|token_drop_rate|expert_load_balance)",
                 ranks=[0])

    def _resolve_moe_autotune(self) -> None:
        """Arm the host-side capacity-factor controller (``moe_autotune``
        config block): the model spec is rebuilt with
        ``moe_capacity_factor_max = max_factor`` so every capacity array is
        padded to the static ceiling and the gate's drop cutoff follows a
        traced scalar (batch key ``moe_capacity_factor``); the controller
        then nudges that scalar between steps from the ``moe/*`` gauges it
        reads at the existing ``steps_per_print`` fetch — never a recompile,
        never an extra device sync."""
        self._moe_autotune = None
        self._moe_cap_leaf = None
        self._moe_cap_leaf_value = None
        cfg = self.config.model.moe_autotune
        if not cfg.enabled:
            return
        # bad bounds are a config error regardless of whether the controller
        # can arm — report them before any disarm path goes quiet
        if not (0 < cfg.min_factor <= cfg.max_factor):
            raise ValueError(
                f"moe_autotune: need 0 < min_factor <= max_factor, got "
                f"[{cfg.min_factor}, {cfg.max_factor}]")
        if not getattr(self, "_moe_metrics", False):
            # the gauges ARE the controller's sensor; every reason metrics
            # are unavailable (telemetry off, dense model, pp>1, zero++/
            # 1-bit/offload step builders) disables autotuning with it
            log_dist("moe_autotune: requires the moe/* dispatch gauges "
                     "(telemetry enabled + an MoE model on a non-pp mesh, "
                     "fused/zero step builders); controller disarmed", ranks=[0])
            return
        import dataclasses as _dc

        mcfg = self.model.transformer_config
        if not mcfg.moe_drop_tokens:
            log_dist("moe_autotune: drop_tokens=False has no capacity bound "
                     "to tune; controller disarmed", ranks=[0])
            return
        # the ceiling must never SHRINK the capacity below the static factor
        # the model was tuned with — arming the controller may only add
        # headroom, so the padded bound is max(max_factor, configured)
        ceiling = max(float(cfg.max_factor), float(mcfg.moe_capacity_factor))
        if ceiling > cfg.max_factor:
            log_dist(
                f"moe_autotune: max_factor={cfg.max_factor} below the "
                f"configured moe_capacity_factor={mcfg.moe_capacity_factor}; "
                f"raising the ceiling to {ceiling} (the controller never "
                "clamps a model below its static factor)", ranks=[0])
        if getattr(mcfg, "moe_capacity_factor_max", None) != ceiling:
            if self.model.rebuild is None:
                log_dist("moe_autotune: model spec has no rebuild hook; set "
                         "TransformerConfig(moe_capacity_factor_max=...) to "
                         "opt in", ranks=[0])
                return
            self.model = self.model.rebuild(
                _dc.replace(mcfg, moe_capacity_factor_max=ceiling))
            mcfg = self.model.transformer_config
        self._moe_autotune = cfg
        self._moe_cap_max = ceiling
        # the knob starts at the configured static factor, clipped in-bounds
        self._moe_cap_factor = float(
            min(max(mcfg.moe_capacity_factor, cfg.min_factor), ceiling))
        log_dist(
            f"moe_autotune: capacity-factor controller ENGAGED (start="
            f"{self._moe_cap_factor:.3f}, bounds=[{cfg.min_factor}, "
            f"{ceiling}], target_drop={cfg.target_drop_rate}, "
            f"cadence=every {self.config.model.steps_per_print} steps)",
            ranks=[0])

    def _moe_autotune_batch_key(self, placed):
        """Thread the controller's knob into the placed batch: a replicated
        ``[gas]`` fp32 leaf (one scalar per micro-step, so it rides the
        micro scan like every other leaf). Shape/dtype/sharding are
        identical every step — only the VALUE moves, the jit cache holds
        one program."""
        if self._moe_autotune is None or not isinstance(placed, dict):
            return placed
        leaf = self._moe_cap_leaf
        if leaf is None or self._moe_cap_leaf_value != self._moe_cap_factor:
            # the leaf only changes at controller ticks (steps_per_print
            # cadence) — cache the placed array so steady-state steps pay
            # no per-step host->device transfer for an unchanged knob
            gas = self.config.gradient_accumulation_steps
            leaf = jax.device_put(
                jnp.full((gas,), self._moe_cap_factor, jnp.float32),
                NamedSharding(self.mesh, PartitionSpec()))
            self._moe_cap_leaf = leaf
            self._moe_cap_leaf_value = self._moe_cap_factor
        placed = dict(placed)
        placed["moe_capacity_factor"] = leaf
        return placed

    def _moe_autotune_update(self, fetched: Dict[str, Any]) -> None:
        """One controller tick from the freshly fetched step metrics:
        drops above target raise the effective factor (fast), a balanced
        no-drop dispatch lowers it (slow decay) — always inside
        ``[min_factor, max_factor]``."""
        cfg = self._moe_autotune
        drop = fetched.get("moe/token_drop_rate")
        balance = fetched.get("moe/expert_load_balance")
        if drop is None:
            return
        drop = float(drop)
        prev = self._moe_cap_factor
        if drop > cfg.target_drop_rate:
            self._moe_cap_factor = min(prev + cfg.increase_step,
                                       self._moe_cap_max)
        elif balance is not None and float(balance) <= cfg.balance_threshold:
            self._moe_cap_factor = max(prev - cfg.decrease_step, cfg.min_factor)
        if self._tracer.enabled:
            # the controller's own breadcrumbs next to the gate gauges it
            # feeds on (moe/capacity_factor_applied confirms arrival)
            self._tracer.registry.gauge("moe/capacity_factor_target").set(
                self._moe_cap_factor)
        if self._moe_cap_factor != prev:
            log_dist(
                f"moe_autotune: drop_rate={drop:.4f} balance="
                f"{float(balance) if balance is not None else -1.0:.3f} -> "
                f"capacity factor {prev:.3f} -> {self._moe_cap_factor:.3f}",
                ranks=[0])

    def _configure_offload(self) -> None:
        """Resolve the ZeRO-Offload/Infinity mode from the config.

        Reference wiring: ``zero/stage3.py:2082`` (optimizer swap into the
        step) + ``swap_tensor/partitioned_optimizer_swapper.py:29`` +
        ``zero/offload_config.py``. TPU-native modes:

        - ``host-jit``: fp32 master + moments live committed to the host CPU
          backend; the optimizer update itself runs as a compiled CPU program
          (the DeepSpeedCPUAdam analog) and only bf16 compute params return to
          the accelerator. Used whenever a ``cpu`` JAX backend coexists with
          the accelerator (and always on CPU test meshes).
        - ``memories``: no CPU backend available (e.g. JAX_PLATFORMS pins the
          TPU only) — master/opt shardings get
          ``memory_kind='pinned_host'`` and stay inside the ONE compiled step;
          XLA inserts the H2D/D2H streams (its latency-hiding scheduler
          overlaps them with compute).
        - ``nvme``: host-jit plus the AIO swapper — moments are written to
          disk after the update (async) and prefetched before the next one
          (ZeRO-Infinity; reference partitioned_optimizer_swapper).
        """
        self._offload_cfg = self.zero_config.offload_optimizer
        self._offload_param_cfg = self.zero_config.offload_param
        self.offload_mode: Optional[str] = None
        self._host_device = None
        self._opt_swapper = None
        self._twin_ratio: Optional[float] = None
        dev = self._offload_cfg.device if self._offload_cfg else "none"
        param_dev = self._offload_param_cfg.device if self._offload_param_cfg else "none"
        if dev not in ("cpu", "nvme"):
            if param_dev in ("cpu", "nvme"):
                # Param-only offload (reference supports it standalone): the
                # split path hosts the fp32 masters either way, so honor the
                # request by enabling it — moments ride along to the host,
                # a superset of the asked-for device-memory saving.
                log_dist(
                    "offload_param set without offload_optimizer: hosting fp32 "
                    "masters AND moments off-device (superset of the request)",
                    ranks=[0],
                )
                dev = "cpu"
            else:
                return
        try:
            self._host_device = jax.devices("cpu")[0]
        except Exception:
            self._host_device = None
        if dev == "nvme":
            if self._host_device is None:
                raise ValueError("offload_optimizer device='nvme' needs a host CPU backend for the update step")
            folder = self._offload_cfg.nvme_path if self._offload_cfg else None
            if not folder:
                # the reference requires nvme_path too; a shared default would
                # let concurrent jobs clobber each other's swapped moments
                raise ValueError("offload_optimizer device='nvme' requires 'nvme_path' in the config")
            from deepspeed_tpu.runtime.swap_tensor import OptimizerStateSwapper

            self._opt_swapper = OptimizerStateSwapper(os.path.join(folder, "opt_state"))
            self.offload_mode = "nvme"
        elif self._host_device is not None:
            self.offload_mode = "host-jit"
        else:
            self.offload_mode = "memories"
        # Twin-Flow partial offload (reference ZeRO-Offload++,
        # blogs/deepspeed-offloadpp: ``offload_optimizer.ratio`` = fraction of
        # parameters whose optimizer step runs on the CPU side; the rest
        # update on-accelerator and skip the host round-trip entirely).
        ratio = float(self._offload_cfg.ratio) if self._offload_cfg else 1.0
        self._twin_ratio = None
        if ratio > 1.0:
            raise ValueError(f"offload_optimizer.ratio={ratio}: must be in (0, 1]")
        if ratio < 1.0:
            if not 0.0 < ratio:
                raise ValueError(
                    f"offload_optimizer.ratio={ratio}: must be in (0, 1] — "
                    "for a fully on-device optimizer drop the offload_optimizer "
                    "section instead of ratio<=0")
            if self.offload_mode != "host-jit":
                raise ValueError(
                    f"offload_optimizer.ratio={ratio} (Twin-Flow partial offload) "
                    f"requires the host-jit cpu offload mode; mode={self.offload_mode!r} "
                    "(nvme swaps the whole state; 'memories' has no split step)")
            if self._offload_param_cfg and self._offload_param_cfg.device != "none":
                raise NotImplementedError(
                    "offload_param does not compose with Twin-Flow partial "
                    "optimizer offload (ratio < 1): param offload clears the "
                    "device bf16 copy every step, which the partial path keeps "
                    "resident — use ratio=1.0 with offload_param")
            self._twin_ratio = ratio
            if self._accum_dtype == jnp.bfloat16:
                # A silently-dead knob is worse than a warning (the
                # prescale_gradients stance): Twin-Flow's stats/partition
                # programs require fp32 gradients, so the bf16-accumulation
                # request cannot be honored on this path.
                logger.warning(
                    "bf16.accumulate_grads_in_fp32=false is ignored with "
                    f"Twin-Flow partial offload (offload_optimizer.ratio={ratio}): "
                    "the split stats/partition programs accumulate gradients in "
                    "fp32 — the host gradient transfer is NOT halved. Drop the "
                    "knob, or use ratio=1.0 (full offload) to keep bf16 "
                    "accumulation.")
        log_dist(
            f"ZeRO-Offload enabled: mode={self.offload_mode} device={dev}"
            + (f" twin_flow_ratio={ratio}" if self._twin_ratio is not None else ""),
            ranks=[0])

    # --------------------------------------------------------- diagnostics
    def _setup_diagnostics(self) -> None:
        """Build the DiagnosticsManager (``diagnostics`` config block) and
        fold the health-probe EMA state into the train state.

        Runs AFTER ``_init_state`` (it extends state/state_sharding) and
        BEFORE step compilation (the probes trace into the step; the
        recompile detector wraps the jitted callables). Disabled => the
        engine keeps ``diagnostics = None``, ``state.health = None``, and
        compiles a program identical to the no-diagnostics build."""
        self.diagnostics = None
        self._health = None
        dcfg = self.config.model.diagnostics
        if not dcfg.enabled:
            return
        from deepspeed_tpu.diagnostics.manager import DiagnosticsManager

        self.diagnostics = DiagnosticsManager(dcfg, fp16=self.fp16)
        if self._twin_ratio is not None and self.diagnostics.health is not None:
            # A silently-dead knob is worse than a warning (the
            # prescale_gradients stance): the Twin-Flow split update bypasses
            # the shared update math the probes live in.
            logger.warning(
                "diagnostics.health is not wired into the Twin-Flow split "
                "update (offload_optimizer.ratio < 1): health probes disabled "
                "for this engine; recompile/step-time/flight-recorder stay on")
            self.diagnostics.health = None
        self._health = self.diagnostics.health
        if self._health is not None:
            sh = self._health_sharding()
            hstate = jax.device_put(self._health.init_state(), sh)
            self.state = self.state._replace(health=hstate)
            self.state_sharding = self.state_sharding._replace(
                health=jax.tree_util.tree_map(lambda _: sh, hstate))
        if self.diagnostics.flight_recorder is not None:
            self.diagnostics.flight_recorder.set_context(
                mesh=dict(self.mesh.shape),
                zero_stage=self.zero_config.stage,
                dtype=self.compute_dtype.__name__,
                train_batch_size=self.config.train_batch_size,
                gradient_accumulation_steps=self.config.gradient_accumulation_steps,
                offload_mode=self.offload_mode,
            )
        log_dist(
            "diagnostics enabled: health="
            + (",".join(f"{s}={p}" for s, p in self._health.policies.items())
               if self._health else "off")
            + f" recompile={dcfg.recompile.enabled}"
            + f" step_time={dcfg.step_time.enabled}"
            + f" flight_recorder={dcfg.flight_recorder.enabled}",
            ranks=[0])

    def _health_sharding(self):
        """Placement of the health-probe EMA state (host-committed on the
        split offload paths, replicated on the mesh otherwise)."""
        if self.offload_mode in ("host-jit", "nvme"):
            from jax.sharding import SingleDeviceSharding

            return SingleDeviceSharding(self._host_device)
        return NamedSharding(self.mesh, PartitionSpec())

    def reset_health(self) -> None:
        """Re-arm the health monitor: fresh EMA baselines in ``state.health``.

        Called by the auto-recovery loop after a rewind — the restored run
        re-warms its spike statistics instead of being judged against the
        baselines that led up to the abort. No-op when health probes are off.
        """
        if self._health is None or self.state.health is None:
            return
        self.state = self.state._replace(
            health=jax.device_put(self._health.init_state(), self._health_sharding()))

    # ---------------------------------------------------- numerics observatory
    def _setup_numerics(self) -> None:
        """Configure the process-global numerics observatory (``numerics``
        config block) and fold the divergence-sentinel state into the train
        state. Runs AFTER ``_setup_diagnostics`` (drift/divergence arm its
        profiler capture) and BEFORE step compilation (the sentinel traces
        into the step). Disabled => ``state.numerics = None`` and the
        compiled program is identical to the no-numerics build."""
        self._numerics = None
        self._numerics_sentinel = None
        ncfg = self.config.model.numerics
        if not ncfg.enabled:
            # process-global hygiene: an engine that does not enable it must
            # not inherit a previous engine's alarms
            _num_mod = sys.modules.get("deepspeed_tpu.telemetry.numerics")
            if _num_mod is not None and _num_mod.enabled():
                _num_mod.configure(enabled=False)
            return
        from deepspeed_tpu.telemetry import numerics as numerics_mod

        obs = numerics_mod.configure(
            enabled=True, sample_every=ncfg.sample_every,
            sentinel=ncfg.sentinel,
            sentinel_sample_every=ncfg.sentinel_sample_every,
            divergence_policy=ncfg.divergence_policy,
            spec_accept_window=ncfg.spec_accept_window,
            spec_accept_mads=ncfg.spec_accept_mads,
            spec_accept_min_n=ncfg.spec_accept_min_n)
        pc = (self.diagnostics.profiler_capture
              if self.diagnostics is not None else None)
        obs.install(profiler_arm=pc.arm if pc is not None else None)
        self._numerics = obs
        sentinel_on = ncfg.sentinel
        if sentinel_on and self.offload_mode in ("host-jit", "nvme"):
            # the digest shard_map needs the device mesh; the split-offload
            # update runs on the host backend (Twin-Flow health precedent:
            # a silently-dead knob is worse than a warning)
            logger.warning(
                "numerics.sentinel is not wired into the host-offload "
                "update paths (offload device=cpu/nvme): divergence "
                "sentinel disabled for this engine; the residual gauges and "
                "serving probes stay on")
            sentinel_on = False
        if sentinel_on:
            specs = jax.tree_util.tree_map(
                lambda sh: getattr(sh, "spec", PartitionSpec()),
                self.param_sharding)
            self._numerics_sentinel = numerics_mod.DivergenceSentinel(
                self.mesh, specs,
                sample_every=ncfg.sentinel_sample_every)
            rep = NamedSharding(self.mesh, PartitionSpec())
            nstate = jax.device_put(
                numerics_mod.DivergenceSentinel.init_state(), rep)
            self.state = self.state._replace(numerics=nstate)
            self.state_sharding = self.state_sharding._replace(
                numerics=jax.tree_util.tree_map(lambda _: rep, nstate))
        log_dist(
            f"numerics observatory enabled: sample_every={ncfg.sample_every} "
            f"sentinel={'on' if self._numerics_sentinel is not None else 'off'}"
            f" (every {ncfg.sentinel_sample_every})"
            f" policy={ncfg.divergence_policy}",
            ranks=[0])

    def _numerics_on_step(self, step: int) -> None:
        """Sampled host plane of the numerics observatory: LoCo
        EF-residual gauges and the sentinel's divergence fold
        (policy ``log`` | ``abort``). The sentinel's event counter is
        LATCHED in the carried state, so a host check can never miss a
        detection — only see it a sample late."""
        nm = self._numerics
        ncfg = self.config.model.numerics
        st = self.state
        if st.numerics is not None:
            every = max(1, int(ncfg.sentinel_sample_every))
            # batch N runs the device probe at pre-increment step N-1
            if (step - 1) % every == 0:
                events, checksum = jax.device_get(
                    (st.numerics.events, st.numerics.checksum))
                new = nm.note_divergence_events(
                    step, int(events), int(checksum) & 0xFFFFFFFF)
                if new > 0 and ncfg.divergence_policy == "abort":
                    from deepspeed_tpu.diagnostics.manager import (
                        TrainingHealthError)

                    dump_path = (self.diagnostics.dump(
                        reason="numerics_divergence")
                        if self.diagnostics is not None else None)
                    raise TrainingHealthError(
                        f"numerics divergence abort at step {step}: "
                        f"cross-replica digest mismatch "
                        f"({int(events)} cumulative event(s))",
                        step, {"numerics/divergence_events": int(events)},
                        dump_path)
        if (ncfg.sample_every > 0 and step % ncfg.sample_every == 0
                and st.comm_error is not None):
            nm.note_ef_residuals(st.comm_error)

    def _wrap_jit(self, name: str, fn: Callable, arg_names=None) -> Callable:
        """Recompile-detector wrap for a jitted callable (identity when
        diagnostics/recompile checking is off).

        With diagnostics off but telemetry on, the compiled-program registry
        still wants the wrap point (telemetry/programs.py) — its watcher does
        the same two cache-size probes and captures only on compile. With
        both off the callable is returned untouched (byte-identical
        dispatch, the zero-overhead contract)."""
        if self.diagnostics is not None:
            return self.diagnostics.wrap_jit(name, fn, arg_names=arg_names)
        tcfg = self.config.model.telemetry
        if fn is not None and tcfg.programs:
            from deepspeed_tpu.telemetry.programs import get_program_registry

            registry = get_program_registry()
            if tcfg.enabled or registry.enabled:
                return registry.wrap(fn, name, hbm_scope="train")
        return fn

    @staticmethod
    def _build_engine_mesh(config) -> Mesh:
        """Mesh from config, with the MiCS sub-group split applied.

        ``mics_shard_size=m`` (reference ``zero/mics.py:64 MiCS_Init`` +
        ``zero/config.py:326``) shards params within groups of m devices and
        replicates across groups. On a mesh that IS a re-factoring of the
        fsdp axis: fsdp becomes m (the shard group) and the leftover factor
        folds into dp (pure replication + gradient averaging), so the
        hierarchical/2-hop gather machinery reduces to an allgather over a
        smaller, ICI-contiguous axis.
        """
        base = build_mesh(config.mesh_config)
        m = config.zero_config.mics_shard_size
        hpz = config.zero_config.zero_hpz_partition_size
        if m and m > 0 and hpz > 1:
            raise ValueError("mics_shard_size and zero_hpz_partition_size are mutually exclusive")
        if (m is None or m <= 0) and hpz > 1:
            # hpZ re-factors the mesh the same way (fsdp -> intra-node group);
            # the placement difference (masters stay sharded over the FULL
            # data world) is applied in _init_state.
            m = hpz
        if m is None or m <= 0:
            return base
        if config.zero_config.stage < 3:
            raise ValueError(
                "mics_shard_size / zero_hpz_partition_size require ZeRO stage 3 (sharded parameters)"
            )
        F = base.shape["fsdp"]
        if F == m:
            return base
        world = F * base.shape["dp"]  # the sub-group draws from the data world
        if world % m:
            raise ValueError(
                f"shard-group size {m} must divide the data world {world} (dp x fsdp)"
            )
        sizes = dict(base.shape)
        sizes["fsdp"] = m
        sizes["dp"] = world // m
        if config.zero_config.mics_hierarchical_params_gather:
            log_dist(
                "mics_hierarchical_params_gather: the intra-group allgather is "
                "inherent to the fsdp-subgroup mesh; no extra hop needed", ranks=[0],
            )
        # re-mesh through a copied MeshConfig so multi-slice handling
        # (num_slices / dcn_axis hybrid device order) survives: the MiCS shard
        # group must stay ICI-contiguous — that IS the point of the knob
        mics_cfg = config.mesh_config.model_copy(update=sizes)
        return build_mesh(mics_cfg)

    def _build_lr_schedule(self, client_sched) -> Tuple[Schedule, Any]:
        if client_sched is not None and callable(client_sched):
            return client_sched, client_sched
        sched_cfg = self.config.model.scheduler
        base_lr = None
        if self.config.model.optimizer is not None:
            base_lr = self.config.model.optimizer.params.get("lr")
        if sched_cfg is not None and sched_cfg.type:
            return get_lr_schedule(sched_cfg.type, sched_cfg.params, base_lr=base_lr), None
        return constant_schedule(base_lr if base_lr is not None else 1e-3), None

    def _check_hbm_budget(self, mcfg) -> None:
        """Pre-flight fit check: estimated per-device state bytes vs device
        memory, BEFORE ``_init_state`` materializes anything — a plain
        out-of-memory refusal that names the estimate, instead of an
        allocator error partway through placement.

        Warn-only by default; ``hbm_guard.enabled=true`` refuses with the
        estimate in the error. No-op when the device budget is undiscoverable
        (CPU backends) and no override is configured."""
        gcfg = self.config.model.hbm_guard
        self._hbm_estimate_bytes = None
        # the estimate is also the calibration baseline the compiled-program
        # registry reconciles XLA's memory_analysis against (hbm/estimate_
        # ratio) — compute it when either consumer is live
        want_calibration = (self.config.model.telemetry.enabled
                            and self.config.model.telemetry.programs)
        if not (gcfg.enabled or gcfg.warn or want_calibration):
            return
        from deepspeed_tpu.autotuning.autotuner import estimate_state_memory
        from deepspeed_tpu.ops.attention import resolves_to_flash
        from deepspeed_tpu.utils.hbm import check_hbm_fit

        try:
            shapes = jax.eval_shape(self.model.init_fn, jax.random.PRNGKey(0))
            n_params = int(sum(np.prod(x.shape)
                               for x in jax.tree_util.tree_leaves(shapes)))
        except Exception as e:  # noqa: BLE001 — the guard is best-effort
            logger.debug(f"hbm_guard: shape probe failed ({e}); skipping")
            return
        offloaded = self.offload_mode in ("host-jit", "nvme")
        compute_b = jnp.dtype(self.compute_dtype).itemsize
        need = estimate_state_memory(
            n_params,
            self.zero_config.stage,
            get_data_parallel_world_size(self.mesh),
            # offload keeps fp32 masters + moments on host; the device holds
            # only the compute-dtype copy + the gradient accumulator
            dtype_bytes=0 if offloaded else 4,
            opt_factor=0 if offloaded else 2,
            compute_dtype_bytes=compute_b,
            accum_dtype_bytes=jnp.dtype(self._accum_dtype).itemsize,
            micro_batch=self.config.train_micro_batch_size_per_gpu or 0,
            seq_len=getattr(mcfg, "max_seq_len", 0) or 0,
            hidden_size=getattr(mcfg, "hidden_size", 0) or 0,
            num_layers=getattr(mcfg, "num_layers", 0) or 0,
            vocab_size=getattr(mcfg, "vocab_size", 0) or 0,
            num_heads=getattr(mcfg, "num_heads", 0) or 0,
            remat=bool(getattr(mcfg, "remat", True)),
            fused_ce=bool(getattr(mcfg, "fused_ce", False)),
            # flash attention never materializes the score matrix, so the
            # attention temp-workspace term vanishes. Ask the ops registry
            # which implementation would actually dispatch for this
            # attn_impl — if the Pallas kernel cannot serve the config the
            # estimate must keep the score-matrix workspace term
            flash_attention=resolves_to_flash(
                getattr(mcfg, "attn_impl", "auto")),
        )
        self._hbm_estimate_bytes = int(need)
        from deepspeed_tpu.telemetry.programs import get_program_registry

        get_program_registry().set_hbm_estimate(need, scope="train")
        if not (gcfg.enabled or gcfg.warn):
            return  # calibration-only probe: the guard itself is off
        override = (int(gcfg.device_memory_gb * (1 << 30))
                    if gcfg.device_memory_gb else None)
        check_hbm_fit(
            need,
            what=f"engine init ({n_params / 1e6:.0f}M params, "
                 f"zero_stage={self.zero_config.stage})",
            mode="refuse" if gcfg.enabled else "warn",
            device_memory=override,
            headroom=gcfg.headroom,
        )

    def _init_state(self, model_parameters, seed: int) -> None:
        mesh = self.mesh
        rng = jax.random.PRNGKey(seed)

        init_rng = None
        if model_parameters is None:
            init_rng, rng = jax.random.split(rng)
            # Sharded construction (the zero.Init analog,
            # partition_parameters.py:825): shapes come from eval_shape (no
            # compute), shardings are derived from them, and the actual init
            # runs ONCE under jit with out_shardings — every leaf materializes
            # directly in its target placement, so models larger than host RAM
            # can be constructed. The eager init-then-place path remains for
            # caller-provided params (e.g. HF ingestion) and host offload.
            param_shapes = jax.eval_shape(
                lambda r: cast_floating(self.model.init_fn(r), jnp.float32), init_rng
            )
            master_f32 = None
        else:
            master_f32 = cast_floating(model_parameters, jnp.float32)
            param_shapes = jax.eval_shape(lambda: master_f32)

        # Model-parallel base placements (AutoTP rules) — ZeRO composes on top.
        base_specs = self._build_base_specs(param_shapes)
        self._base_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), base_specs
        )
        self._hpz_compute_sharding = None
        if self.zero_config.stage >= 3 and self.zero_config.zero_hpz_partition_size > 1:
            # ZeRO++ hpZ (zero/config.py:294, utils/groups.py:650): masters
            # keep the FULL data-world partition (dp x fsdp jointly — maximal
            # ZeRO-3 memory win); compute params constrain to a SECONDARY
            # partition over the (re-meshed, ICI-local) fsdp axis only. One
            # cross-group gather materializes the secondary copy per step;
            # every per-layer allgather then rides the intra-node axis.
            self.param_sharding = zero_mod.master_sharding(param_shapes, mesh, self.zero_config, base_specs)
            self._hpz_compute_sharding = zero_mod.params_sharding(
                param_shapes, mesh, self.zero_config, base_specs
            )
        elif self.zero_config.stage >= 3:
            # Stage 3: master params use the fsdp param placement so compute
            # params inherit it without an extra reshard.
            self.param_sharding = zero_mod.params_sharding(param_shapes, mesh, self.zero_config, base_specs)
        elif self.zero_config.stage >= 1:
            self.param_sharding = zero_mod.master_sharding(param_shapes, mesh, self.zero_config, base_specs)
        else:
            self.param_sharding = self._base_shardings

        # Device placement of the bf16 COMPUTE params (also the master
        # placement unless offload moves the masters off-device).
        self._device_param_sharding = self.param_sharding
        # ZeRO-3 over fsdp > 1: a scanned layer's products read their weight's
        # placement here and gather it themselves (zero.ScanGathers)
        self._scan_gathers = zero_mod.scan_gathers(
            self._hpz_compute_sharding or self._device_param_sharding, param_shapes, mesh
        ) if self.zero_config.stage >= 3 else None
        if self.offload_mode == "memories":
            # Masters + moments live in host memory inside the one compiled
            # step; XLA streams them (reference: CPU optimizer partition).
            self.param_sharding = jax.tree_util.tree_map(
                lambda sh: sh.with_memory_kind("pinned_host"), self.param_sharding
            )
        elif self.offload_mode in ("host-jit", "nvme"):
            from jax.sharding import SingleDeviceSharding

            host_sh = SingleDeviceSharding(self._host_device)
            if self._twin_ratio is not None:
                # Twin-Flow: the first `ratio` fraction of master bytes (in
                # stable tree-flatten order) updates host-side; the rest
                # keeps its on-mesh master placement and updates in a fused
                # device program (reference ZeRO-Offload++ Twin-Flow).
                leaves, treedef = jax.tree_util.tree_flatten(param_shapes)
                sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves]
                total = sum(sizes)
                flags, cum = [], 0
                for s in sizes:
                    flags.append(cum < self._twin_ratio * total)
                    cum += s
                self._tf_host_mask = jax.tree_util.tree_unflatten(treedef, flags)
                self.param_sharding = jax.tree_util.tree_map(
                    lambda m, sh: host_sh if m else sh,
                    self._tf_host_mask, self._device_param_sharding)
                n_host = sum(s for s, m in zip(sizes, flags) if m)
                log_dist(
                    f"Twin-Flow split: {n_host / max(total, 1):.1%} of "
                    f"{total / 1e6:.1f}M master params update host-side "
                    f"(ratio={self._twin_ratio})", ranks=[0])
            else:
                self.param_sharding = jax.tree_util.tree_map(lambda _: host_sh, param_shapes)

        if master_f32 is not None:
            # unaliased: user-supplied initial params are often host numpy;
            # zero-copy device_put + the donated step is the PR-1 landmine
            from deepspeed_tpu.utils.compat import device_put_unaliased

            params = jax.tree_util.tree_map(
                device_put_unaliased, master_f32, self.param_sharding)
        elif self.offload_mode in ("host-jit", "nvme"):
            # host-resident masters: eager init lands on host anyway
            params = jax.device_put(
                cast_floating(self.model.init_fn(init_rng), jnp.float32), self.param_sharding
            )
        else:
            # sharded construction: leaves materialize pre-placed (zero.Init)
            params = jax.jit(
                lambda r: cast_floating(self.model.init_fn(r), jnp.float32),
                out_shardings=self.param_sharding,
            )(init_rng)

        opt_shapes = jax.eval_shape(self.tx.init, params)
        if self.offload_mode in ("host-jit", "nvme"):
            from jax.sharding import SingleDeviceSharding

            host_sh = SingleDeviceSharding(self._host_device)
            if self._twin_ratio is not None:
                # Two structure-preserving masked views of the ONE optimizer:
                # each partition's state keeps the param-tree shape with
                # optax.MaskedNode holes for the other partition, so the
                # fragment/checkpoint walkers still see param-shaped moment
                # trees. Out-of-partition leaves are fed as 0-d dummies the
                # masked transform never reads.
                self._tf_dev_mask = jax.tree_util.tree_map(
                    lambda m: not m, self._tf_host_mask)
                self._tf_tx_host = optax.masked(self.tx, self._tf_host_mask)
                self._tf_tx_dev = optax.masked(self.tx, self._tf_dev_mask)
                host_sub = self._tf_partition(params, host_side=True)
                dev_sub = self._tf_partition(params, host_side=False)
                opt_host = jax.jit(self._tf_tx_host.init)(host_sub)  # cpu backend
                opt_dev = jax.jit(self._tf_tx_dev.init)(dev_sub)
                opt_state = (opt_host, opt_dev)
                self.opt_sharding = jax.tree_util.tree_map(
                    lambda x: x.sharding, opt_state)
            else:
                self.opt_sharding = jax.tree_util.tree_map(lambda _: host_sh, opt_shapes)
                # out_shardings COMMITS the moments to the host device. A bare
                # jit leaves its outputs uncommitted, while every later
                # offload_update_step output is committed — that placement
                # flip recompiled the host update once on call 2 (found by
                # the PR-2 RecompileDetector).
                opt_state = jax.jit(
                    self.tx.init, out_shardings=self.opt_sharding
                )(params)  # inputs committed to host => runs on the cpu backend
            ls_state = make_loss_scale_state(
                enabled=self.fp16,
                initial_scale_power=self.config.model.fp16.initial_scale_power,
                static_loss_scale=self.config.model.fp16.loss_scale,
                hysteresis=self.config.model.fp16.hysteresis,
            )
            ls_state = jax.device_put(ls_state, host_sh)
            self.state = TrainState(
                step=jax.device_put(jnp.zeros((), jnp.int32), host_sh),
                params=params,
                opt_state=opt_state,
                loss_scale=ls_state,
                rng=jax.device_put(jax.random.key_data(rng), host_sh),
            )
            self.state_sharding = TrainState(
                step=host_sh,
                params=self.param_sharding,
                opt_state=self.opt_sharding,
                loss_scale=jax.tree_util.tree_map(lambda _: host_sh, ls_state),
                rng=host_sh,
            )
            self.grad_sharding = zero_mod.grads_sharding(param_shapes, mesh, self.zero_config, base_specs)
            self._compute_dev = None  # bf16 device params, materialized lazily
            self._opt_on_nvme = False
            return

        replicated_sh = NamedSharding(mesh, PartitionSpec())
        try:
            # Optimizer moments inherit their parameter's placement exactly
            # (no resharding in the update); non-param leaves replicate.
            self.opt_sharding = optax.tree_map_params(
                self.tx,
                lambda _leaf, sh: sh,
                opt_shapes,
                self.param_sharding,
                transform_non_params=lambda _leaf: replicated_sh,
            )
        except Exception as e:
            # Custom client transforms that tree_map_params cannot traverse:
            # fall back to the shape-based data-axes rule. This loses any
            # model-parallel (tp) placement for the moments (opt-state tree
            # structure differs from params, so base specs cannot be mapped),
            # costing a reshard per update — make it visible.
            logger.warning(
                f"optimizer-state placement fell back to the shape-based rule "
                f"(tree_map_params failed: {type(e).__name__}: {e}); tp placements "
                f"are not propagated to optimizer moments"
            )
            self.opt_sharding = zero_mod.master_sharding(opt_shapes, mesh, self.zero_config)
        if self.offload_mode == "memories":
            self.opt_sharding = jax.tree_util.tree_map(
                lambda sh: sh.with_memory_kind("pinned_host"), self.opt_sharding
            )
        opt_state = jax.jit(self.tx.init, out_shardings=self.opt_sharding)(params)

        ls_state = make_loss_scale_state(
            enabled=self.fp16,
            initial_scale_power=self.config.model.fp16.initial_scale_power,
            static_loss_scale=self.config.model.fp16.loss_scale,
            hysteresis=self.config.model.fp16.hysteresis,
        )
        replicated = NamedSharding(mesh, PartitionSpec())
        ls_state = jax.device_put(ls_state, replicated)

        self.state = TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), replicated),
            params=params,
            opt_state=opt_state,
            loss_scale=ls_state,
            rng=jax.device_put(jax.random.key_data(rng), replicated),
        )
        self.state_sharding = TrainState(
            step=replicated,
            params=self.param_sharding,
            opt_state=self.opt_sharding,
            loss_scale=jax.tree_util.tree_map(lambda _: replicated, ls_state),
            rng=replicated,
        )
        self.grad_sharding = zero_mod.grads_sharding(param_shapes, mesh, self.zero_config, base_specs)

        err_live = None
        if getattr(self, "_onebit", None):
            err_live = self._onebit
        elif getattr(self, "_zpp", None) and self._zpp[3]:
            err_live = self._zpp[0]  # ZeRO++ LoCo residuals, same layout
        if err_live:
            # per-rank error-feedback residuals: [dp_world, *shape], dim 0
            # sharded over the live data axes (each rank owns its own slice)
            live = err_live
            live_entry = live if len(live) > 1 else live[0]
            W = 1
            for a in live:
                W *= mesh.shape[a]
            err_sharding = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, PartitionSpec(live_entry)), param_shapes
            )
            errors = jax.jit(
                lambda: jax.tree_util.tree_map(
                    lambda l: jnp.zeros((W,) + tuple(l.shape), jnp.float32), param_shapes
                ),
                out_shardings=err_sharding,
            )()
            self.state = self.state._replace(comm_error=errors)
            self.state_sharding = self.state_sharding._replace(comm_error=err_sharding)

    def _build_base_specs(self, param_shapes) -> Any:
        """Per-param model-parallel PartitionSpecs from the model's rules."""
        rules = self.model.partition_rules
        if rules is None:
            return jax.tree_util.tree_map(lambda _: PartitionSpec(), param_shapes)

        def one(key_path, leaf):
            spec = rules(jax.tree_util.keystr(key_path), tuple(leaf.shape))
            return spec if spec is not None else PartitionSpec()

        return jax.tree_util.tree_map_with_path(one, param_shapes)

    @property
    def zero_gather_mb(self) -> int:
        """MB a chip receives a micro-step from the gathers the layer scan
        states itself under ZeRO-3, forward and backward; 0 where it states none."""
        return round(self._scan_gathers.received_bytes / 1e6) if self._scan_gathers is not None else 0

    # ----------------------------------------------------------- train step
    def _loss_and_aux(self, params, batch, rng):
        loss_fn = self.model.loss_fn
        ac_cfg = self.config.model.activation_checkpointing
        if ac_cfg.enabled:
            # remat policy applied to the whole loss: XLA re-schedules the
            # recompute (reference activation_checkpointing/checkpointing.py:948)
            from deepspeed_tpu.runtime.activation_checkpointing import (
                apply_activation_checkpointing,
            )

            loss_fn = apply_activation_checkpointing(loss_fn, ac_cfg)
        out = loss_fn(params, batch, rng)
        if isinstance(out, tuple):
            return out[0], out[1:]
        return out, ()

    @jax.named_scope("optimizer")  # the 16-bit recast is the update's last part
    def _compute_params(self, master_params):
        compute = cast_floating(master_params, self.compute_dtype)
        if self.offload_mode == "memories":
            # Masters live in pinned host memory: pin the bf16 copies to
            # DEVICE memory explicitly so the whole forward doesn't try to
            # consume host-resident buffers.
            compute = jax.lax.with_sharding_constraint(compute, self._device_param_sharding)
        if self.zero_config.stage in (1, 2):
            # Updated shards -> full weights: the stage-1/2 post-step allgather
            # (reference stage_1_and_2.py:1835ff), done in 16-bit. Model-
            # parallel (tp) placements are preserved; only data-axis shards
            # gather.
            compute = jax.lax.with_sharding_constraint(compute, self._base_shardings)
        elif self._hpz_compute_sharding is not None:
            # hpZ secondary partition: one gather across the dp groups here;
            # per-layer gathers downstream ride only the intra-node fsdp axis
            compute = jax.lax.with_sharding_constraint(compute, self._hpz_compute_sharding)
        return compute

    def _zpp_config(self):
        """(live_axes, qw, qg) when ZeRO++ collectives should be active."""
        from deepspeed_tpu.topology.mesh import BATCH_AXES

        zc = self.zero_config
        qw, qg = zc.zero_quantized_weights, zc.zero_quantized_gradients
        if zc.zero_hpz_partition_size > 1 and (qw or qg):
            raise NotImplementedError(
                "hpZ (zero_hpz_partition_size) + quantized collectives "
                "(qwZ/qgZ) are not composed yet: the quantized gather path "
                "bypasses the secondary-partition constraint; enable one"
            )
        if not (qw or qg):
            if zc.loco_param:
                raise ValueError("loco_param requires zero_quantized_gradients: true "
                                 "(LoCo compensates the qgZ wire)")
            return None
        if qg and zc.stage < 2:
            raise ValueError("zero_quantized_gradients requires ZeRO stage >= 2 (sharded gradients)")
        loco = dict(zc.loco_param) if zc.loco_param else None
        if loco and not qg:
            raise ValueError("loco_param requires zero_quantized_gradients: true")
        live = tuple(a for a in BATCH_AXES if self.mesh.shape[a] > 1)
        if not live:
            logger.warning("ZeRO++ quantized collectives requested but no data-parallel axis > 1; ignored")
            return None
        return live, qw, qg, loco

    def _build_zpp_micro_fn(self, live, qw: bool, qg: bool, loco=None) -> Callable:
        """Micro-batch gradient fn with addressable (quantized) collectives.

        Runs the loss inside a partial-manual shard_map (data axes manual,
        model axes auto): weights enter as their master-layout shards, are
        gathered through ``sharded_weight_gather`` (int8 when qwZ), and its
        custom VJP reduce-scatters the gradients back (int8 all-to-all when
        qgZ). Reference: coalesced_collectives.py:31, partition_parameters.py:1200.

        ``loco`` ({"err_beta": float, ...}) switches qgZ to the LoCo
        error-feedback reduce (reference coalesced_collectives.py:81): the fn
        then takes/returns per-rank residual buffers (``state.comm_error``),
        stored in TRUE gradient units so loss-scale changes can't corrupt them.
        """
        from deepspeed_tpu.parallel import zeropp

        mesh = self.mesh

        def _manual_only(spec: PartitionSpec) -> PartitionSpec:
            entries = []
            for e in spec:
                if e is None:
                    entries.append(None)
                    continue
                names = e if isinstance(e, tuple) else (e,)
                keep = tuple(a for a in names if a in live)
                entries.append(keep if len(keep) > 1 else (keep[0] if keep else None))
            return PartitionSpec(*entries)

        master_specs = jax.tree_util.tree_map(lambda sh: sh.spec, self.param_sharding)
        param_in_specs = jax.tree_util.tree_map(_manual_only, master_specs)
        plans = jax.tree_util.tree_map(lambda s: zeropp.leaf_comm_plan(s, live), param_in_specs)
        grad_out_specs = jax.tree_util.tree_map(
            lambda p: PartitionSpec(*[
                (p.axes if len(p.axes) > 1 else p.axes[0]) if d == p.dim else None
                for d in range(p.dim + 1)
            ]) if p.sharded else PartitionSpec(),
            plans,
        )
        batch_spec = PartitionSpec(live if len(live) > 1 else live[0])

        from deepspeed_tpu.utils.compat import shard_map

        if loco:
            err_beta = float(loco.get("err_beta", 0.8))
            live_entry = live if len(live) > 1 else live[0]
            err_specs = jax.tree_util.tree_map(
                lambda _: PartitionSpec(live_entry), plans)

            def local_fn_loco(param_shards, err_blocks, micro, scale, inv, step_rng):
                r = jax.random.fold_in(
                    jax.random.wrap_key_data(step_rng), jax.lax.axis_index(live)
                )
                errs = jax.tree_util.tree_map(lambda e: e[0], err_blocks)

                def scaled_loss(shards_errs, b, rr):
                    shards, errs_ = shards_errs
                    full = zeropp.gather_params_for_compute(
                        shards, plans, qw, qg, live_axes=live,
                        errors=errs_, err_beta=err_beta, inv=inv)
                    loss, _aux = self._loss_and_aux(full, b, rr)
                    return (loss.astype(jnp.float32) * scale).astype(
                        self.compute_dtype if self.fp16 else jnp.float32), loss

                (_, loss), (grads, new_errs) = jax.value_and_grad(
                    scaled_loss, has_aux=True)((param_shards, errs), micro, r)
                grads = cast_floating(grads, jnp.float32)
                grads = jax.tree_util.tree_map(
                    lambda g, p: g if p.sharded else _facade_grad_mean(g, live),
                    grads, plans
                )
                new_errs = jax.tree_util.tree_map(lambda e: e[None].astype(jnp.float32),
                                                  new_errs)
                return grads, new_errs, jax.lax.pmean(loss, live)

            return shard_map(
                local_fn_loco,
                mesh=mesh,
                in_specs=(param_in_specs, err_specs, batch_spec,
                          PartitionSpec(), PartitionSpec(), PartitionSpec()),
                out_specs=(grad_out_specs, err_specs, PartitionSpec()),
                axis_names=set(live),
                check_vma=False,
            )

        def local_fn(param_shards, micro, scale, step_rng):
            # de-correlate dropout across data ranks
            r = jax.random.fold_in(
                jax.random.wrap_key_data(step_rng), jax.lax.axis_index(live)
            )

            def scaled_loss(shards, b, rr):
                full = zeropp.gather_params_for_compute(
                    shards, plans, qw, qg, live_axes=live)
                loss, _aux = self._loss_and_aux(full, b, rr)
                return (loss.astype(jnp.float32) * scale).astype(self.compute_dtype if self.fp16 else jnp.float32), loss

            (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(param_shards, micro, r)
            grads = cast_floating(grads, jnp.float32)
            grads = jax.tree_util.tree_map(
                lambda g, p: g if p.sharded else _facade_grad_mean(g, live), grads, plans
            )
            return grads, jax.lax.pmean(loss, live)

        return shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(param_in_specs, batch_spec, PartitionSpec(), PartitionSpec()),
            out_specs=(grad_out_specs, PartitionSpec()),
            axis_names=set(live),
            check_vma=False,
        )

    def _onebit_config(self):
        """Live data axes when 1-bit compressed gradient allreduce is active.

        Triggered by ``gradient_compression.enabled`` or a OneBit optimizer
        name (reference OnebitAdam/OnebitLamb/ZeroOneAdam,
        ``runtime/comm/nccl.py compressed_allreduce``). Validates composition
        at construction — dead/lying knobs are worse than errors."""
        from deepspeed_tpu.topology.mesh import BATCH_AXES

        gc = self.config.model.gradient_compression
        opt = self.config.model.optimizer
        opt_name = opt.type.lower().replace("_", "") if opt else ""
        onebit_opt = opt_name in ("onebitadam", "onebitlamb", "zerooneadam")
        if not (gc.enabled or onebit_opt):
            return None
        if gc.enabled and gc.bits != 1:
            raise NotImplementedError("gradient_compression.bits must be 1 (sign compression)")
        if self.zero_config.stage >= 2:
            raise ValueError(
                "gradient_compression / OneBit optimizers need full local gradients: "
                "use ZeRO stage <= 1 (the reference 1-bit optimizers have the same constraint)"
            )
        if self._zpp:
            raise ValueError("gradient_compression does not compose with ZeRO++ quantized collectives")
        if self.offload_mode in ("host-jit", "nvme", "memories"):
            raise ValueError("gradient_compression does not compose with optimizer offload")
        live = tuple(a for a in BATCH_AXES if self.mesh.shape[a] > 1)
        if not live:
            logger.warning("gradient_compression enabled but only one data rank; compression is a no-op")
            return None
        return live

    def _build_onebit_fn(self, live) -> Callable:
        """shard_map program: local grad accumulation + sign-compressed exact-
        mean allreduce with error feedback (parallel/onebit.py)."""
        from deepspeed_tpu.utils.compat import shard_map

        from deepspeed_tpu.parallel import onebit as onebit_mod

        mesh = self.mesh

        def _manual_only(spec: PartitionSpec) -> PartitionSpec:
            entries = []
            for e in spec:
                if e is None:
                    entries.append(None)
                    continue
                names = e if isinstance(e, tuple) else (e,)
                keep = tuple(a for a in names if a in live)
                entries.append(keep if len(keep) > 1 else (keep[0] if keep else None))
            return PartitionSpec(*entries)

        base_specs = jax.tree_util.tree_map(lambda sh: sh.spec, self._base_shardings)
        param_in_specs = jax.tree_util.tree_map(_manual_only, base_specs)
        live_entry = live if len(live) > 1 else live[0]
        err_specs = jax.tree_util.tree_map(lambda _: PartitionSpec(live_entry), base_specs)
        batch_spec = PartitionSpec(None, live_entry)

        def local_fn(params, batch, scale, inv, step_rng, errors):
            r0 = jax.random.wrap_key_data(step_rng)
            rank = jax.lax.axis_index(live)

            def scaled_loss(p, b, rr):
                loss, _aux = self._loss_and_aux(p, b, rr)
                return (loss.astype(jnp.float32) * scale).astype(
                    self.compute_dtype if self.fp16 else jnp.float32
                ), loss

            grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)

            def micro_step(carry, xs):
                acc, i = carry
                r = jax.random.fold_in(jax.random.fold_in(r0, i), rank)
                (_, loss), g = grad_fn(params, xs, r)
                g = cast_floating(g, jnp.float32)
                acc = jax.tree_util.tree_map(lambda a, b: a + b, acc, g)
                return (acc, i + 1), loss

            zero = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (acc, _), losses = jax.lax.scan(micro_step, (zero, 0), batch)
            # compress in TRUE gradient units (unscale first): the residuals
            # stay valid across dynamic loss-scale changes
            acc = jax.tree_util.tree_map(lambda g: g * inv, acc)
            mean_grads, new_err = onebit_mod.compressed_grad_mean(acc, errors, live)
            return mean_grads, new_err, jax.lax.pmean(losses, live)

        return shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(param_in_specs, batch_spec, PartitionSpec(), PartitionSpec(), PartitionSpec(), err_specs),
            out_specs=(
                jax.tree_util.tree_map(lambda _: PartitionSpec(), base_specs),
                err_specs,
                PartitionSpec(),
            ),
            axis_names=set(live),
            check_vma=False,
        )

    def _build_train_step(self) -> Callable:
        gas = self.config.gradient_accumulation_steps
        clip = self.config.gradient_clipping
        fp16_cfg = self.config.model.fp16
        dynamic = self.fp16 and fp16_cfg.dynamic
        grad_pspecs = self.grad_sharding  # NamedShardings: usable without a context mesh

        zpp_fn = self._build_zpp_micro_fn(*self._zpp) if self._zpp else None
        zpp_loco = self._zpp[3] if self._zpp else None
        ob_fn = self._build_onebit_fn(self._onebit) if self._onebit else None
        # ZeRO++ micro-grads come back fp32 from the quantized collectives —
        # a bf16 carry would flip dtypes mid-scan
        accum_dtype = jnp.float32 if zpp_fn is not None else self._accum_dtype

        def train_step(state: TrainState, batch):
            rng = jax.random.wrap_key_data(state.rng)
            rng, step_rng = jax.random.split(rng)
            scale = state.loss_scale.loss_scale

            if ob_fn is not None:
                compute_params = self._compute_params(state.params)
                # inv: residuals are stored in TRUE gradient units, so a
                # dynamic-loss-scale change between steps cannot corrupt the
                # carried error feedback.
                inv = 1.0 / (gas * scale)
                grads, new_err, losses = ob_fn(
                    compute_params, batch, scale, inv, jax.random.key_data(step_rng), state.comm_error
                )
                loss_mean = jnp.mean(losses.astype(jnp.float32))
                new_state, metrics = self._update_math(
                    state, grads, jax.random.key_data(rng), grads_are_unscaled=True,
                    loss=loss_mean,
                )
                # fp16 overflow: a non-finite step would store NaN residuals
                # and poison every later step — keep the previous buffers
                # (the reference skips its error-feedback update on overflow
                # the same way). A health-policy skip keeps them too: the
                # residual update belongs to an update that never applied.
                keep = ~metrics["overflow"]
                if "health/skip" in metrics:
                    keep = keep & ~metrics["health/skip"]
                new_err = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(keep, n, o), new_err, state.comm_error
                )
                new_state = new_state._replace(comm_error=new_err)
                metrics["loss"] = loss_mean
                return new_state, metrics

            if zpp_fn is not None:
                # ZeRO++ path: compute params stay in master layout; the
                # (quantized) gather happens inside the micro fn's shard_map.
                compute_params = jax.lax.with_sharding_constraint(
                    cast_floating(state.params, self.compute_dtype), self._device_param_sharding
                )
            else:
                compute_params = self._compute_params(state.params)

            moe_stats_on = getattr(self, "_moe_metrics", False)

            def scaled_loss(p, micro, r):
                loss, _aux = self._loss_and_aux(p, micro, r)
                # MoE dispatch stats ride the grad aux (parallel/moe.py;
                # model contract: the last aux element is a dict of scalars)
                stats = (_aux[-1] if moe_stats_on and _aux
                         and isinstance(_aux[-1], dict) else None)
                return (loss.astype(jnp.float32) * scale).astype(self.compute_dtype if self.fp16 else jnp.float32), (loss, stats)

            grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)

            def micro_step(carry, micro_batch):
                acc, i = carry
                if zpp_fn is not None:
                    grads, loss = zpp_fn(
                        compute_params, micro_batch, scale, jax.random.key_data(jax.random.fold_in(step_rng, i))
                    )
                    stats = None
                else:
                    with dot_general_context(self._scan_gathers):
                        (_, (loss, stats)), grads = grad_fn(
                            compute_params, micro_batch, jax.random.fold_in(step_rng, i))
                with jax.named_scope("grad_accum"):  # the accumulator's casts and adds, in a device trace
                    if zpp_fn is None:
                        grads = cast_floating(grads, accum_dtype)
                    acc = jax.tree_util.tree_map(lambda a, g: (a + g).astype(accum_dtype), acc, grads)
                # shard the accumulator (stage>=2 => reduce-scatter per micro-batch)
                acc = jax.lax.with_sharding_constraint(acc, grad_pspecs)
                return (acc, i + 1), (loss, stats)

            with jax.named_scope("grad_accum"):
                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), state.params
                )
            zero_grads = jax.lax.with_sharding_constraint(zero_grads, grad_pspecs)

            if zpp_loco is not None:
                # LoCo (reference coalesced_collectives.py:81): residuals ride
                # the micro-step carry; reset every reset_T steps (reference
                # loco_idx > reset_T re-zeroes the buffers).
                inv_s = 1.0 / scale
                err0 = state.comm_error
                reset_T = int(zpp_loco.get("reset_T", 0) or 0)
                if reset_T:
                    do_reset = (state.step % reset_T == 0) & (state.step > 0)
                    err0 = jax.tree_util.tree_map(
                        lambda e: jnp.where(do_reset, jnp.zeros_like(e), e), err0)

                def micro_step_loco(carry, micro_batch):
                    acc, err, i = carry
                    grads, err, loss = zpp_fn(
                        compute_params, err, micro_batch, scale, inv_s,
                        jax.random.key_data(jax.random.fold_in(step_rng, i)))
                    with jax.named_scope("grad_accum"):
                        acc = jax.tree_util.tree_map(lambda a, g: a + g, acc, grads)
                    acc = jax.lax.with_sharding_constraint(acc, grad_pspecs)
                    return (acc, err, i + 1), loss

                if gas == 1:
                    (grads, new_err, _), losses = micro_step_loco(
                        (zero_grads, err0, 0),
                        jax.tree_util.tree_map(lambda x: x[0], batch))
                    losses = losses[None]
                else:
                    (grads, new_err, _), losses = jax.lax.scan(
                        micro_step_loco, (zero_grads, err0, 0), batch)

                loss_mean = jnp.mean(losses.astype(jnp.float32))
                new_state, metrics = self._update_math(
                    state, grads, jax.random.key_data(rng), loss=loss_mean)
                # overflow/health skip => keep the previous residuals (as the
                # 1-bit path)
                keep = ~metrics["overflow"]
                if "health/skip" in metrics:
                    keep = keep & ~metrics["health/skip"]
                new_err = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(keep, n, o), new_err, state.comm_error)
                new_state = new_state._replace(comm_error=new_err)
                metrics["loss"] = loss_mean
                return new_state, metrics

            if gas == 1:
                (grads, _), (losses, moe_stats) = micro_step(
                    (zero_grads, 0), jax.tree_util.tree_map(lambda x: x[0], batch))
                losses = losses[None]
            else:
                (grads, _), (losses, moe_stats) = jax.lax.scan(
                    micro_step, (zero_grads, 0), batch)

            loss_mean = jnp.mean(losses.astype(jnp.float32))
            new_state, metrics = self._update_math(
                state, grads, jax.random.key_data(rng), loss=loss_mean)
            metrics["loss"] = loss_mean
            if moe_stats is not None:
                # mean over micro-batches (scan stacked them); scalar per key
                metrics.update({
                    k: jnp.mean(jnp.asarray(v).astype(jnp.float32))
                    for k, v in moe_stats.items()})
            return new_state, metrics

        return jax.jit(
            train_step,
            in_shardings=(self.state_sharding, None),
            out_shardings=(self.state_sharding, None),
            donate_argnums=(0,),
        )

    def _update_math(self, state: TrainState, grads, new_rng_data,
                     grads_are_unscaled: bool = False,
                     loss: Any = None) -> Tuple[TrainState, Dict[str, Any]]:
        """Scale / clip / optimizer update / overflow-skip / loss-scale step.

        The ONE copy of the update semantics, traced into the fused step, the
        forward/backward/step apply program, and the offload host program —
        so the three paths cannot drift (reference ``FP16_Optimizer.step``).
        ``loss`` (optional step-mean loss) feeds the loss-spike health probe
        on paths that have it (the fused step; the offload host program and
        the apply path receive gradients only)."""
        gas = self.config.gradient_accumulation_steps
        clip = self.config.gradient_clipping
        fp16_cfg = self.config.model.fp16
        dynamic = self.fp16 and fp16_cfg.dynamic
        scale = state.loss_scale.loss_scale

        # bf16-accumulated grads upcast here, at the accumulation boundary:
        # norm/clip/optimizer math is always fp32 (no-op for fp32 grads)
        grads = cast_floating(grads, jnp.float32)
        if not grads_are_unscaled:
            inv = 1.0 / (gas * scale)
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        finite = all_finite(grads) if self.fp16 else jnp.asarray(True)
        with jax.named_scope("grad_norm"):
            gnorm = global_norm(grads)
        # Health probes (diagnostics/health.py) on the raw unscaled/unclipped
        # gradients — extends the finite/gnorm this step already computes,
        # never a second fetch. skip_step-policy signals gate the update off
        # inside the program, exactly like the fp16 overflow skip.
        health_metrics: Dict[str, Any] = {}
        new_health = state.health
        apply_ok = finite
        if self._health is not None and state.health is not None:
            new_health, health_metrics, hskip, _habort = self._health.probe(
                state.health, grads, gnorm, loss=loss, finite=finite)
            apply_ok = finite & ~hskip
        # ``optimizer`` names clip + update + apply in a device trace; the
        # unscale, norm and health probes above stay outside it
        with jax.named_scope("optimizer"):
            if clip and clip > 0:
                grads, gnorm = clip_by_global_norm(grads, clip, norm=gnorm)

            updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)

        # overflow / unhealthy => skip the update (reference
        # FP16_Optimizer.step overflow path, extended to health verdicts)
        def sel(new, old):
            return jax.tree_util.tree_map(lambda n, o: jnp.where(apply_ok, n, o), new, old)

        new_ls, new_step, metrics = self._post_update_bookkeeping(
            finite, gnorm, state.step, state.loss_scale, apply_ok=apply_ok)
        metrics.update(health_metrics)
        sel_params = sel(new_params, state.params)
        # Divergence sentinel (telemetry/numerics.py) on the COMMITTED input
        # params, not the freshly computed update: the inputs are at-rest
        # device buffers, bit-replicated by construction, so a digest
        # mismatch is real corruption — mid-step values are whatever GSPMD's
        # chosen collective schedule rounds them to per device (observed:
        # per-device reduction-order jitter flagging healthy steps). A
        # lax.cond samples 1-in-N steps; disabled traces no digest
        # (jaxpr-identical).
        new_numerics = state.numerics
        if (getattr(self, "_numerics_sentinel", None) is not None
                and state.numerics is not None):
            new_numerics, numerics_metrics = self._numerics_sentinel.probe(
                state.numerics, state.params, state.step)
            metrics.update(numerics_metrics)
        new_state = TrainState(
            step=new_step,
            params=sel_params,
            opt_state=sel(new_opt, state.opt_state),
            loss_scale=new_ls,
            rng=new_rng_data,
            comm_error=state.comm_error,
            health=new_health,
            numerics=new_numerics,
        )
        return new_state, metrics

    def _post_update_bookkeeping(self, finite, gnorm, step, ls_state, apply_ok=None):
        """Loss-scale advance + step counter + step metrics — shared by
        ``_update_math`` (fused / host-jit / apply paths) AND the Twin-Flow
        host program, so the overflow/bookkeeping semantics cannot drift
        between full and partial offload.

        ``apply_ok`` (default ``finite``) is whether the update actually
        applied — a health-policy skip advances neither the step counter nor
        the loss scale's notion of success... the loss scale stays keyed on
        ``finite`` alone: a healthy-but-skipped step is not an fp16 overflow
        and must not shrink the scale."""
        fp16_cfg = self.config.model.fp16
        dynamic = self.fp16 and fp16_cfg.dynamic
        apply_ok = finite if apply_ok is None else apply_ok
        new_ls = update_loss_scale(
            ls_state,
            finite,
            dynamic=dynamic,
            scale_window=fp16_cfg.loss_scale_window,
            min_scale=fp16_cfg.min_loss_scale,
            init_hysteresis=fp16_cfg.hysteresis,
            consecutive_hysteresis=fp16_cfg.consecutive_hysteresis,
        ) if self.fp16 else ls_state
        new_step = step + jnp.where(apply_ok, 1, 0).astype(jnp.int32)
        metrics = {
            "grad_norm": gnorm,
            "lr": jnp.asarray(self.lr_scheduler_fn(step), jnp.float32),
            "loss_scale": ls_state.loss_scale,
            "overflow": ~finite,
        }
        return new_ls, new_step, metrics

    # ----------------------------------------------------- offload split path
    def _build_offload_grad_step(self) -> Callable:
        """Device program: micro-batch grad accumulation only (no optimizer).

        Mirrors ``_build_train_step``'s accumulation exactly so offload runs
        match non-offload trajectories; the update happens on the host
        (reference ``zero/stage3.py:2082`` optimizer-swap step boundary)."""
        gas = self.config.gradient_accumulation_steps
        grad_pspecs = self.grad_sharding
        # Twin-Flow's stats/partition programs assume fp32 grads; plain
        # offload honors the bf16-accumulation knob (upcast in _update_math)
        accum_dtype = jnp.float32 if self._twin_ratio is not None else self._accum_dtype

        def grad_step(compute_params, batch, scale, step_rng):
            step_rng = jax.random.wrap_key_data(step_rng)

            def scaled_loss(p, micro, r):
                loss, _aux = self._loss_and_aux(p, micro, r)
                return (loss.astype(jnp.float32) * scale).astype(self.compute_dtype if self.fp16 else jnp.float32), loss

            grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)

            def micro_step(carry, micro_batch):
                acc, i = carry
                (_, loss), grads = grad_fn(compute_params, micro_batch, jax.random.fold_in(step_rng, i))
                with jax.named_scope("grad_accum"):
                    grads = cast_floating(grads, accum_dtype)
                    acc = jax.tree_util.tree_map(lambda a, g: (a + g).astype(accum_dtype), acc, grads)
                acc = jax.lax.with_sharding_constraint(acc, grad_pspecs)
                return (acc, i + 1), loss

            with jax.named_scope("grad_accum"):
                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), compute_params
                )
            zero_grads = jax.lax.with_sharding_constraint(zero_grads, grad_pspecs)
            if gas == 1:
                (grads, _), losses = micro_step((zero_grads, 0), jax.tree_util.tree_map(lambda x: x[0], batch))
                losses = losses[None]
            else:
                (grads, _), losses = jax.lax.scan(micro_step, (zero_grads, 0), batch)
            return grads, losses

        return jax.jit(grad_step)

    def _build_offload_update_step(self) -> Callable:
        """Host program: scale/clip/update on the CPU-committed master state.

        Emits the next step's bf16 compute params so only 2 bytes/param
        return to the accelerator (the reference ships fp16 params back from
        the CPU optimizer the same way)."""
        def update(state: TrainState, grads):
            rng = jax.random.wrap_key_data(state.rng)
            rng, _ = jax.random.split(rng)  # same key advance as the fused step
            new_state, metrics = self._update_math(state, grads, jax.random.key_data(rng))
            compute_16 = cast_floating(new_state.params, self.compute_dtype)
            return new_state, compute_16, metrics

        return jax.jit(update)  # inputs committed to the host device => runs on the cpu backend

    def _dev_replicated(self, x):
        """Commit a small host scalar/key to the mesh (explicit target — a
        bare device_put is a NO-OP for arrays already committed to the host
        device)."""
        return jax.device_put(x, NamedSharding(self.mesh, PartitionSpec()))

    def _swapped_in_state(self) -> TrainState:
        """Engine state with NVMe-resident optimizer moments read back in."""
        state = self.state
        if self._opt_on_nvme:
            state = state._replace(opt_state=self._opt_swapper.swap_in_opt_state(device_put=False))
        return state

    # ------------------------------------------------ Twin-Flow (partial) --
    def _tf_partition(self, tree, host_side: bool):
        """One partition's view of a params-shaped tree: out-of-partition
        leaves become 0-d numpy zeros (uncommitted, never read by the masked
        optimizer) so each program's inputs live on ONE backend."""
        keep = self._tf_host_mask if host_side else self._tf_dev_mask
        return jax.tree_util.tree_map(
            lambda m, x: x if m else np.zeros((), x.dtype), keep, tree)

    def _tf_merge(self, host_tree, dev_tree):
        """Re-assemble a full params-shaped tree from the two partition
        views (dummy leaves from each side are dropped)."""
        return jax.tree_util.tree_map(
            lambda m, h, d: h if m else d, self._tf_host_mask, host_tree, dev_tree)

    def _tf_refresh_compute(self, host_16, dev_16):
        """Merged on-accelerator bf16 compute params: the host partition's
        refresh crosses H2D into its mesh placement; the device partition's
        is already there."""
        host16_dev = jax.tree_util.tree_map(
            lambda m, x, sh: jax.device_put(x, sh) if m else x,
            self._tf_host_mask, host_16, self._device_param_sharding)
        return self._tf_merge(host16_dev, dev_16)

    def _build_twin_flow_steps(self) -> None:
        """The three Twin-Flow programs (reference ZeRO-Offload++): a device
        stats pass (finite + global norm over the FULL gradient, so clipping
        stays mathematically identical to the fused step), a fused on-device
        update for the device partition, and the host-jit update + bookkeeping
        for the host partition."""
        gas = self.config.gradient_accumulation_steps
        clip = self.config.gradient_clipping

        def stats(grads, inv):
            finite = all_finite(grads) if self.fp16 else jnp.asarray(True)
            # norm is 1-homogeneous: norm(g * inv) == norm(g) * inv
            with jax.named_scope("grad_norm"):
                return finite, global_norm(grads) * inv

        def _clipped(grads_sub, inv, gnorm):
            g = jax.tree_util.tree_map(lambda x: x * inv, grads_sub)
            if clip and clip > 0:
                g, _ = clip_by_global_norm(g, clip, norm=gnorm)
            return g

        @jax.named_scope("optimizer")
        def dev_update(params_sub, opt_dev, grads_sub, inv, finite, gnorm):
            g = _clipped(grads_sub, inv, gnorm)
            updates, new_opt = self._tf_tx_dev.update(g, opt_dev, params_sub)
            new_params = optax.apply_updates(params_sub, updates)
            sel = lambda n, o: jax.tree_util.tree_map(  # noqa: E731
                lambda a, b: jnp.where(finite, a, b), n, o)
            new_params = sel(new_params, params_sub)
            new_opt = sel(new_opt, opt_dev)
            return new_params, new_opt, cast_floating(new_params, self.compute_dtype)

        @jax.named_scope("optimizer")
        def host_update(params_sub, opt_host, grads_sub, step, ls_state, rng_data,
                        finite, gnorm):
            rng = jax.random.wrap_key_data(rng_data)
            rng, _ = jax.random.split(rng)  # same key advance as the fused step
            inv = 1.0 / (gas * ls_state.loss_scale)
            g = _clipped(grads_sub, inv, gnorm)
            updates, new_opt = self._tf_tx_host.update(g, opt_host, params_sub)
            new_params = optax.apply_updates(params_sub, updates)
            sel = lambda n, o: jax.tree_util.tree_map(  # noqa: E731
                lambda a, b: jnp.where(finite, a, b), n, o)
            new_params = sel(new_params, params_sub)
            new_opt = sel(new_opt, opt_host)
            new_ls, new_step, metrics = self._post_update_bookkeeping(
                finite, gnorm, step, ls_state)
            return (new_params, new_opt, new_step, new_ls,
                    jax.random.key_data(rng), metrics,
                    cast_floating(new_params, self.compute_dtype))

        self._tf_stats = jax.jit(stats)
        self._tf_dev_update = jax.jit(dev_update)
        self._tf_host_update = jax.jit(host_update)  # host-committed inputs => cpu backend

    def _tf_apply_update(self, state: TrainState, grads) -> Dict[str, Any]:
        """Twin-Flow step tail: device partition updates on-accelerator; only
        the host partition's gradients cross to the CPU and only its bf16
        refresh crosses back (the Twin-Flow win over full offload)."""
        from jax.sharding import SingleDeviceSharding

        host_sh = SingleDeviceSharding(self._host_device)
        scale = float(jax.device_get(state.loss_scale.loss_scale))
        inv = 1.0 / (self.config.gradient_accumulation_steps * scale)
        finite, gnorm = self._tf_stats(grads, inv)

        dev_grads = self._tf_partition(grads, host_side=False)
        host_grads = jax.tree_util.tree_map(
            lambda m, x: jax.device_put(x, host_sh) if m else np.zeros((), x.dtype),
            self._tf_host_mask, grads)

        opt_host, opt_dev = state.opt_state
        new_dev_params, new_opt_dev, dev_16 = self._tf_dev_update(
            self._tf_partition(state.params, host_side=False), opt_dev,
            dev_grads, inv, finite, gnorm)
        finite_h = jax.device_get(finite)
        gnorm_h = jax.device_get(gnorm)
        (new_host_params, new_opt_host, new_step, new_ls, new_rng, metrics,
         host_16) = self._tf_host_update(
            self._tf_partition(state.params, host_side=True), opt_host,
            host_grads, state.step, state.loss_scale, state.rng,
            finite_h, gnorm_h)

        overflow = bool(jax.device_get(metrics["overflow"]))
        if not overflow:
            self._compute_dev = self._tf_refresh_compute(host_16, dev_16)
        self.state = TrainState(
            step=new_step,
            params=self._tf_merge(new_host_params, new_dev_params),
            opt_state=(new_opt_host, new_opt_dev),
            loss_scale=new_ls,
            rng=new_rng,
            comm_error=state.comm_error,
            health=state.health,
            numerics=state.numerics,
        )
        return metrics

    def _offload_apply_update(self, state: TrainState, grads) -> Dict[str, Any]:
        """Host update + device bf16 refresh + NVMe swap-out (shared by the
        train_batch fast path and the forward/backward/step parity path)."""
        if self._twin_ratio is not None:
            return self._tf_apply_update(state, grads)
        from jax.sharding import SingleDeviceSharding

        host_sh = SingleDeviceSharding(self._host_device)
        grads_host = jax.device_put(grads, jax.tree_util.tree_map(lambda _: host_sh, grads))
        new_state, compute_16, metrics = self._offload_update_step(state, grads_host)
        overflow = bool(jax.device_get(metrics["overflow"]))
        if not overflow:
            self._compute_dev = jax.device_put(compute_16, self._device_param_sharding)
        if self.offload_mode == "nvme":
            self._opt_swapper.swap_out_opt_state(new_state.opt_state)
            new_state = new_state._replace(opt_state=None)
            self._opt_on_nvme = True
        self.state = new_state
        if self._offload_param_cfg and self._offload_param_cfg.device != "none":
            # ZeRO-Infinity param offload: nothing persists on the device
            # between steps; bf16 params re-stream next step.
            self._compute_dev = None
        return metrics

    def _offload_train_batch(self, placed) -> Dict[str, Any]:
        state = self._swapped_in_state()
        # same split as the fused step: step_rng drives dropout, rng advances
        step_rng = jax.random.split(jax.random.wrap_key_data(state.rng))[1]
        self._materialize_compute_dev()
        scale = self._dev_replicated(jnp.float32(jax.device_get(state.loss_scale.loss_scale)))
        # the split step HAS separable phases: device grad program vs host
        # optimizer update — the telemetry spans reflect that
        with self._tracer.span("fwd_bwd", offload=True):
            grads, losses = self._offload_grad_step(
                self._compute_dev, placed, scale, self._dev_replicated(jax.random.key_data(step_rng))
            )
        with self._tracer.span("step", offload=True):
            metrics = dict(self._offload_apply_update(state, grads))
        metrics["loss"] = jnp.mean(losses.astype(jnp.float32))
        return metrics

    def _materialize_compute_dev(self):
        """Ensure bf16 compute params exist on the accelerator; returns them."""
        if self._compute_dev is None:
            cast = jax.jit(functools.partial(cast_floating, dtype=self.compute_dtype))
            if self._twin_ratio is not None:
                # mixed master placement: one jit per partition's backend
                host_16 = cast(self._tf_partition(self.state.params, host_side=True))
                dev_16 = cast(self._tf_partition(self.state.params, host_side=False))
                self._compute_dev = self._tf_refresh_compute(host_16, dev_16)
            else:
                self._compute_dev = jax.device_put(
                    cast(self.state.params), self._device_param_sharding)
        return self._compute_dev

    def materialize_state(self) -> None:
        """Bring NVMe-swapped optimizer state back into ``self.state`` (for
        checkpointing or direct inspection)."""
        if self.offload_mode == "nvme" and self._opt_on_nvme:
            self.state = self.state._replace(opt_state=self._opt_swapper.swap_in_opt_state(device_put=False))
            self._opt_on_nvme = False

    # ------------------------------------- checkpoint-canonical opt_state --
    def canonical_opt_state(self, opt_state: Any = None) -> Any:
        """Checkpoint-boundary canonical form of ``opt_state``.

        Twin-Flow stores the optimizer state as a tuple of two
        ``optax.masked`` partition states whose ``MaskedNode`` hole placement
        depends on ``offload_optimizer.ratio`` and tree-flatten order — a
        partitioning artifact that must never leak into checkpoints (the
        reference's universal format is partitioning-independent fp32 atoms).
        This merges the two complementary partitions back into the single
        param-shaped moment tree ``self.tx.init(params)`` would produce, so a
        checkpoint saved under any ratio restores under any other ratio or
        into a non-Twin-Flow engine. Identity for non-Twin-Flow engines.
        """
        opt_state = self.state.opt_state if opt_state is None else opt_state
        if self._twin_ratio is None:
            return opt_state
        opt_host, opt_dev = opt_state
        hole = lambda x: isinstance(x, optax.MaskedNode)  # noqa: E731
        return jax.tree_util.tree_map(
            lambda h, d: d if isinstance(h, optax.MaskedNode) else h,
            opt_host.inner_state, opt_dev.inner_state, is_leaf=hole)

    def opt_state_from_canonical(self, canonical: Any) -> Any:
        """Inverse of ``canonical_opt_state``: re-partition a param-shaped
        moment tree into this engine's Twin-Flow ``(host, device)`` masked
        pair (hole placement taken from the live state, so the split follows
        THIS engine's ratio, not the saving engine's). Identity when
        Twin-Flow is off."""
        if self._twin_ratio is None:
            return canonical
        from jax.sharding import SingleDeviceSharding

        host_sh = SingleDeviceSharding(self._host_device)
        hole = lambda x: isinstance(x, optax.MaskedNode)  # noqa: E731

        def refill(template, host_side):
            def fill(t, c):
                if isinstance(t, optax.MaskedNode):
                    return t
                # The live partition states come from jit-ing the masked
                # inits, whose outputs are UNCOMMITTED — the device program
                # mixes mesh-committed params with them, which only composes
                # while the moments stay uncommitted. Restored arrays arrive
                # committed (orbax places them), so rebuild each leaf the way
                # init placed it: host partition committed to the host
                # backend, device partition uncommitted on the default device.
                v = jnp.asarray(np.asarray(jax.device_get(c)))
                return jax.device_put(v, host_sh) if host_side else v

            inner = jax.tree_util.tree_map(
                fill, template.inner_state, canonical, is_leaf=hole)
            return optax.MaskedState(inner)

        opt_host, opt_dev = self.state.opt_state
        return (refill(opt_host, True), refill(opt_dev, False))

    # ------------------------------------------------------------- data path
    def _leaf_batch_sharding(self, x, leading_none: int = 0) -> NamedSharding:
        """Rank-aware batch sharding for one array leaf.

        The batch dim shards over (dp, fsdp); the following (sequence) dim
        shards over sp only when the leaf has one and it divides evenly.
        """
        from deepspeed_tpu.topology.mesh import BATCH_AXES

        mesh = self.mesh
        batch_axes = tuple(a for a in BATCH_AXES if mesh.shape[a] > 1)
        entries: list = [None] * leading_none + [batch_axes if batch_axes else None]
        sp = mesh.shape["sp"]
        seq_dim = leading_none + 1
        if sp > 1 and x.ndim > seq_dim and x.shape[seq_dim] % sp == 0 and x.shape[seq_dim] > 1:
            entries.append("sp")
        return NamedSharding(mesh, PartitionSpec(*entries))

    def _place_batch(self, batch, leading_none: int = 0) -> Any:
        return jax.device_put(
            batch,
            jax.tree_util.tree_map(lambda x: self._leaf_batch_sharding(x, leading_none), batch),
        )

    def _shard_global_batch(self, batch) -> Any:
        """[global_batch, ...] -> [gas, micro*dp, ...] placed on the mesh."""
        gas = self.config.gradient_accumulation_steps

        def reshape(x):
            x = jnp.asarray(x)
            if x.shape[0] != self.config.train_batch_size:
                raise ValueError(
                    f"batch leading dim {x.shape[0]} != train_batch_size {self.config.train_batch_size}"
                )
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        return self._place_batch(jax.tree_util.tree_map(reshape, batch), leading_none=1)

    def _stack_micro_batches(self, data_iter: Iterator) -> Any:
        gas = self.config.gradient_accumulation_steps
        micros = [next(data_iter) for _ in range(gas)]
        batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micros)
        return self._place_batch(batch, leading_none=1)

    # ------------------------------------------------------------ public API
    def train_batch(self, batch: Any = None, data_iter: Optional[Iterator] = None) -> Dict[str, Any]:
        """One full optimizer step over ``train_batch_size`` samples.

        Pass either a global batch (leading dim = train_batch_size) or an
        iterator yielding micro-batches (leading dim = micro*dp_world), the
        reference ``PipelineEngine.train_batch(data_iter)`` convention.
        """
        with self._tracer.span("train_batch", step=self._batch_count):
            return self._train_batch_inner(batch, data_iter)

    def _train_batch_inner(self, batch: Any, data_iter: Optional[Iterator]) -> Dict[str, Any]:
        if (batch is None) == (data_iter is None):
            raise ValueError("provide exactly one of batch= or data_iter=")
        set_mesh(self.mesh)  # models read the active mesh at trace time
        with self._tracer.span("data"):
            if batch is not None:
                placed = self._shard_global_batch(batch)
            else:
                placed = self._stack_micro_batches(data_iter)
            if getattr(self, "_moe_autotune", None) is not None:
                placed = self._moe_autotune_batch_key(placed)
        prof = self.flops_profiler
        fp_cfg = prof.config
        config_fire = (fp_cfg.enabled and prof.result is None
                       and self._batch_count >= fp_cfg.profile_step)
        # step wall-clock for the anomaly detector (same honesty caveat as the
        # spans: dispatch time under async dispatch)
        diag_t0 = time.perf_counter() if self.diagnostics is not None else None
        if self.diagnostics is not None:
            # an armed profiler-capture window starts here so the device
            # trace brackets whole step dispatches
            self.diagnostics.before_step(self._batch_count + 1)
        if self._train_step is None:  # offload split path
            if (prof.armed or config_fire) and not getattr(self, "_offload_prof_warned", False):
                logger.warning(
                    "flops profiler is not supported with optimizer offload "
                    "(the step is split across backends); skipping profile"
                )
                prof.armed = False
                self._offload_prof_warned = True
            self.throughput_timer.start()
            metrics = self._offload_train_batch(placed)
            self.throughput_timer.stop()
        elif prof.armed or config_fire:
            # profile this step's compiled program (reference FlopsProfiler
            # hooks the fwd at profile_step; here it is XLA cost analysis).
            # `result is None` guard: fires once even if global_steps stalls
            # on fp16 overflow-skipped steps. The profiled execution IS the
            # training step for this batch (no double-step, no state copy);
            # the throughput timer skips it — compile/analysis time would
            # poison the samples/sec history.
            self.state, metrics = prof.profile_engine_step(placed)
            prof.print_model_profile(top=fp_cfg.top_modules)
        else:
            self.throughput_timer.start()
            # the fused program has no separable fwd/bwd/step phases — this
            # span is the whole optimizer step's dispatch; its device time
            # is in the device rows of the same jax.profiler trace
            with self._tracer.span("step", fused=True) as span:
                self.state, metrics = self._train_step(self.state, placed)
                # MB a chip receives a micro-step from the layer scan's own gathers (counted where they are traced)
                span.set_metadata(zero_gather_mb=self.zero_gather_mb)
            self.throughput_timer.stop()
        with self._tracer.span("post_step"):
            self._post_step(metrics, diag_t0)
        return metrics

    def _post_step(self, metrics: Dict[str, Any], diag_t0: Optional[float]) -> None:
        """Everything between the step's dispatch and ``return metrics``:
        fleet note, diagnostics, snapshot, observatories, monitor buffering
        and the ``steps_per_print`` fetch."""
        # Metrics stay device-side: fetching them here would block the host on
        # the step and break JAX async dispatch (measured 743 ms -> 102 ms per
        # step on v5e for the 125M bench). Callers that want numbers call
        # ``float()``/``np.asarray`` on the returned leaves.
        self.losses = metrics["loss"]
        self._batch_count += 1
        step = self._batch_count
        # /healthz + fleet-heartbeat liveness breadcrumb (two plain writes)
        _fleet_note_step(step)
        if self.diagnostics is not None:
            # flight-recorder ring append (device refs, no fetch) + step-time
            # anomaly observe + the abort-policy check (which may raise)
            self.diagnostics.after_step(
                step, metrics, step_time_s=time.perf_counter() - diag_t0)
        if self.snapshot_manager is not None:
            # AFTER the abort check: a step the health policy aborted must
            # never become the snapshot the recovery loop rewinds to
            self.snapshot_manager.after_step(step)
        if self._numerics is not None:
            # sampled EF-residual gauges + the divergence-sentinel fold
            # (which may raise under the abort policy)
            self._numerics_on_step(step)
        if self.monitor is not None:
            scalars = {
                "Train/loss": metrics["loss"],
                "Train/lr": metrics["lr"],
                **({"Train/loss_scale": metrics["loss_scale"]} if self.fp16 else {}),
            }
            if self._health is not None:
                scalars.update({
                    f"Health/{k[len('health/'):]}": metrics[k]
                    for k in ("health/skip", "health/grad_zscore",
                              "health/nonfinite_total")
                    if k in metrics})
            # MoE dispatch gauges (device-computed inside the step; ride the
            # buffered bulk fetch with every other monitor scalar)
            scalars.update({
                f"Moe/{k[len('moe/'):]}": metrics[k]
                for k in _MOE_METRIC_KEYS if k in metrics})
            if self._tracer.enabled:
                # host-side floats only (counter deltas, memory watermarks,
                # last phase wall times) — never a device fetch
                scalars.update(self._tracer.step_scalars())
            self._monitor_pending.append((step, scalars))
        if step % self.config.model.steps_per_print == 0:
            # periodic sync point: one fetch per steps_per_print batches
            fetched = jax.device_get(metrics)
            if getattr(self, "_moe_autotune", None) is not None:
                # controller tick: the fetch already paid the sync, the
                # adjustment is pure host arithmetic on the step's gauges
                self._moe_autotune_update(fetched)
            if self._tracer.enabled:
                # moe/* registry gauges refresh at the existing sync cadence
                # (ROADMAP item 4 instrumentation: capacity/drops/balance in
                # the same exposition as every other subsystem)
                for k in _MOE_METRIC_KEYS:
                    if k in fetched:
                        self._tracer.registry.gauge(k).set(float(fetched[k]))
            log_dist(
                f"step={step} loss={float(fetched['loss']):.4f} lr={float(fetched['lr']):.3e} "
                f"grad_norm={float(fetched['grad_norm']):.3f}",
                ranks=[0],
            )
            self.flush_monitor()
            if self.config.model.memory_breakdown:
                from deepspeed_tpu.utils.memory import see_memory_usage

                see_memory_usage(f"after step {step}", force=True)

    def flush_monitor(self) -> None:
        """Write buffered scalars to the monitor (one bulk device fetch) and
        any configured telemetry exports."""
        if self._tracer.enabled:
            self._tracer.maybe_export()
        if self.monitor is None or not self._monitor_pending:
            self._monitor_pending = []
            return
        with self._tracer.span("flush_monitor"):
            pending, self._monitor_pending = self._monitor_pending, []
            for step, scalars in jax.device_get(pending):
                self.monitor.write_scalars(int(step), {k: float(v) for k, v in scalars.items()})

    def __del__(self):  # pragma: no cover - interpreter teardown ordering
        try:
            self.flush_monitor()
        except Exception:
            pass

    # --- forward / backward / step parity path ----------------------------
    def forward(self, batch: Any) -> Any:
        """Inference/eval forward returning model outputs (loss by default)."""
        with self._tracer.span("fwd"):
            return self._forward_inner(batch)

    def _forward_inner(self, batch: Any) -> Any:
        set_mesh(self.mesh)
        offload_split = self._train_step is None
        if self._eval_step is None:
            if offload_split:
                def eval_fn(params, batch, rng):
                    loss, aux = self._loss_and_aux(params, batch, jax.random.wrap_key_data(rng))
                    return (loss, *aux) if aux else loss

                self._eval_step = self._wrap_jit(
                    "eval_step", jax.jit(eval_fn), ("params", "batch", "rng"))
            else:
                def eval_fn(params, batch, rng):
                    loss, aux = self._loss_and_aux(self._compute_params(params), batch, jax.random.wrap_key_data(rng))
                    return (loss, *aux) if aux else loss

                self._eval_step = self._wrap_jit(
                    "eval_step",
                    jax.jit(eval_fn, in_shardings=(self.param_sharding, None, None)),
                    ("params", "batch", "rng"))
        placed = self._place_batch(jax.tree_util.tree_map(jnp.asarray, batch))
        self._last_batch = placed
        if offload_split:
            params = self._materialize_compute_dev()
            return self._eval_step(params, placed, self._dev_replicated(self.state.rng))
        return self._eval_step(self.state.params, placed, self.state.rng)

    def eval_batch(self, batch: Any) -> Any:
        return self.forward(batch)

    def backward(self, loss: Any = None, batch: Any = None) -> None:
        """Accumulate gradients for one micro-batch.

        JAX cannot differentiate "backward from a returned loss value", so this
        recomputes forward+backward for the micro-batch (``batch`` or the one
        passed to the last ``forward``). ``train_batch`` is the efficient path.
        """
        with self._tracer.span("bwd", micro_step=self._micro_steps):
            return self._backward_inner(loss, batch)

    def _backward_inner(self, loss: Any, batch: Any) -> None:
        if self._onebit:
            raise NotImplementedError(
                "1-bit compressed gradients are only wired into train_batch "
                "(the error-feedback state lives in the fused step); use "
                "train_batch with gradient_compression"
            )
        if self._zpp and self._zpp[3]:
            raise NotImplementedError(
                "ZeRO++ LoCo is only wired into train_batch (the residual "
                "state lives in the fused step); use train_batch or drop "
                "loco_param"
            )
        set_mesh(self.mesh)
        if batch is None:
            batch = getattr(self, "_last_batch", None)
            if batch is None:
                raise RuntimeError("backward() needs a batch= or a preceding forward(batch)")
        else:
            batch = self._place_batch(jax.tree_util.tree_map(jnp.asarray, batch))
        offload_split = self._train_step is None
        if self._grad_step is None:
            grad_pspecs = self.grad_sharding

            if self._zpp:
                zpp_fn = self._build_zpp_micro_fn(*self._zpp)

                def micro_grads(params, scale, micro, rng):
                    compute = jax.lax.with_sharding_constraint(
                        cast_floating(params, self.compute_dtype), self._device_param_sharding
                    )
                    grads, loss = zpp_fn(compute, micro, scale, jax.random.key_data(rng))
                    grads = jax.lax.with_sharding_constraint(grads, grad_pspecs)
                    return loss, grads
            else:
                def micro_grads(params, scale, micro, rng):
                    def scaled(p, b, r):
                        p = p if offload_split else self._compute_params(p)
                        loss, _ = self._loss_and_aux(p, b, r)
                        return loss.astype(jnp.float32) * scale, loss

                    with dot_general_context(None if offload_split else self._scan_gathers):
                        (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(params, micro, rng)
                    # same dtype rule as the compiled steps (Twin-Flow stays fp32)
                    acc_dt = (jnp.float32 if self._twin_ratio is not None
                              else self._accum_dtype)
                    grads = jax.lax.with_sharding_constraint(
                        cast_floating(grads, acc_dt), grad_pspecs)
                    return loss, grads

            if offload_split:
                self._grad_step = self._wrap_jit(
                    "grad_step", jax.jit(micro_grads),
                    ("params", "scale", "batch", "rng"))
            else:
                self._grad_step = self._wrap_jit(
                    "grad_step",
                    jax.jit(micro_grads, in_shardings=(self.param_sharding, None, None, None)),
                    ("params", "scale", "batch", "rng"))
            self._accum_add = jax.jit(
                lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=(0, 1)
            )
        rng = jax.random.fold_in(jax.random.wrap_key_data(self.state.rng), self._micro_steps)
        params_arg = self._materialize_compute_dev() if offload_split else self.state.params
        scale_arg = self.state.loss_scale.loss_scale
        if offload_split:
            rng = self._dev_replicated(rng)
            scale_arg = self._dev_replicated(jnp.float32(jax.device_get(scale_arg)))
        loss_val, grads = self._grad_step(params_arg, scale_arg, batch, rng)
        if self._pending_grads is None:
            self._pending_grads = grads
        else:
            self._pending_grads = self._accum_add(self._pending_grads, grads)
        self._pending_losses.append(loss_val)
        self._micro_steps += 1

    def step(self) -> Dict[str, Any]:
        """Apply accumulated gradients at the accumulation boundary
        (reference ``engine.step`` :2338 — no-op until gas micro-batches seen)."""
        if self._micro_steps < self.config.gradient_accumulation_steps:
            return {}
        with self._tracer.span("step"):
            return self._step_inner()

    def _step_inner(self) -> Dict[str, Any]:
        if self._pending_grads is None:
            raise RuntimeError("step() called with no accumulated gradients")
        if self._train_step is None:  # offload split: update runs on the host
            metrics = self._offload_apply_update(self._swapped_in_state(), self._pending_grads)
        else:
            if self._apply_step is None:
                self._apply_step = self._wrap_jit(
                    "apply_step", self._build_apply_step(), ("state", "grads"))
            self.state, metrics = self._apply_step(self.state, self._pending_grads)
        metrics = {k: np.asarray(v) for k, v in metrics.items()}
        if self._pending_losses:
            metrics["loss"] = np.mean([np.asarray(l, dtype=np.float32) for l in self._pending_losses])
        self._pending_grads = None
        self._pending_losses = []
        self._micro_steps = 0
        return metrics

    def _build_apply_step(self) -> Callable:
        def apply_step(state: TrainState, grads):
            # advance the key so the next accumulation cycle gets fresh dropout
            new_rng = jax.random.key_data(jax.random.split(jax.random.wrap_key_data(state.rng))[0])
            return self._update_math(state, grads, new_rng)

        return jax.jit(
            apply_step,
            in_shardings=(self.state_sharding, self.grad_sharding),
            out_shardings=(self.state_sharding, None),
            donate_argnums=(0, 1),
        )

    # ------------------------------------------------------------ accessors
    @property
    def global_steps(self) -> int:
        return int(self.state.step)

    @property
    def cur_scale(self) -> float:
        return float(self.state.loss_scale.loss_scale)

    @property
    def skipped_steps(self) -> int:
        return int(self.state.loss_scale.skipped_steps)

    def get_lr(self) -> float:
        return float(jnp.asarray(self.lr_scheduler_fn(self.state.step)))

    def get_global_grad_norm(self) -> Optional[float]:
        return None  # populated from last metrics by callers if needed

    @property
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    @property
    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps_value(self) -> int:
        return self.config.gradient_accumulation_steps

    def module_state_dict(self) -> Any:
        """Full (gathered) fp32 params — reference ``module_state_dict``."""
        if self.offload_mode in ("host-jit", "nvme"):
            return jax.device_get(self.state.params)  # already host-resident + unsharded
        gather = jax.jit(
            lambda p: p,
            out_shardings=jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, PartitionSpec()), self.state.params
            ),
        )
        return jax.device_get(gather(self.state.params))

    # ------------------------------------------------------------------ I/O
    def deepspeed_io(self, dataset, batch_size: Optional[int] = None) -> Any:
        """Build the training dataloader (reference ``deepspeed_io``
        engine.py:1854). Consults the ``data_efficiency`` config: an enabled
        curriculum (``data_sampling.curriculum_learning``) installs the
        difficulty-filtered ``DeepSpeedDataSampler``."""
        from deepspeed_tpu.runtime.dataloader import DeepSpeedTPUDataLoader

        bs = batch_size or self.config.train_micro_batch_size_per_gpu * get_data_parallel_world_size(self.mesh)
        sampler = self._build_data_efficiency_sampler(dataset, bs)
        if sampler is not None and isinstance(dataset, dict) and "difficulties" in dataset:
            dataset = {k: v for k, v in dataset.items() if k != "difficulties"}
        return DeepSpeedTPUDataLoader(
            dataset,
            batch_size=bs,
            seed=self.config.model.seed,
            sampler=sampler,
        )

    def _build_data_efficiency_sampler(self, dataset, batch_size: int):
        de = self.config.model.data_efficiency
        if not de.enabled:
            return None
        ds_cfg = de.data_sampling or {}
        cl = ds_cfg.get("curriculum_learning", {})
        if not ds_cfg.get("enabled", True) or not cl.get("enabled", False):
            return None
        from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler
        from deepspeed_tpu.runtime.data_pipeline.data_sampler import DeepSpeedDataSampler

        sched = CurriculumScheduler(cl)
        difficulties = getattr(dataset, "difficulties", None)
        if difficulties is None and isinstance(dataset, dict):
            difficulties = dataset.get("difficulties")
        if difficulties is None and sched.metric == "seqlen" and isinstance(dataset, dict) \
                and "input_ids" in dataset:
            # seqlen metric default: per-sample non-pad length (the reference
            # precomputes this into an index map, data_analyzer.py)
            ids = np.asarray(dataset["input_ids"])
            mask = dataset.get("attention_mask")
            difficulties = (np.asarray(mask).sum(-1) if mask is not None
                            else np.full(len(ids), ids.shape[-1]))
        if difficulties is None:
            raise ValueError(
                "curriculum_learning needs per-sample difficulties: provide "
                "dataset.difficulties / a 'difficulties' column, or use the "
                "'seqlen' metric with an input_ids column"
            )
        n = len(np.asarray(difficulties))
        return DeepSpeedDataSampler(
            n, batch_size, difficulties=np.asarray(difficulties),
            curriculum=sched, seed=de.seed,
        )

    @functools.cached_property
    def checkpoint_engine(self):
        """Engine selected by the config ``checkpoint.engine`` key
        ('orbax' | 'async'/'nebula'; reference ``_configure_checkpointing``
        engine.py:354)."""
        from deepspeed_tpu.checkpoint.engine import get_checkpoint_engine

        return get_checkpoint_engine(self.config.model.checkpoint.get("engine", "orbax"))

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None, client_state: Optional[Dict] = None,
                        save_latest: bool = True) -> None:
        from deepspeed_tpu.checkpoint.checkpointing import save_checkpoint as _save

        self.flush_monitor()
        self.materialize_state()
        _save(self, save_dir, tag=tag, client_state=client_state or {}, save_latest=save_latest,
              checkpoint_engine=self.checkpoint_engine)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_universal: bool = False) -> Tuple[Optional[str], Dict]:
        """Restore state. ``load_universal=True`` reads the mesh-independent
        atom format instead (reference ``load_universal_checkpoint`` flag).
        A directory holding only elastic snapshots (``<dir>/snapshots/``, no
        orbax ``latest``) routes to the snapshot restore path — manifest
        checksums validated before any device state is touched, previous tag
        on corruption."""
        self.materialize_state()
        if load_universal:
            from deepspeed_tpu.checkpoint.universal import load_universal as _loadu

            out = _loadu(self, load_dir, tag=tag,
                         placement=self.config.model.checkpoint.get("restore", "fresh")), {}
        elif (not os.path.exists(os.path.join(load_dir, "latest"))
              and os.path.isdir(os.path.join(load_dir, "snapshots"))):
            out = self.restore_snapshot(load_dir, tag=tag), {}
        else:
            from deepspeed_tpu.checkpoint.checkpointing import load_checkpoint as _load

            out = _load(self, load_dir, tag=tag, load_optimizer_states=load_optimizer_states)
        if self.offload_mode in ("host-jit", "nvme"):
            self._compute_dev = None  # params changed: bf16 view re-materializes
        return out

    def restore_snapshot(self, base_dir: Optional[str] = None,
                         tag: Optional[str] = None, fallback: bool = True) -> str:
        """Restore an elastic snapshot (``checkpoint/snapshot.py``) into this
        engine — any mesh, fresh committed buffers, checksum-validated with
        previous-tag fallback. Returns the tag restored."""
        self.materialize_state()
        if self.snapshot_manager is not None and (
                base_dir is None
                or os.path.abspath(base_dir)
                == os.path.abspath(self.snapshot_manager.base_dir)):
            return self.snapshot_manager.restore(tag=tag, fallback=fallback)
        if base_dir is None:
            raise ValueError("restore_snapshot needs a base_dir (no snapshot "
                             "manager configured on this engine)")
        from deepspeed_tpu.checkpoint.snapshot import restore_snapshot as _restore

        return _restore(self, base_dir, tag=tag, fallback=fallback)

    def save_universal_checkpoint(self, save_dir: str, tag: Optional[str] = None) -> str:
        """Write the mesh-independent atom checkpoint (reference
        ``checkpoint/ds_to_universal.py`` done online — no offline pass)."""
        from deepspeed_tpu.checkpoint.universal import save_universal as _saveu

        self.materialize_state()  # NVMe-swapped moments must be in the state
        return _saveu(self, save_dir, tag=tag)
