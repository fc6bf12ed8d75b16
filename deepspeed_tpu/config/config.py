"""The framework config: JSON/dict -> typed config tree.

TPU-native analog of the reference's ``deepspeed/runtime/config.py``
(``DeepSpeedConfig`` :708). Accepts the same JSON surface where it makes sense
on TPU (batch triad, optimizer, scheduler, fp16/bf16, zero_optimization,
gradient_clipping, monitors, flops profiler, activation checkpointing), plus a
TPU-specific ``mesh`` section declaring named parallelism axes
(dp/fsdp/tp/sp/ep/pp) in place of the reference's implicit world-size plumbing.

Batch triad arithmetic (reference ``runtime/config.py:983``):
``train_batch_size = micro_batch_per_device * gradient_accumulation_steps * dp_world``
where ``dp_world`` is the product of the data-like mesh axes (dp * fsdp).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple, Union

from pydantic import Field

from deepspeed_tpu.config.config_utils import DeepSpeedConfigModel
from deepspeed_tpu.utils.logging import logger

TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"


class FP16Config(DeepSpeedConfigModel):
    """fp16 section (reference ``runtime/fp16/loss_scaler.py`` semantics)."""

    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == 0.0


class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False
    # fp32 gradient accumulation across microbatches (reference bf16_optimizer
    # immediate_grad_update analog — on TPU this picks the accum dtype).
    accumulate_grads_in_fp32: bool = True


class OffloadDeviceEnum:
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class OffloadConfig(DeepSpeedConfigModel):
    """offload_optimizer / offload_param sections (reference ``zero/offload_config.py``)."""

    device: str = OffloadDeviceEnum.none
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = 1.0
    max_in_cpu: int = 1_000_000_000


class ZeroConfig(DeepSpeedConfigModel):
    """zero_optimization section (reference ``runtime/zero/config.py:86``).

    On TPU, stages map to sharding placements of one jitted program:
      stage 0: params+grads+opt replicated (plain DP, psum grads)
      stage 1: optimizer state sharded over data axes
      stage 2: + gradients reduce-scattered / accumulated sharded
      stage 3: + parameters sharded over the ``fsdp`` mesh axis
    """

    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True  # XLA latency-hiding scheduler does this; kept for schema parity
    offload_param: Optional[OffloadConfig] = None
    offload_optimizer: Optional[OffloadConfig] = None
    sub_group_size: int = 1_000_000_000
    # stage-3 partitioning knobs
    param_persistence_threshold: int = 100_000  # params smaller than this stay replicated
    model_persistence_threshold: int = 9_223_372_036_854_775_807
    max_live_parameters: int = 1_000_000_000
    max_reuse_distance: int = 1_000_000_000
    stage3_gather_16bit_weights_on_model_save: bool = False
    # ZeRO++ analogs (quantized collectives)
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    # ZeRO++ LoCo (reference coalesced_collectives.py:81
    # all_to_all_loco_quant_reduce): error-feedback compensation on the qgZ
    # quantized gradient reduce. Requires zero_quantized_gradients.
    # e.g. {"err_beta": 0.8, "reset_T": 1024}
    loco_param: Optional[Dict[str, Any]] = None
    zero_hpz_partition_size: int = 1
    # MiCS analog: shard params over a sub-group of the fsdp axis, replicate across groups
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    round_robin_gradients: bool = False
    ignore_unused_parameters: bool = True
    log_trace_cache_warnings: bool = False

    @property
    def offload_optimizer_device(self) -> str:
        return self.offload_optimizer.device if self.offload_optimizer else OffloadDeviceEnum.none

    @property
    def offload_param_device(self) -> str:
        return self.offload_param.device if self.offload_param else OffloadDeviceEnum.none


class OptimizerConfig(DeepSpeedConfigModel):
    type: str = "AdamW"
    params: Dict[str, Any] = Field(default_factory=dict)


class SchedulerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = Field(default_factory=dict)


class MeshConfig(DeepSpeedConfigModel):
    """TPU-specific: named parallelism axes over the device mesh.

    Replaces the reference's process-group plumbing (``utils/groups.py``,
    ``runtime/pipe/topology.py``). Sizes of -1 mean "absorb remaining devices".
    Axis order here is the physical layout order (outermost first): pp rides
    DCN when multi-slice; tp is innermost for fastest ICI.
    """

    pp: int = 1
    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    # multi-slice: number of slices connected over DCN (1 = single slice)
    num_slices: int = 1
    dcn_axis: str = "dp"  # which axis spans DCN in multi-slice deployments


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """reference ``runtime/activation_checkpointing/config``; on TPU this maps
    to jax.checkpoint (remat) policies applied to the compiled loss
    (``runtime/activation_checkpointing.py``)."""

    enabled: bool = False
    partition_activations: bool = False
    cpu_checkpointing: bool = False  # maps to XLA host-memory offload of residuals
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU extension: jax.checkpoint policy name (see runtime/activation_checkpointing.py)
    policy: str = "full"


class TensorboardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class HBMGuardConfig(DeepSpeedConfigModel):
    """hbm_guard section — pre-flight memory-fit check (``utils/hbm.py``).

    Before the engine materializes parameters on device it estimates the
    per-device state bytes (params + grads/accumulator + optimizer state +
    activations + logits, ``autotuning.estimate_state_memory``) against the
    device budget. Default: warn-only. ``enabled=True`` REFUSES over-budget
    configs with the estimate in the error, before anything is placed."""

    enabled: bool = False  # True: raise HBMBudgetError instead of warning
    warn: bool = True  # False (with enabled=False): guard fully off
    # Override budget discovery (jax memory_stats / DSTPU_DEVICE_MEMORY_GB).
    device_memory_gb: Optional[float] = None
    headroom: float = 0.92  # fraction of the budget the estimate may use


class TelemetryConfig(DeepSpeedConfigModel):
    """telemetry section — the unified observability substrate
    (``deepspeed_tpu/telemetry``): span tracer + metrics registry + trace
    exporters. TPU-native; the closest reference analog is the union of
    ``wall_clock_breakdown``, the comms logger, and the monitor scalars,
    sharing one registry here. Zero overhead when disabled (the default)."""

    enabled: bool = False
    # Bounded in-memory event buffer; overflow counts dropped_events.
    max_events: int = 100_000
    # Chrome trace-event JSON (open at https://ui.perfetto.dev), written at
    # monitor flushes and by explicit telemetry.export_chrome_trace() calls.
    trace_path: Optional[str] = None
    # Structured event log, one JSON object per line.
    jsonl_path: Optional[str] = None
    # Per-step device-memory gauges (PJRT memory_stats / jax.live_arrays).
    memory_watermarks: bool = True
    # Prometheus text exposition of the whole registry, rewritten at every
    # monitor flush (node-exporter textfile-collector style). None = off.
    prometheus_path: Optional[str] = None
    # Opt-in /metrics HTTP endpoint (stdlib thread, telemetry/exposition.py):
    # GET /metrics (Prometheus text) + /metrics.json (snapshot). 0 binds a
    # free port; None (default) starts no server.
    http_port: Optional[int] = None
    # Compiled-program registry (telemetry/programs.py): capture cost/memory/
    # collective analysis of every jitted program at the recompile-detector
    # wrap point, published as program/* + compile/* metrics and feeding the
    # hbm/estimate_ratio calibration. Follows `enabled`; set false to keep
    # spans/metrics without program capture (skips the one-time per-compile
    # AOT analysis pass).
    programs: bool = True
    # Fleet federation (telemetry/fleet.py + telemetry/collector.py): when
    # set, this process registers with the FleetCollector at this URL
    # (identity + clock handshake) and pushes mergeable registry snapshots
    # and heartbeats (step rate, HBM watermark, anomaly flags) on the
    # cadence below, from a daemon thread. None = no
    # fleet client (single-process runs pay nothing).
    fleet_url: Optional[str] = None
    fleet_push_interval_s: float = 5.0
    # Identity override for this process's role in the fleet ledger
    # (train | router | replica | collector | worker); None keeps the
    # $DSTPU_ROLE / default resolution.
    fleet_role: Optional[str] = None
    # ---- incident plane (telemetry/events.py + telemetry/alerts.py) ----
    # Structured event stream: bounded ring of typed detector events
    # (always on — emission is a lock + deque append; the knobs below only
    # size the ring / route the JSONL export next to the trace stream).
    events_capacity: int = 2048
    events_dedup_window_s: float = 300.0
    # Event JSONL export path; None = $DSTPU_TELEMETRY_DIR/event_log.jsonl
    # when telemetry is enabled, written at monitor flushes.
    events_jsonl_path: Optional[str] = None
    # Declarative alert engine over the registry + event stream. When
    # enabled, the default rule pack (numerics divergence, collective
    # drift, perf regressions, dead replicas, RPC failures, health aborts,
    # recompile storms) evaluates on a daemon thread at this cadence.
    alerts_enabled: bool = False
    alerts_interval_s: float = 5.0
    # Optional sinks beyond the log: JSONL notification stream, and a
    # webhook POSTed from a worker thread that never raises (PR-13
    # push_async discipline).
    alerts_jsonl_path: Optional[str] = None
    alerts_webhook_url: Optional[str] = None


class HealthConfig(DeepSpeedConfigModel):
    """In-jit training-health probes (``diagnostics/health.py``): per-leaf-
    group nonfinite counts plus grad-norm/loss EMA z-score spike detection,
    traced into the compiled step next to the existing overflow/grad-norm
    math. Per-signal policy: ``log`` (record in metrics), ``skip_step`` (gate
    the optimizer update off inside the program — the fp16 overflow-skip
    select, extended), ``abort`` (skip AND raise ``TrainingHealthError``
    host-side; the one policy that syncs the dispatch pipeline per step)."""

    enabled: bool = True
    nonfinite_policy: str = "log"  # log | skip_step | abort
    grad_spike_policy: str = "log"
    loss_spike_policy: str = "log"
    grad_spike_zscore: float = 6.0
    loss_spike_zscore: float = 6.0
    ema_beta: float = 0.98
    # healthy steps absorbed into the EMAs before z-scores may fire
    warmup_steps: int = 20


class RecompileDetectConfig(DeepSpeedConfigModel):
    """Recompile detection on the engine's jitted callables
    (``diagnostics/recompile.py``): compile-cache growth tracking + argument
    shape-diff attribution, with storm escalation when recompiles cluster."""

    enabled: bool = True
    storm_threshold: int = 3  # recompiles within the window => storm error
    storm_window_s: float = 60.0


class StepTimeConfig(DeepSpeedConfigModel):
    """Step-time anomaly detection (``diagnostics/anomaly.py``): rolling
    median + MAD straggler flags and sustained-regression detection over the
    per-step wall times; results land as ``anomaly/`` registry gauges."""

    enabled: bool = True
    window: int = 64
    straggler_mads: float = 6.0
    regression_factor: float = 1.3
    min_samples: int = 8


class FlightRecorderConfig(DeepSpeedConfigModel):
    """Crash flight recorder (``diagnostics/flight_recorder.py``): bounded
    ring of recent step records (metric snapshot + health verdicts), dumped
    to JSONL + Perfetto on unhandled exception, SIGTERM/SIGUSR1, or an
    explicit ``engine.diagnostics.dump()``."""

    enabled: bool = True
    capacity: int = 16  # step records kept in the ring
    dump_dir: Optional[str] = None  # default: $DSTPU_TELEMETRY_DIR or ./telemetry_out
    install_signal_handlers: bool = True  # SIGTERM/SIGUSR1 -> dump (process-wide, once)
    dump_on_exception: bool = True  # sys.excepthook chain -> dump


class ProfilerCaptureConfig(DeepSpeedConfigModel):
    """Anomaly-triggered device-trace capture (``profiling/capture.py``).

    When the step-time anomaly detector flags a straggler or sustained
    regression (or on SIGUSR2, or an explicit
    ``engine.diagnostics.profiler_capture.arm()``), ``jax.profiler`` traces
    the next ``steps`` steps and drops the trace directory next to the
    flight record — so the post-mortem of a slow step holds the device
    timeline that explains it, not just the host-side flag. Opt-in:
    ``jax.profiler`` is heavyweight, so nothing starts unless this block is
    enabled AND a trigger fires; ``cooldown_steps`` bounds how often."""

    enabled: bool = False
    steps: int = 3  # steps traced per capture window
    on_anomaly: bool = True  # straggler/regression flags arm a capture
    signal: bool = True  # SIGUSR2 arms a capture (process-wide, once)
    cooldown_steps: int = 200  # min steps between capture windows
    dir: Optional[str] = None  # default: the flight recorder's dump dir


class DiagnosticsConfig(DeepSpeedConfigModel):
    """diagnostics section — the watching half of observability
    (``deepspeed_tpu/diagnostics``), built on the telemetry core. Disabled
    (the default) the engine compiles the identical program as without the
    block and every hook is one attribute check."""

    enabled: bool = False
    health: HealthConfig = Field(default_factory=HealthConfig)
    recompile: RecompileDetectConfig = Field(default_factory=RecompileDetectConfig)
    step_time: StepTimeConfig = Field(default_factory=StepTimeConfig)
    flight_recorder: FlightRecorderConfig = Field(default_factory=FlightRecorderConfig)
    profiler_capture: ProfilerCaptureConfig = Field(default_factory=ProfilerCaptureConfig)


class NumericsConfig(DeepSpeedConfigModel):
    """numerics section — the numerics observatory
    (``telemetry/numerics.py``): the in-jit cross-replica divergence sentinel
    (carried in ``TrainState.numerics`` like the health field), LoCo
    error-feedback residual gauges, and serving fidelity probes. Disabled
    (the default) the traced step program is jaxpr-identical to a build
    without the block (pinned by ``tests/unit/test_numerics.py``)."""

    enabled: bool = False
    # 1-in-N steps reads the EF-residual gauges (training) and runs the
    # serving fidelity probes; <= 0 never does
    sample_every: int = 16
    # in-jit divergence sentinel: digests the params on sampled steps and
    # compares replicas across the mesh axes each leaf is replicated over
    sentinel: bool = True
    sentinel_sample_every: int = 16
    # what a confirmed cross-replica divergence does: "log" (counter +
    # loud warning + profiler capture arm) or "abort" (raise
    # TrainingHealthError through the diagnostics manager, dumping the
    # flight recorder when one is live)
    divergence_policy: str = "log"  # log | abort
    # spec-decode acceptance-rate trend alarm (PR-2 median+MAD, low side)
    spec_accept_window: int = 64
    spec_accept_mads: float = 6.0
    spec_accept_min_n: int = 8


class SnapshotConfig(DeepSpeedConfigModel):
    """snapshot section — elastic async sharded snapshots
    (``checkpoint/snapshot.py``). At every ``every_n_steps`` step boundary the
    engine copies the canonical fp32 train state device→host (the one
    synchronous cost) and a background thread serializes, checksums, fsyncs
    and atomically commits it under ``<dir>/snapshots/<tag>`` with a
    ``latest`` pointer updated only after durability — the step clock never
    blocks on disk, and a crash mid-save can never publish a torn snapshot.
    Snapshots restore onto ANY mesh (``engine.restore_snapshot`` /
    ``elasticity.run_resilient``). See ``docs/elastic.md``."""

    enabled: bool = False
    dir: Optional[str] = None  # snapshot base directory (required when enabled)
    every_n_steps: int = 100  # snapshot cadence in optimizer-step boundaries
    keep: int = 2  # committed snapshots retained (older ones pruned)
    shard_megabytes: int = 64  # per-shard-file ceiling (atoms sliced on dim 0)
    fsync: bool = True  # fsync shards+manifest before commit (durability)
    blocking: bool = False  # debug: write synchronously at the boundary


class RecoveryConfig(DeepSpeedConfigModel):
    """recovery section — the auto-recovery policy ``elasticity.run_resilient``
    applies when diagnostics abort a run (``TrainingHealthError``) or a
    snapshot turns out corrupt: dump the flight recorder, rewind to the
    last-good snapshot with exponential backoff, re-arm the health monitor,
    and give up (re-raise, naming the flight record) after
    ``max_rewinds_per_snapshot`` rewinds land on the SAME snapshot — a fault
    that reproduces from identical state is deterministic, not transient."""

    max_rewinds_per_snapshot: int = 2  # same-snapshot rewinds before giving up
    max_total_rewinds: int = 8  # across the whole run
    backoff_base_s: float = 1.0  # first-rewind sleep; doubles per consecutive rewind
    backoff_max_s: float = 60.0


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = Field(default_factory=list)


class PipelineConfig(DeepSpeedConfigModel):
    stages: Union[int, str] = "auto"
    partition: str = "parameters"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    use_reentrant: bool = True


class GradientCompressionConfig(DeepSpeedConfigModel):
    enabled: bool = False
    bits: int = 1  # 1-bit Adam analog via sign+error-feedback compression


class DataEfficiencyConfig(DeepSpeedConfigModel):
    enabled: bool = False
    seed: int = 1234
    data_sampling: Dict[str, Any] = Field(default_factory=dict)
    data_routing: Dict[str, Any] = Field(default_factory=dict)


class MoEAutotuneConfig(DeepSpeedConfigModel):
    """moe_autotune section — host-side capacity-factor controller
    (``runtime/engine.py``): consumes the ``moe/*`` dispatch gauges the MoE
    gate already computes (telemetry + ``moe_metrics``) at the existing
    ``steps_per_print`` sync cadence and moves the gate's *effective*
    capacity factor between steps, inside configured bounds. Jit-cache
    stable by construction: the capacity ARRAYS are padded to a static
    ceiling (``TransformerConfig.moe_capacity_factor_max``, which the
    engine installs from ``max_factor`` via the same rebuild hook the moe
    gauges use) and the controller only moves the traced drop cutoff
    WITHIN that preallocated bucket — one compiled program, a scalar knob
    threaded through the batch (key ``moe_capacity_factor``)."""

    enabled: bool = False
    # drop rate above this raises capacity (the controller's error signal);
    # at-or-below it, a balanced dispatch lowers capacity to reclaim the
    # dead padding FLOPs
    target_drop_rate: float = 0.01
    # controller bounds on the effective factor. ``max_factor`` is also the
    # static padding ceiling the capacity arrays are sized by (the bucket).
    min_factor: float = 1.0
    max_factor: float = 2.0
    # asymmetric steps (raise fast on drops, decay slowly when balanced —
    # drops hurt the loss, slack only hurts the step time)
    increase_step: float = 0.25
    decrease_step: float = 0.0625
    # only lower capacity while expert load balance (E * sum(share^2), 1.0
    # = uniform) is below this — an imbalanced dispatch needs its headroom
    balance_threshold: float = 1.25


class EngineConfig(DeepSpeedConfigModel):
    """Top-level typed config (reference ``DeepSpeedConfig`` runtime/config.py:708)."""

    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None

    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    gradient_clipping: float = 0.0
    sparse_gradients: bool = False
    disable_allgather: bool = False

    seed: int = 1234

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = Field(default_factory=FP16Config)
    bf16: BF16Config = Field(default_factory=BF16Config)
    zero_optimization: ZeroConfig = Field(default_factory=ZeroConfig)
    mesh: MeshConfig = Field(default_factory=MeshConfig)
    activation_checkpointing: ActivationCheckpointingConfig = Field(
        default_factory=ActivationCheckpointingConfig
    )
    tensorboard: TensorboardConfig = Field(default_factory=TensorboardConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    flops_profiler: FlopsProfilerConfig = Field(default_factory=FlopsProfilerConfig)
    comms_logger: CommsLoggerConfig = Field(default_factory=CommsLoggerConfig)
    telemetry: TelemetryConfig = Field(default_factory=TelemetryConfig)
    diagnostics: DiagnosticsConfig = Field(default_factory=DiagnosticsConfig)
    numerics: NumericsConfig = Field(default_factory=NumericsConfig)
    hbm_guard: HBMGuardConfig = Field(default_factory=HBMGuardConfig)
    snapshot: SnapshotConfig = Field(default_factory=SnapshotConfig)
    recovery: RecoveryConfig = Field(default_factory=RecoveryConfig)
    pipeline: PipelineConfig = Field(default_factory=PipelineConfig)
    data_efficiency: DataEfficiencyConfig = Field(default_factory=DataEfficiencyConfig)
    moe_autotune: MoEAutotuneConfig = Field(default_factory=MoEAutotuneConfig)
    gradient_compression: GradientCompressionConfig = Field(default_factory=GradientCompressionConfig)

    # Inference / misc sections accepted for schema parity
    communication_data_type: Optional[str] = None
    checkpoint: Dict[str, Any] = Field(default_factory=dict)
    elasticity: Dict[str, Any] = Field(default_factory=dict)
    autotuning: Dict[str, Any] = Field(default_factory=dict)
    compression_training: Dict[str, Any] = Field(default_factory=dict)


class DeepSpeedTPUConfig:
    """Parsed + resolved config. The runtime-facing object.

    Resolves the batch-size triad against the mesh's data-parallel world size
    exactly as the reference does (``runtime/config.py:938-1045``).
    """

    def __init__(self, config: Union[str, Dict[str, Any], None] = None, dp_world_size: Optional[int] = None):
        if config is None:
            config = {}
        if isinstance(config, str):
            with open(config, "r") as f:
                config = json.load(f)
        if not isinstance(config, dict):
            raise ValueError(f"Expected a dict or a path to a JSON file, got {type(config)}")
        self.raw: Dict[str, Any] = dict(config)
        self.model = EngineConfig(**config)
        self._dp_world_size = dp_world_size
        self._resolve_batch_triad()

    # -- batch triad -------------------------------------------------------
    def _resolve_batch_triad(self) -> None:
        m = self.model
        train = m.train_batch_size
        micro = m.train_micro_batch_size_per_gpu
        gas = m.gradient_accumulation_steps
        dp = self._dp_world_size or 1

        if train is not None and micro is not None and gas is not None:
            if train != micro * gas * dp:
                raise ValueError(
                    f"Inconsistent batch config: train_batch_size={train} != "
                    f"micro_batch({micro}) * gas({gas}) * dp_world({dp})"
                )
        elif train is not None and micro is not None:
            gas = train // (micro * dp)
            if train % (micro * dp) != 0 or gas == 0:
                raise ValueError(
                    f"train_batch_size={train} not divisible by micro_batch({micro}) * dp_world({dp})"
                )
        elif train is not None and gas is not None:
            micro = train // (gas * dp)
            if train % (gas * dp) != 0 or micro == 0:
                raise ValueError(
                    f"train_batch_size={train} not divisible by gas({gas}) * dp_world({dp})"
                )
        elif micro is not None:
            gas = gas or 1
            train = micro * gas * dp
        elif train is not None:
            micro = train // dp
            gas = 1
            if train % dp != 0 or micro == 0:
                raise ValueError(f"train_batch_size={train} not divisible by dp_world({dp})")
        else:
            # only gas given (or nothing): micro defaults to 1
            micro = 1
            gas = gas or 1
            train = micro * gas * dp

        m.train_batch_size = train
        m.train_micro_batch_size_per_gpu = micro
        m.gradient_accumulation_steps = gas

    # -- convenience accessors --------------------------------------------
    @property
    def train_batch_size(self) -> int:
        return self.model.train_batch_size

    @property
    def train_micro_batch_size_per_gpu(self) -> int:
        return self.model.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.model.gradient_accumulation_steps

    @property
    def zero_config(self) -> ZeroConfig:
        return self.model.zero_optimization

    @property
    def zero_enabled(self) -> bool:
        return self.model.zero_optimization.stage > 0

    @property
    def fp16_enabled(self) -> bool:
        return self.model.fp16.enabled

    @property
    def bf16_enabled(self) -> bool:
        return self.model.bf16.enabled

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.model.bf16.enabled:
            return jnp.bfloat16
        if self.model.fp16.enabled:
            return jnp.float16
        return jnp.float32

    @property
    def gradient_clipping(self) -> float:
        return self.model.gradient_clipping

    @property
    def mesh_config(self) -> MeshConfig:
        return self.model.mesh

    def print_config(self, name: str = "DeepSpeedTPUConfig") -> None:
        logger.info(f"{name}:\n{json.dumps(self.model.model_dump(), indent=2, default=str)}")
