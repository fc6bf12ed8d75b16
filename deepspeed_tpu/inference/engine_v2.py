"""Continuous-batching inference engine (FastGen analog).

TPU-native analog of reference ``InferenceEngineV2``
(``inference/v2/engine_v2.py:30``): sequences identified by uid, tokens pushed
via ``put(uids, tokens)``, KV state lives in a paged pool addressed through
per-sequence block tables, and admission control (``can_schedule``/``query``)
lets a serving loop pack prefill chunks and decodes into one step.

Differences from the reference, by TPU design:
  - one jitted ragged step program per (rows, chunk) bucket instead of a
    kernel zoo; the paged gather/attention lives in ``paged.py``
  - the scheduler-facing API is identical in shape, but scheduling quanta are
    bucket sizes (static shapes) rather than arbitrary token counts

Serving fast path (the host leaves the per-token critical path):
  - sampling is fused into the jitted step programs, so decode dispatches
    return token ids, not ``[rows, vocab]`` logits — no per-token logits D2H
  - decode runs as a K-step chained program (``paged.ragged_decode_chain``):
    one dispatch and one host sync per K decoded tokens, with per-row
    EOS/budget masking inside the ``lax.scan``; the scheduler admits and
    preempts at chain boundaries, and the chain length auto-shrinks to honor
    ``max_new_tokens`` and KV-pool pressure (``decode_chain=1`` reproduces
    the per-token loop's outputs exactly)
  - one chain stays queued behind the running one (a chain ahead): chain N+1
    is dispatched from chain N's own carry on the device before chain N's
    tokens are fetched, wherever the next boundary has nothing to decide
    (``generate``; ``decode_chain(..., ahead=True)``)
  - batch assembly writes into preallocated per-bucket staging buffers
    (``ragged.BatchStaging``), and all scheduler bookkeeping is O(1) amortized
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from pydantic import Field

from deepspeed_tpu.config.config_utils import DeepSpeedConfigModel
from deepspeed_tpu.diagnostics.anomaly import CallLog, CallRecord, StallRecord
from deepspeed_tpu.inference.config import _DTYPES, QuantConfig, ServingSLOConfig
from deepspeed_tpu.inference.lifecycle import LifecycleTracker
from deepspeed_tpu.inference.cache import PagedKVPool, Pools, cache_plan
from deepspeed_tpu.inference.paged import (
    MigrationBuffer,
    copy_pool_blocks,
    export_pool_blocks,
    fetch_pool_block,
    import_pool_blocks,
    ragged_decode_chain,
    ragged_forward,
    ragged_spec_decode_chain,
)
from deepspeed_tpu.inference.ragged import (
    BatchStaging,
    PrefixCache,
    RaggedBatch,
    StateManager,
    build_ragged_batch,
)
from deepspeed_tpu.inference.sampling import sample_logits
from deepspeed_tpu.models.transformer import TransformerConfig, causal_lm_partition_rules
from deepspeed_tpu.parallel.autotp import place_parameters
from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.telemetry.fleet import note_step as _fleet_note_step
from deepspeed_tpu.topology.mesh import build_mesh, set_mesh
from deepspeed_tpu.utils.logging import log_dist, logger


class RaggedInferenceConfig(DeepSpeedConfigModel):
    """v2 engine config (reference ``RaggedInferenceEngineConfig``:
    state-manager + KV-cache sizing)."""

    dtype: str = "bf16"
    tp_size: int = 1
    # Expert-parallel serving (ISSUE 15): width of the mesh's ``ep`` axis.
    # Expert weights shard over ep at placement (moe_partition_rules) and
    # the MoE block's dispatch/combine runs through the facade all_to_all
    # (model._moe_ep_collective — exact no-drop routing, so an ep>1 engine
    # decodes token-identical to ep=1 on the same checkpoint). The serving
    # router is oblivious: replicas declare capacity, not topology.
    ep_size: int = 1
    kv_block_size: int = 16
    num_kv_blocks: int = 512
    # Quantized KV-cache storage (ISSUE 10): None = pool in ``dtype``;
    # "int8" | "fp8" = pool holds 1-byte values + one fp32 scale per
    # (layer, slot, kv-head) head vector (the shared ops.quant block math),
    # dequant fused into the paged-attention block loads. ~1.9x the token
    # slots per HBM byte at head_dim>=64 — the admission-capacity lever.
    kv_cache_dtype: Optional[str] = None
    # Byte budget for the paged pool: when set, ``num_kv_blocks`` is DERIVED
    # as kv_blocks_for_bytes(kv_pool_bytes, ...) with the real (quantized or
    # dense) block bytes — fixed HBM, variable capacity. None keeps the
    # explicit num_kv_blocks.
    kv_pool_bytes: Optional[int] = None
    # Weight-only quantization for the serving weights (inference/woq.py —
    # same QuantConfig as v1 init_inference, incl. per-tensor-class
    # selection): int8/int4/fp8 bytes in HBM, dequant at the matmul boundary.
    quant: QuantConfig = Field(default_factory=QuantConfig)
    max_seqs: int = 64  # max concurrently tracked sequences
    max_seq_len: Optional[int] = None  # default: model max_seq_len
    row_bucket: int = 8
    chunk_bucket: int = 16
    # Admission by tokens (reference ``RaggedInferenceEngineConfig``'s
    # state-manager field of the same name): one prefill call of ``generate``
    # takes queued prompts while the call's PADDED tokens (rows rounded to
    # row_bucket x the longest prompt rounded to chunk_bucket) stay within
    # it; the first prompt of a call is always taken, and the rest go into
    # further calls, one after another, before the round's decode chain. None:
    # every queued prompt that fits the pool goes into one call, whatever its
    # length.
    max_ragged_batch_size: Optional[int] = None
    # K decode iterations per dispatched program (paged.ragged_decode_chain):
    # one dispatch + one host sync per K decoded tokens. 1 = per-token loop
    # (same outputs, K× the dispatch/sync overhead). The effective chain
    # shrinks automatically near max_new_tokens and under KV-pool pressure.
    decode_chain: int = 8
    # Content-hash prefix cache over the paged pool (ISSUE 12): finished
    # prefill blocks are indexed by position-aligned token-chain hash and
    # kept alive by allocator refcounts, so a later prompt sharing the
    # prefix reuses the QUANTIZED block bytes directly (zero re-prefill,
    # zero re-quantization); a partially matching block is reused via
    # copy-on-write at the first divergent token. Off by default — the
    # decode fast path is byte-identical when disabled.
    prefix_cache: bool = False
    # Cap on cache-held blocks as a fraction of the pool
    # (utils/hbm.prefix_cache_capacity_blocks) — cache-aware pool sizing:
    # the cache can never starve live sequences below (1-fraction) of the
    # pool, and admission pressure evicts LRU entries before preempting.
    prefix_cache_fraction: float = 0.5
    # Record a blake2b digest of each cached block's quantized pool bytes at
    # insert (one jitted fetch + D2H per NEW block, prefill-boundary only).
    # The digest is the cached artifact's integrity identity — the
    # correctness harness and the nightly smoke compare it at hit time.
    # Lookups key on token-chain hashes either way, so latency-critical
    # deployments can turn the fetch off without changing cache behavior.
    prefix_cache_hash_bytes: bool = True
    # Disaggregated serving role (ISSUE 14): which phase this replica serves
    # under a phase-aware ServingRouter. "mixed" (default) serves both —
    # the engine-only behavior, byte-identical to before. "prefill" replicas
    # take fresh admissions and hand finished prefills to the decode pool
    # via KV-block migration; "decode" replicas never take fresh admissions,
    # they re-admit migrated requests and run their decode chains. The role
    # only steers the router's placement — every engine can run every
    # program (that is what the mixed-mode fallback relies on).
    role: str = "mixed"
    # In-flight post-prefill export cap per replica (double-buffered page
    # streaming: the export of request N overlaps the prefill of N+1).
    migration_depth: int = 2
    # Speculative decoding (ISSUE 12): number of draft tokens verified per
    # model forward inside the decode chain (0 = off). Drafts come from an
    # on-device n-gram (prompt-lookup) proposer over the row's history;
    # verify-and-accept runs in the SAME jitted chain program — still one
    # dispatch + one host sync per chain, >1 accepted token per forward on
    # agreeable text. Greedy-only (acceptance compares argmax targets).
    spec_decode: int = 0
    spec_ngram: int = 2  # n-gram length the proposer matches on
    # Pre-flight HBM-fit check (utils/hbm.py) before param/pool
    # materialization: "warn" | "refuse" | "off".
    hbm_check: str = "warn"
    # SLO targets for the per-request lifecycle metrics (TTFT/TPOT goodput —
    # inference/lifecycle.py). Tracking itself keys off the telemetry tracer;
    # this block only sets the targets and rolling-window length.
    serving_slo: ServingSLOConfig = Field(default_factory=ServingSLOConfig)
    # Serving flight-recorder mode (diagnostics/flight_recorder.py): keep a
    # bounded ring of per-request records (id, phase stamps, chain count) so
    # a crashed serving run's post-mortem names the in-flight requests.
    flight_recorder: bool = False

    @property
    def jax_dtype(self):
        return _DTYPES[self.dtype.lower()]

    @property
    def validated_role(self) -> str:
        if self.role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be prefill|decode|mixed, got {self.role!r}")
        return self.role

    @property
    def kv_quant(self) -> Optional[str]:
        """None | 'int8' | 'fp8' — quantized-storage mode of the KV pool."""
        name = (self.kv_cache_dtype or "").lower()
        if name in ("int8", "fp8"):
            return name
        if name and name not in _DTYPES:
            raise ValueError(
                f"kv_cache_dtype must be a float dtype name or 'int8'|'fp8', "
                f"got {self.kv_cache_dtype!r}")
        return None

    @property
    def kv_jax_dtype(self):
        """Pool storage dtype when NOT block-quantized (default: compute)."""
        if self.kv_quant is not None or not self.kv_cache_dtype:
            return self.jax_dtype
        return _DTYPES[self.kv_cache_dtype.lower()]

    @property
    def kv_dtype_name(self) -> str:
        """The label the serving gauges carry ('int8'/'fp8'/float name)."""
        return (self.kv_cache_dtype or self.dtype).lower()


@dataclasses.dataclass
class _ChainInFlight:
    """A decode chain that is dispatched and not fetched
    (``InferenceEngineV2.decode_chain``): what the host asked of it, and the
    program's outputs, still on the device."""

    chain_id: int
    n_rows: int             # the program's rows, a row bucket
    uids: List[int]         # the rows that hold a request, in the caller's order
    at: np.ndarray          # [n] the program's row of each
    start: np.ndarray       # [n] seen_tokens where the chain starts
    budgets: np.ndarray     # [n] what each row may still emit, this chain and after
    k: int
    eos_id: Optional[int]
    sample_kw: Tuple
    rng_in: Any             # the key it was dispatched with, the caller's own object
    call: CallRecord        # its record in ``engine.calls``: the stamps of its serve:dispatch span
    # the program's outputs, on the device
    out: jax.Array          # [n_rows, k] tokens
    emitted: jax.Array      # [n_rows]
    carry: Tuple            # (tokens, start_pos, active) of the chain after it
    rng_out: jax.Array
    routed: Tuple           # a routed model's (touched, picks), else empty
    # the rows whose seen_tokens already stand k further, where this chain
    # ends unless an EOS ends them: set when the chain after it is planned
    moved: np.ndarray       # [n] bool
    # ahead only, set once the chain before it is fetched:
    last: Optional[np.ndarray] = None   # [n] the token each row starts from


class _CallSpan:
    """A call's ``serve:dispatch`` or ``serve:fetch`` span with the host's
    stamps where it opens and closes (``CallRecord``): the one place that
    writes either. A fetch that closes gives the call its cadence, on the span
    as ``cadence_ms``, and hands a verdict on the slow call before it, if one
    is due, to ``InferenceEngineV2._stalled`` once the span is closed."""

    __slots__ = ("_engine", "_rec", "_fetch", "_span", "_inner")

    def __init__(self, engine: "InferenceEngineV2", rec: CallRecord, fetch: bool, args: Dict[str, Any]):
        self._engine, self._rec, self._fetch = engine, rec, fetch
        self._span = engine._tracer.span("serve:fetch" if fetch else "serve:dispatch", kind=rec.kind, **args)

    def __enter__(self):
        self._inner = self._span.__enter__()
        stamp = self._engine._log.stamp()
        if self._fetch:
            self._rec.fetch_open = stamp
        else:
            self._rec.dispatch_open = stamp
        return self._inner

    def __exit__(self, *exc):
        log, rec = self._engine._log, self._rec
        stamp = log.stamp()
        if not self._fetch:
            rec.dispatch_close = stamp
            log.host_span("serve:dispatch", stamp.wall - rec.dispatch_open.wall)
            return self._span.__exit__(*exc)
        rec.fetch_close = stamp
        stall = None
        if exc[0] is None:
            stall = log.fetched(rec)
            self._inner.set_metadata(cadence_ms=round(rec.cadence_s * 1e3, 3))
        out = self._span.__exit__(*exc)
        if stall is not None:
            self._engine._stalled(stall)
        return out


class _HostSpan:
    """A host span of the loop that the call log times (two clock reads): of
    those between two fetches the longest is named where the host was busy."""

    __slots__ = ("_log", "_name", "_span", "_t0")

    def __init__(self, log: CallLog, name: str, span):
        self._log, self._name, self._span = log, name, span

    def __enter__(self):
        inner = self._span.__enter__()
        self._t0 = time.perf_counter()
        return inner

    def __exit__(self, *exc):
        self._log.host_span(self._name, time.perf_counter() - self._t0)
        return self._span.__exit__(*exc)


def build_hf_engine(
    path: str,
    config: Union["RaggedInferenceConfig", Dict, None] = None,
    mesh: Optional[Mesh] = None,
) -> "InferenceEngineV2":
    """One call from a HuggingFace checkpoint directory to a serving engine
    (reference ``inference/v2/engine_factory.py:69 build_hf_engine`` — there a
    policy zoo maps each family onto kernel containers; here the 13-family
    ingestion in ``checkpoint/hf.py`` produces the generic ragged
    transformer's pytree directly)."""
    from deepspeed_tpu.checkpoint.hf import load_hf_checkpoint

    model_config, params = load_hf_checkpoint(path)
    return InferenceEngineV2(model_config, params, config, mesh=mesh)


class InferenceEngineV2:
    """uid-keyed continuous batching over a paged KV pool."""

    def __init__(
        self,
        model_config: TransformerConfig,
        params: Any,
        config: Union[RaggedInferenceConfig, Dict, None] = None,
        mesh: Optional[Mesh] = None,
    ):
        if config is None:
            config = {}
        if isinstance(config, dict):
            config = RaggedInferenceConfig(**config)
        config.validated_role  # raise on a bad disagg role before any work
        self.model_config = model_config
        self.config = config
        if mesh is None:
            axes = {"tp": config.tp_size, "dp": -1}
            if config.ep_size > 1:
                axes["ep"] = config.ep_size
            mesh = build_mesh(axis_sizes=axes)
        self.mesh = mesh
        set_mesh(mesh)
        ep = mesh.shape.get("ep", 1)
        if ep > 1:
            if model_config.num_experts <= 0:
                raise ValueError(f"ep_size={ep} on a dense model: expert parallelism needs num_experts > 0")
            if model_config.num_experts % ep:
                raise ValueError(f"num_experts={model_config.num_experts} not divisible by ep_size={ep}")
            log_dist(f"expert-parallel serving: experts sharded over ep={ep}, MoE dispatch/combine through the "
                     "facade all_to_all", ranks=[0])

        max_len = config.max_seq_len or model_config.max_seq_len
        self.max_seq_len = max_len
        # What this model keeps of a sequence (inference/cache.py): the classes of page a row holds, the
        # row's table layout, the state slot, their bytes, and what the kind does not serve with
        self.plan = plan = cache_plan(model_config, config.kv_block_size, max_len)
        for refused in plan.refusals(config, mesh):
            raise ValueError(refused)
        self.max_pages = plan.max_pages
        self.windows_closed = 0  # EVA: windows pooled into summaries so far
        self.ring_pages_overwritten = 0  # ring pages a later block of the same row has been written over
        if model_config.hc_mult and mesh.shape["tp"] > 1:
            raise ValueError(f"hyper-connections (hc_mult={model_config.hc_mult}) with tp={mesh.shape['tp']}: the mix "
                             "is a statistic and a product over the whole hidden width of every stream, which the "
                             "partition rules replicate and nothing has needed split yet")

        dtype = config.jax_dtype
        kv_quant = config.kv_quant
        kv_dtype = config.kv_jax_dtype
        # The real (quantized or dense) per-token pool cost — ONE formula
        # shared with the pre-flight guard and the capacity benchmark.
        self.kv_bytes_per_token = plan.bytes_per_token(kv_dtype, kv_quant)
        # a routed model's programs hand out the experts they sent each token
        # to, as one more output (fetched only by *_with_picks)
        self._routed = model_config.num_experts > 0
        # ``put_with_selected``: a ``put`` whose program hands out what an indexed model's queries kept
        self._hand_selected = False
        self._selected_log: Optional[List[Any]] = None
        self.picks_log: Optional[List[Dict[str, Any]]] = None
        self.last_experts_touched: Optional[float] = None
        self.last_experts_read: Optional[float] = None  # what the decode product read: dead rows' picks too
        self.last_held_visits: Optional[float] = None  # of a chip's share: visits a step to its experts, a layer
        # under a learned indexer: cached tokens a live row's query scored and kept, a layer, in the newest chain
        self.last_tokens_scored: Optional[float] = None
        self.last_tokens_kept: Optional[float] = None
        # and in the serving loop's newest prefill of a chunk: (queries fed, scored and kept a query and layer)
        self.last_prefill_kept: Optional[Tuple[int, float, float]] = None
        if model_config.expert_parallel is not None and ep > 1:
            raise ValueError(f"expert_parallel={model_config.expert_parallel} on a mesh with ep={ep}: the "
                             "model is ONE chip's share of its layer, and the exchange between the chips is not built")
        # The ring's class of page: every seat its whole ring (a row takes its pages as it grows to a window
        # and keeps them to its flush, so no seat can be short of one), at a token's bytes a sliding layer
        self.ring_blocks = config.max_seqs * plan.ring_columns
        self.ring_bytes = plan.ring_bytes(self.ring_blocks, kv_dtype)
        if config.kv_pool_bytes is not None:
            # byte-budget sizing: admission capacity follows the REAL block
            # bytes, so an int8 pool at the same budget admits ~1.9x the
            # concurrent requests of a bf16 one (of two classes of page, the
            # ring's come off the budget first, the first class takes the rest)
            num_blocks = max((int(config.kv_pool_bytes) - self.ring_bytes)
                             // (config.kv_block_size * self.kv_bytes_per_token), 1)
        else:
            num_blocks = config.num_kv_blocks
        self.num_kv_blocks = num_blocks
        self.state = StateManager(num_blocks, config.kv_block_size, config.max_seqs, layout=plan.layout,
                                  state_slots=config.max_seqs if plan.state else None, ring_blocks=self.ring_blocks)
        self._staging = BatchStaging(self.max_pages)
        self.prefix_cache: Optional[PrefixCache] = None
        if config.prefix_cache:
            from deepspeed_tpu.utils.hbm import prefix_cache_capacity_blocks

            self.prefix_cache = PrefixCache(self.state.allocator, config.kv_block_size, capacity_blocks=(
                prefix_cache_capacity_blocks(num_blocks, config.prefix_cache_fraction)))

        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        tp = max(mesh.shape["tp"], 1)
        heads = plan.classes[0].heads  # (a row that is one slab for all heads has nothing to split over tp)
        kv_on_tp = heads > 0 and heads % tp == 0
        quantize = None  # weight-only quantization of the serving weights (inference/woq.py)
        if config.quant.enabled:
            from deepspeed_tpu.inference.woq import quantize_params, quantized_bytes_estimate, woq_format

            woq = dict(min_size=config.quant.min_leaf_size, classes=config.quant.tensor_classes)
            quantize = functools.partial(quantize_params, fmt=woq_format(config.quant), **woq)
        # Compiled-program registry (telemetry/programs.py): the v2 step programs are wrapped at build time
        # when capture is live, and the pre-flight byte estimate below doubles as the serving-scope
        # calibration baseline for hbm/estimate_ratio.
        from deepspeed_tpu.telemetry.programs import get_program_registry

        self._programs = get_program_registry()
        if config.hbm_check != "off" or self._programs.enabled:
            # Refuse/warn BEFORE any device materialization: PER-DEVICE bytes — params shard over tp (autotp
            # partition rules), the KV pool shards over tp only when kv_heads divides — plus a [rows, vocab]
            # logits buffer and a step's attention workspace. Quantized storage enters with its REAL byte
            # formulas: a pool/model that only fits quantized is admitted, an over-budget one refused before
            # placement.
            from deepspeed_tpu.utils.hbm import check_hbm_fit

            dtype_b = jnp.dtype(dtype).itemsize
            if quantize is not None and tp == 1:
                param_bytes = quantized_bytes_estimate(params, woq_format(config.quant), dense_itemsize=dtype_b, **woq)
            else:
                # tp>1 places dense shards first (WOQ quantizes in place after — see below), so the dense
                # tp-shard bytes ARE the placement peak
                param_bytes = n_params * dtype_b // tp
            kv_bytes = (num_blocks * config.kv_block_size * self.kv_bytes_per_token
                        + plan.state_bytes(config.max_seqs, dtype))
            need = (param_bytes + kv_bytes // (tp if kv_on_tp else 1)
                    + config.row_bucket * model_config.vocab_size * 4 + plan.workspace_bytes(config, dtype_b))
            if config.hbm_check != "off":
                check_hbm_fit(need, what="InferenceEngineV2 init (params + KV pool)", mode=config.hbm_check)
            self._programs.set_hbm_estimate(need, scope="serving")
        # WOQ before placement where tp == 1 (the dense weights never hit the device): int8/int4/fp8 values +
        # fp32 scales, dequant at each matmul boundary with compute-dtype accumulation. tp>1 instead places the
        # dense shards and quantizes after — the pre-quantized flat layout would place replicated, costing MORE
        # per device than a dense tp shard for tp>2.
        if quantize is not None and tp == 1:
            params = quantize(params)
        self.params = place_parameters(params, mesh, causal_lm_partition_rules, dtype)
        if quantize is not None and tp > 1:
            self.params = jax.jit(quantize)(self.params)
        # The pools. The first class: the merged kvH*hd dim over tp (contiguous head groups, so each rank
        # holds its own heads' lanes), pages replicated over dp; every sequence a state slot: as many as seats
        pools = plan.init(num_blocks, self.ring_blocks, config.max_seqs, kv_dtype, kv_quant, state_dtype=dtype)
        if not kv_on_tp and tp > 1:
            # correct but a quiet perf/memory cliff: each tp rank holds the FULL pool instead of 1/tp of it
            log_dist(f"KV pool REPLICATED over tp={tp}: kv_heads={model_config.kv_heads} not "
                     "divisible — expect tp-times the per-chip KV memory; pick tp dividing kv_heads to shard it",
                     ranks=[0])
        kv_spec = NamedSharding(mesh, P(None, None, "tp" if kv_on_tp else None))
        # scales: 4/hd of the values, slot-major rows. Also where every step
        # program's small outputs land, so the host's operands are placed there too
        self._replicated = replicated = NamedSharding(mesh, P())
        # what every step program takes, donated, and its result is assigned to
        self.pools = Pools(
            PagedKVPool(*(a if a is None else jax.device_put(a, kv_spec if i < 2 else replicated)
                          for i, a in enumerate(pools.kv))),
            *jax.device_put((pools.state, pools.ring), replicated))
        by_class = "" if plan.ring is None else (
            f"; two classes of page: global {num_blocks * config.kv_block_size * self.kv_bytes_per_token} B, "
            f"ring {self.ring_blocks}x{config.kv_block_size} slots a sliding layer {self.ring_bytes} B")
        log_dist(f"InferenceEngineV2: {n_params/1e6:.1f}M params, {num_blocks}x{config.kv_block_size} KV slots "
                 f"[{config.kv_dtype_name}, {self.kv_bytes_per_token} B/token], mesh={dict(mesh.shape)}" + by_class)
        self._step_cache: Dict[Tuple, Any] = {}
        self._chain_buf: Dict[int, Dict[str, np.ndarray]] = {}
        self._ahead: Optional[_ChainInFlight] = None  # the chain dispatched ahead, if any
        self._spec_buf: Dict[int, Dict[str, np.ndarray]] = {}
        self._tracer = get_tracer()
        # Serving flight recorder (opt-in): per-request ring so a crash dump
        # names the in-flight requests even with the tracer disabled.
        self._recorder = None
        if config.flight_recorder:
            from deepspeed_tpu.diagnostics.flight_recorder import FlightRecorder, install_process_hooks

            self._recorder = FlightRecorder(request_capacity=max(2 * config.max_seqs, 32))
            self._recorder.set_context(kind="serving", max_seqs=config.max_seqs, decode_chain=config.decode_chain,
                                       kv_blocks=self.num_kv_blocks)
            install_process_hooks()
        # Most recent generate()'s per-request tracker (None when telemetry is disabled and no recorder is
        # configured — no records allocated).
        self.lifecycle: Optional[LifecycleTracker] = None
        # Serving-loop accounting (always on — plain int adds). The parity tests assert the dispatch/sync
        # contract on these; the serving benchmark and telemetry gauges read them too.
        self.dispatch_count = 0        # compiled programs dispatched
        self.host_sync_count = 0       # host blocking fetches
        self.tokens_decoded = 0        # decode tokens produced by generate()
        self.chain_steps = 0           # decode-chain dispatches (fleet liveness)
        self.chains_ahead = 0          # of the chains, dispatched while the one before was unfetched
        # The call log (always on, like the counters above: a few clock reads a call of 80-200 ms): every device
        # call's host stamps in ``self.calls``, and a slow call with its cause in ``self.stalls``
        # (diagnostics/anomaly.py; docs/diagnostics.md, "A slow call").
        self._log = CallLog()
        self._clock = time.perf_counter  # of the loop's open-loop arrivals
        # prefix-cache + speculative accounting (plain int adds; the serving benchmark and the router smoke read these)
        self.prefill_tokens_total = 0  # prompt tokens submitted for prefill
        self.prefill_tokens_cached = 0  # of those, served from the prefix cache
        self.cow_copies = 0            # copy-on-write block clones dispatched
        self.spec_model_steps = 0      # model forwards inside spec chains
        self.spec_tokens_emitted = 0   # tokens those forwards emitted

    @property
    def pool(self) -> PagedKVPool:
        """The first class of page, ``pools.kv``: read-only, for what reads the engine from outside
        (``benchmarks/runners/serve.py`` reads ``engine.pool.k.shape``; the router compares replicas' pools)."""
        return self.pools.kv

    def _state_args(self, rows: int) -> Dict[str, int]:
        """For a ``serve:dispatch`` span of a model with recurrent state, while
        somebody records spans: ``state_rows``, the live rows x steps whose
        state slots the call updates (a chain's as its budgets plan it)."""
        if self.pools.state is None or not self._tracer.recording():
            return {}
        return {"state_rows": int(rows)}

    def stats(self) -> Dict[str, int]:
        """The pool's bytes by class of page, and the pages of each the live rows hold: ``kv_global_bytes``
        (the page pool every model has) and, under a sliding kind, ``kv_ring_bytes`` beside it."""
        held = [0] * len(self.state.allocators)
        for seq in self.state._seqs.values():
            for cls, pages in zip(self.plan.layout.classes, (seq.n_summary, seq.n_window)):
                held[cls] += pages
        out = {"kv_global_bytes": self.num_kv_blocks * self.config.kv_block_size * self.kv_bytes_per_token,
               "kv_global_pages_held": held[0]}
        if self.plan.ring is not None:
            out.update(kv_ring_bytes=self.ring_bytes, kv_ring_pages_held=held[1],
                       ring_pages_overwritten=self.ring_pages_overwritten)
        return out

    @staticmethod
    def _rows_at(a, at) -> np.ndarray:
        """A program's padded output on the host, cut to the rows ``at``."""
        return np.asarray(a[at]) if isinstance(at, slice) else np.asarray(a)[at]

    # ---------------------------------------------------------------- the call log
    @property
    def calls(self):
        """The last few thousand device calls (``CallRecord``), oldest first."""
        return self._log.calls

    @property
    def stalls(self):
        """The slow calls among them, each with its cause (``StallRecord``)."""
        return self._log.stalls

    def _dispatching(self, rec: CallRecord, **args) -> _CallSpan:
        return _CallSpan(self, rec, False, args)

    def _fetching(self, rec: CallRecord, **args) -> _CallSpan:
        return _CallSpan(self, rec, True, args)

    def _host_span(self, name: str, **args) -> _HostSpan:
        return _HostSpan(self._log, name, self._tracer.span(name, **args))

    def _stalled(self, stall: StallRecord) -> None:
        """A slow call has its verdict: the line an untraced run's output
        keeps, the flight recorder's ring, and the ``serve:stall`` span, which
        in a traced run stands beside the device plane that can confirm it."""
        logger.warning(stall.line())
        if self._recorder is not None:
            self._recorder.record(stall.chain, stall._asdict(), stall=stall.cause)
        with self._tracer.span("serve:stall", **stall.span_args()):
            self._tracer.count("serving/stalls", 1.0)
            self._tracer.count("serving/stall_s", stall.excess_s)

    # ---------------------------------------------------------------- admission
    def query(self, uid: int) -> Tuple[int, int]:
        """(seen_tokens, free_kv_slots) for scheduler accounting (reference
        ``engine_v2.query`` :158)."""
        seq = self.state.get(uid)
        seen = seq.seen_tokens if seq is not None else 0
        return seen, self.state.free_blocks * self.config.kv_block_size

    def can_schedule(self, uids: Sequence[int], token_counts: Sequence[int]) -> bool:
        return self.state.can_schedule(uids, token_counts)

    def flush(self, uid: int) -> None:
        self.state.flush(uid)

    # ---------------------------------------------------------------- programs
    def _watch(self, fn, kind: str, *parts):
        """Program-registry watcher around a jitted step (identity when
        capture is off at build time — the dispatch path stays untouched;
        ``jit_cache_size`` counts ``_step_cache`` entries either way).
        The label carries every component of the step-cache key so distinct
        compiled programs never collide under one registry label."""
        if not self._programs.enabled:
            return fn
        label = f"v2:{kind}:" + "".join(str(p) for p in parts)
        return self._programs.wrap(fn, label, hbm_scope="serving")

    @staticmethod
    def _kw_tag(sample_kw: Tuple, eos_id=None) -> str:
        """Deterministic short tag for the sampling-config part of a step
        key ('' for the common default config)."""
        if not sample_kw and eos_id is None:
            return ""
        import zlib

        return f"s{zlib.crc32(repr((tuple(sample_kw), eos_id)).encode()) & 0xffff:04x}"

    def _step_fn(self, rows: int, chunk: int):
        """Mixed prefill/decode step -> last-token logits (the v2 ``put``)."""
        key = ("logits", rows, chunk, self._hand_selected)
        if key not in self._step_cache:
            cfg = self.model_config
            bs = self.config.kv_block_size
            picks, selected = self._routed, self._hand_selected

            @functools.partial(jax.jit, donate_argnums=(1,))
            def step(params, pool, tokens, positions, new_lens, block_tables):
                return ragged_forward(params, cfg, pool, tokens, positions, new_lens, block_tables, bs,
                                      with_picks=picks, with_selected=selected)

            self._step_cache[key] = self._watch(step, "step", f"r{rows}", f"c{chunk}", "sel" * selected)
        return self._step_cache[key]

    def _sample_step_fn(self, rows: int, chunk: int, sample_kw: Tuple):
        """Mixed step with sampling FUSED into the program -> token ids [N].

        ``put``-for-decode through this path returns int32 ids, not
        [rows, vocab] logits — the per-token logits D2H is gone.
        """
        key = ("sample", rows, chunk, sample_kw)
        if key not in self._step_cache:
            cfg = self.model_config
            bs = self.config.kv_block_size
            kw = dict(sample_kw)
            picks = self._routed

            @functools.partial(jax.jit, donate_argnums=(1,))
            def step(params, pool, tokens, positions, new_lens, block_tables, rng):
                logits, pool, *picked = ragged_forward(
                    params, cfg, pool, tokens, positions, new_lens, block_tables, bs,
                    with_picks=picks)
                rng, sub = jax.random.split(rng)
                toks = sample_logits(logits, sub, **kw)
                return (toks, rng, pool, *picked)

            self._step_cache[key] = self._watch(
                step, "prefill", f"r{rows}", f"c{chunk}", self._kw_tag(sample_kw))
        return self._step_cache[key]

    def _chain_fn(self, rows: int, k: int, eos_id: Optional[int], sample_kw: Tuple):
        """K-step decode chain program (paged.ragged_decode_chain): one a
        ``(rows, k)``, whether a chain starts from the host's values or from
        the carry the chain before it returned."""
        key = ("chain", rows, k, eos_id, sample_kw)
        if key not in self._step_cache:
            cfg = self.model_config
            bs = self.config.kv_block_size
            kw = dict(sample_kw, with_picks=self._routed)

            @functools.partial(jax.jit, donate_argnums=(1,))
            def chain(params, pool, tokens, start_pos, block_tables, active, budgets, rng):
                return ragged_decode_chain(
                    params, cfg, pool, tokens, start_pos, block_tables, bs,
                    active, budgets, rng, k, eos_id, **kw)

            self._step_cache[key] = self._watch(
                chain, "decode_chain", f"r{rows}", f"k{k}",
                self._kw_tag(sample_kw, eos_id))
        return self._step_cache[key]

    def _spec_chain_fn(self, rows: int, k: int, eos_id: Optional[int]):
        """Speculative K-step decode chain program
        (paged.ragged_spec_decode_chain). Keyed (rows, k) like the plain
        chain — n_spec/ngram are engine config, so one compiled program per
        (rows, K) still holds. Greedy-only by construction."""
        key = ("spec", rows, k, eos_id)
        if key not in self._step_cache:
            cfg = self.model_config
            bs = self.config.kv_block_size
            n_spec = self.config.spec_decode
            ngram = self.config.spec_ngram

            @functools.partial(jax.jit, donate_argnums=(1,))
            def chain(params, pool, tokens, start_pos, block_tables, active,
                      budgets, rng, history, hist_len):
                return ragged_spec_decode_chain(
                    params, cfg, pool, tokens, start_pos, block_tables, bs,
                    active, budgets, rng, k, eos_id, history, hist_len,
                    n_spec=n_spec, ngram=ngram)

            self._step_cache[key] = self._watch(
                chain, "spec_chain", f"r{rows}", f"k{k}", f"m{n_spec}",
                self._kw_tag((), eos_id))
        return self._step_cache[key]

    def _cow_fn(self):
        """Copy-on-write block clone (paged.copy_pool_blocks): src/dst ride
        as traced scalars, so ONE compiled program serves every COW event."""
        key = ("cow",)
        if key not in self._step_cache:
            layers = self.model_config.num_layers

            @functools.partial(jax.jit, donate_argnums=(0,))
            def cow(pool, src, dst):
                return copy_pool_blocks(pool, src, dst, layers)

            self._step_cache[key] = self._watch(cow, "cow")
        return self._step_cache[key]

    def jit_cache_size(self, kind: Optional[str] = None) -> int:
        """Number of compiled step programs (optionally of one kind:
        'logits' | 'sample' | 'chain' | 'spec' | 'cow') — recompile
        assertions in tests."""
        return sum(1 for k in self._step_cache if kind is None or k[0] == kind)

    # ---------------------------------------------------------- prefix cache
    def _block_fetch_fn(self):
        """One jitted gather program fetching a block's pool pages, every
        layer's (the block id rides as a traced scalar — eager indexing
        would compile a fresh XLA program per distinct block)."""
        key = ("blockfetch",)
        if key not in self._step_cache:
            layers = self.model_config.num_layers

            @jax.jit
            def fetch(pool, block):
                return fetch_pool_block(pool, block, layers)

            self._step_cache[key] = fetch
        return self._step_cache[key]

    def _block_content_hash(self, block: int) -> str:
        """blake2b over the block's pool bytes — for a quantized pool the
        int8/fp8 value pages AND the fp32 scale pages together (the PR-10
        layout travels as one unit). This digest is the cached artifact's
        identity: tests and the nightly smoke compare it at hit time against
        the insert-time digest to prove sharing/COW/eviction never touched
        the stored bytes, and it is taken over exactly the bytes the
        paged-attention block loads read (a hit is never re-quantized).
        Row-major, layers outermost, then the block's slots, heads and
        ``hd``: the order every pool layout so far has had."""
        import hashlib

        parts = self._block_fetch_fn()(self.pool, jnp.int32(block))
        h = hashlib.blake2b(digest_size=16)
        for arr in parts:
            if arr is not None:
                h.update(np.asarray(arr).tobytes())
        return h.hexdigest()

    def prefix_probe(self, cand: np.ndarray):
        """Prefix-cache lookup for admission accounting: returns
        ``(hit, admission_token_count)`` where the count excludes the
        tokens fully cached blocks cover. The COW clone's block is
        deliberately NOT subtracted — ``_attach_prefix`` allocates it
        outside ``can_schedule``, and counting its tokens as to-prefill
        makes the admission estimate cover that allocation. One definition
        shared by ``generate`` and the serving router."""
        pc = self.prefix_cache
        if pc is None:
            return None, len(cand)
        hit = pc.match(cand)
        return hit, len(cand) - hit.n_blocks * self.config.kv_block_size

    def _pin_hit(self, hit) -> None:
        """Take a temporary reference on every block of a PrefixHit. Between
        ``prefix_probe`` and ``_attach_prefix`` the admission path may evict
        LRU cache entries (``_can_schedule_evicting``) — without the pin,
        eviction of an entry whose ONLY holder was the cache would free the
        very blocks the hit is about to share, and the attach would raise
        mid-serving. Pinned blocks survive eviction (the entry goes, the
        bytes stay) and the pin is dropped by ``_unpin_hit`` either way."""
        if hit is not None:
            self.state.allocator.share(hit.blocks + [hit.cow_block] * (hit.cow_block is not None))

    def _unpin_hit(self, hit) -> None:
        if hit is not None:
            self.state.allocator.release(hit.blocks + [hit.cow_block] * (hit.cow_block is not None))

    def _attach_prefix(self, uid: int, hit) -> int:
        """Wire a PrefixHit into a fresh sequence: share the full cached
        blocks, clone the COW block (if any) up to the divergent token, and
        return how many prompt tokens the cache covered (== the new
        sequence's ``seen_tokens``)."""
        bs = self.config.kv_block_size
        alloc = self.state.allocator
        seq = self.state.get_or_create(uid)
        assert seq.seen_tokens == 0 and seq.n_blocks == 0
        reuse = 0
        if hit.blocks:
            alloc.share(hit.blocks)
            seq.append_blocks(np.asarray(hit.blocks, np.int32))
            reuse = len(hit.blocks) * bs
        if hit.cow_block is not None and hit.cow_len > 0:
            # hold the source across the allocation (our own allocate may
            # trigger LRU eviction, which could otherwise free the source)
            alloc.share([hit.cow_block])
            dst = self._ensure_blocks(1)
            with self._tracer.span("serve:cow", src=hit.cow_block, dst=int(dst[0])):
                self.pools = self.pools._replace(kv=self._cow_fn()(
                    self.pool, jnp.int32(hit.cow_block), jnp.int32(dst[0])))
            self.dispatch_count += 1
            alloc.release([hit.cow_block])
            seq.append_blocks(dst)
            reuse += hit.cow_len
            self.cow_copies += 1
        seq.seen_tokens = reuse
        return reuse

    def _ensure_blocks(self, n: int) -> np.ndarray:
        """Allocate ``n`` blocks, evicting LRU prefix-cache entries if the
        free stack runs short."""
        self._evict_until(lambda: self.state.free_blocks >= n)
        return self.state.allocator.allocate(n)

    def _evict_until(self, fits) -> bool:
        """Whether ``fits()``, once LRU prefix entries have released their references until it does or the cache
        is dry (cache-only blocks are reclaimed under pressure: cached prefixes never starve live traffic)."""
        while not fits():
            if self.prefix_cache is None or not self.prefix_cache.evict_one():
                return False
        return True

    def _insert_prefix(self, uid: int, full_tokens: np.ndarray) -> None:
        """Index the finished prefill's full blocks (values already in the
        pool — the entries' content hashes are snapshots of the quantized
        bytes as written)."""
        pc = self.prefix_cache
        seq = self.state.get(uid)
        if pc is None or seq is None:
            return
        hasher = (self._block_content_hash
                  if self.config.prefix_cache_hash_bytes else None)
        pc.insert(full_tokens, seq.blocks, hasher=hasher)

    def try_admit(self, uid: int, cand: np.ndarray, other_uids: Sequence[int],
                  other_counts: Sequence[int]) -> Optional[np.ndarray]:
        """ONE definition of prefix-aware admission, shared by ``generate``
        and the serving router: probe the cache, pin the hit across the
        (evicting) schedule check, attach shared/COW blocks on success, and
        account the reuse. Returns the suffix tokens still needing prefill,
        or None when the request does not fit alongside ``other_uids``
        (state unchanged — the pin is dropped either way)."""
        hit, adm_count = self.prefix_probe(cand)
        self._pin_hit(hit)
        if not self._can_schedule_evicting(
                list(other_uids) + [uid], list(other_counts) + [adm_count]):
            self._unpin_hit(hit)
            return None
        reuse = 0
        if hit is not None and (hit.blocks or hit.cow_len):
            reuse = self._attach_prefix(uid, hit)
        self._unpin_hit(hit)
        if self.prefix_cache is not None:
            self.prefix_cache.record(hit)
        self.prefill_tokens_total += len(cand)
        self.prefill_tokens_cached += reuse
        return cand[reuse:]

    # ------------------------------------------------------------- migration
    def _export_fn(self, pages: int):
        """Block-export gather program (paged.export_pool_blocks): block ids
        ride as traced values, so one compiled program per page bucket
        serves every migration. NOT donated — the source pool stays live
        (the source keeps serving while the pages stream out)."""
        key = ("export", pages)
        if key not in self._step_cache:
            mc = self.model_config

            @jax.jit
            def export(pool, blocks):
                return export_pool_blocks(pool, blocks, mc.num_layers, mc.kv_heads)

            self._step_cache[key] = self._watch(export, "export", f"p{pages}")
        return self._step_cache[key]

    def _import_fn(self, pages: int):
        """Block-import scatter program (paged.import_pool_blocks): the
        destination pool is donated like every other pool-mutating step."""
        key = ("import", pages)
        if key not in self._step_cache:

            @functools.partial(jax.jit, donate_argnums=(0,))
            def imp(pool, buf, blocks, n_valid):
                return import_pool_blocks(pool, buf, blocks, n_valid)

            self._step_cache[key] = self._watch(imp, "import", f"p{pages}")
        return self._step_cache[key]

    @staticmethod
    def _page_bucket(n: int) -> int:
        """Round a migration's page count up to the next power of two so a
        handful of compiled export/import programs serve every request
        length (the same static-shape discipline as the step buckets)."""
        b = 1
        while b < n:
            b *= 2
        return b

    def export_request(self, uid: int) -> Dict[str, Any]:
        """Export ``uid``'s KV blocks as a contiguous migration buffer
        (ISSUE 14): a read-only gather in block-table order — quantized
        bytes verbatim, scale pages riding along, refcounts untouched (a
        block the prefix cache shares is exported without disturbing its
        holders; the source releases its OWN reference only at ``flush``
        after the import commits). The dispatch is asynchronous: the pages
        stream out while the host assembles the next prefill."""
        refused = self.plan.migration_refusal()
        if refused:
            raise ValueError(refused)
        seq = self.state.get(uid)
        if seq is None or seq.n_blocks == 0:
            raise ValueError(f"uid {uid} has no KV blocks to export")
        n = seq.n_blocks
        pages = self._page_bucket(n)
        padded = np.zeros((pages,), np.int32)
        padded[:n] = seq.blocks
        with self._tracer.span("serve:export", uid=uid, blocks=n):
            buf = self._export_fn(pages)(self.pool, jnp.asarray(padded))
        self.dispatch_count += 1
        return {"buffer": buf, "n_blocks": n, "pages": pages, "seen_tokens": seq.seen_tokens, **self._wire_layout()}

    def _wire_layout(self) -> Dict[str, Any]:
        """What two pools must agree on for pages to move between them verbatim."""
        return {"block_size": self.config.kv_block_size, "quant": self.pool.quant,
                "kv_dtype": str(jnp.dtype(self.pool.k.dtype))}

    def can_import(self, n_blocks: int) -> bool:
        """Whether an ``n_blocks`` migration could be admitted right now
        (seq slot + free blocks after LRU cache eviction) — the refusal
        path the router consults so a rejected import leaves the request
        on its source instead of dropping it."""
        return (self.state.n_active < self.config.max_seqs
                and self._evict_until(lambda: self.state.free_blocks >= n_blocks))

    def import_request(self, uid: int, export: Dict[str, Any]) -> bool:
        """Import an ``export_request`` ticket as a fresh sequence ``uid``:
        allocate destination blocks (any fragmentation — the scatter IS the
        block-table rewrite), scatter the buffer verbatim, and register the
        descriptor with the source's ``seen_tokens``. Returns False —
        destination state unchanged — when capacity refuses; raises on a
        layout mismatch (pools that disagree on dtype/geometry are a
        deployment error, not a capacity condition)."""
        refused = self.plan.migration_refusal(importing=True)
        if refused:
            raise ValueError(refused)
        said = "(bs={block_size}, quant={quant}, dtype={kv_dtype})".format
        if said(**export) != said(**self._wire_layout()):
            raise ValueError(f"migration layout mismatch: source {said(**export)} vs "
                             f"destination {said(**self._wire_layout())}")
        buf: MigrationBuffer = export["buffer"]
        mc = self.model_config
        if buf.k.shape[0] != mc.num_layers or tuple(buf.k.shape[2:]) != (mc.kv_heads, mc.dims_per_head):
            raise ValueError(f"migration layout mismatch: buffer pages {buf.k.shape} vs pool of "
                             f"{mc.num_layers} layers x {mc.kv_heads} heads x {mc.dims_per_head}")
        n = export["n_blocks"]
        if not self.can_import(n):
            return False
        dst_blocks = self.state.allocator.allocate(n)
        pages = export["pages"]
        padded = np.zeros((pages,), np.int32)
        padded[:n] = dst_blocks
        try:
            with self._tracer.span("serve:import", uid=uid, blocks=n):
                self.pools = self.pools._replace(kv=self._import_fn(pages)(
                    self.pool, buf, jnp.asarray(padded), jnp.int32(n)))
        except BaseException:
            # the scatter never committed (self.pools rebinds only on
            # success): return the allocation so a failed import — which
            # the router degrades, not drops — cannot leak destination
            # capacity attempt over attempt
            self.state.allocator.free(dst_blocks)
            raise
        self.dispatch_count += 1
        seq = self.state.get_or_create(uid)
        assert seq.seen_tokens == 0 and seq.n_blocks == 0
        seq.append_blocks(dst_blocks)
        seq.seen_tokens = export["seen_tokens"]
        return True

    def chain_window(self, budgets: Sequence[int], k: int) -> List[int]:
        """KV tokens one K-step chain may consume per row: each of the K
        iterations emits up to ``1 + spec_decode`` tokens, plus the
        ``spec_decode`` transient rejected-draft slots. One formula for
        ``generate`` and the router's pressure loops (spec_decode=0 reduces
        to the plain ``min(k, budget)``)."""
        m = 1 + self.config.spec_decode
        return [min(k * m, b) + self.config.spec_decode for b in budgets]

    def _can_schedule_evicting(self, uids, counts) -> bool:
        """``can_schedule`` that reclaims cache-only blocks under pressure (``_evict_until``)."""
        return self._evict_until(lambda: self.state.can_schedule(uids, counts))

    # ---------------------------------------------------------------- EVA
    def _eva_args(self, positions: np.ndarray, lens: np.ndarray) -> Dict[str, Any]:
        """What a dispatch of an EVA model reads, for its ``serve:dispatch``
        span, while somebody records spans: ``positions`` [rows, steps] of the
        tokens it feeds, the first ``lens`` [rows] of each row real (a chain's
        as its budgets plan them).
        ``attended_rows`` sums, over those tokens, the rows attention reads
        (closed windows' summaries + the open window up to the token),
        ``context_tokens`` what full attention would read, ``row_steps`` the
        tokens, ``windows_closed`` those that end a window."""
        lay = self.plan.windows
        if lay is None or not self._tracer.recording():
            return {}
        pos = positions.astype(np.int64)
        fed = np.arange(pos.shape[1])[None, :] < np.asarray(lens)[:, None]
        return {"attended_rows": int(lay.attended(pos)[fed].sum()), "context_tokens": int((pos + 1)[fed].sum()),
                "row_steps": int(fed.sum()),
                "windows_closed": int((pos % lay.window == lay.window - 1)[fed].sum())}

    def _advance(self, uids, counts) -> int:
        """``seen_tokens`` of each uid forward by its count. Returns the
        windows that closed on the way (EVA), for ``windows_closed``."""
        rings, closed = self.plan.rings, 0
        for uid, n in zip(uids, counts):
            # (a fresh prompt writes its last window alone)
            if rings is not None and (seen := self.state.get(uid).seen_tokens):
                over = rings.overwritten(seen, int(n)) * self.plan.ring.layers
                self.ring_pages_overwritten += over
                self._tracer.count("serving/ring_pages_overwritten", float(over))
            closed += self.state.advance(uid, int(n))
        return closed

    # ---------------------------------------------------------------- put
    def _build_batch(self, uids, token_lists) -> RaggedBatch:
        with self._host_span("serve:assemble", rows=len(uids)):
            return build_ragged_batch(
                self.state, uids, token_lists, self.max_pages,
                self.config.row_bucket, self.config.chunk_bucket,
                staging=self._staging,
            )

    def put(self, uids: Sequence[int], token_lists: Sequence[np.ndarray]) -> np.ndarray:
        """Push new tokens for each uid; returns last-token logits [len(uids), V]
        (reference ``engine_v2.put`` :107). Mixed prefill/decode is fine —
        pass a whole prompt for new sequences and single tokens for decodes.

        This is the logits-returning compatibility path; the serving loop
        (``generate``) uses the fused-sampling programs instead and never
        ships logits to the host.
        """
        if not self.can_schedule(uids, [len(t) for t in token_lists]):
            raise RuntimeError("insufficient KV blocks/slots; call can_schedule first")
        per_call = self._fresh_rows_a_call(token_lists)
        outs = [self._put_call(uids[i:i + per_call], token_lists[i:i + per_call], range(i, i + per_call))
                for i in range(0, max(len(uids), 1), per_call)]
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def _put_call(self, uids, token_lists, rids) -> np.ndarray:
        """One program call of ``put``; ``rids``: the rows' places in ``put``'s own lists, for the picks' log."""
        batch = self._build_batch(uids, token_lists)
        step = self._step_fn(batch.n_rows, batch.tokens.shape[1])
        call = self._log.open("put", -1, batch.n_rows, batch.tokens.shape[1])
        with self._dispatching(call, rows=batch.n_rows,
                               **self._eva_args(batch.positions, batch.new_lens),
                               **self._state_args(len(uids)), **self._ring_args(uids),
                               **self._flash_args(batch.new_lens, batch.tokens.shape[1])):
            logits, self.pools, *picks = step(
                self.params, self.pools,
                jnp.asarray(batch.tokens), jnp.asarray(batch.positions),
                jnp.asarray(batch.new_lens), jnp.asarray(batch.block_tables),
            )
        self.dispatch_count += 1
        self._log_picks(picks, uids, rids[:len(uids)], token_lists, at=batch.at)
        if self._selected_log is not None and len(picks) > 1:
            self._selected_log.append((picks[0], batch.at))
        self.windows_closed += self._advance(uids, map(len, token_lists))
        with self._fetching(call):
            out = self._rows_at(logits, batch.at)
        self.host_sync_count += 1
        return out

    def _fresh_rows_a_call(self, token_lists) -> int:
        """Rows one ``put`` call takes. Under a sliding kind a fresh prompt computes its attention from the
        chunk's own q, k, v, every row's at once, so a call is held to the serving loop's own token budget
        (``max_ragged_batch_size`` padded tokens, one row at the least) and ``put`` feeds the rest in further
        calls of the same program; every other model takes all its rows in one call, as it always did."""
        budget = self.config.max_ragged_batch_size
        if self.plan.rings is None or not budget or not token_lists:
            return max(len(token_lists), 1)
        longest = max(map(len, token_lists))
        if longest <= 1:  # (one token a row: the ``(rows, 1)`` program, ``ragged.build_ragged_batch``)
            return len(token_lists)
        chunk = -(-longest // self.config.chunk_bucket) * self.config.chunk_bucket
        bucket = self.config.row_bucket
        return max(budget // chunk // bucket * bucket, 1)

    def put_with_selected(self, uids: Sequence[int], token_lists: Sequence[np.ndarray]):
        """``put_with_picks`` of a model with a learned indexer, and what every
        query fed kept: ``(logits, picks, selected)``, ``selected[i]`` int32
        ``[len(token_lists[i]), layers, ceil(max_pages * block / 32)]``, a
        query's mask over its row's positions packed 32 a word
        (``ops/dsa.py::pack_mask``); None where the block table holds no more
        than a query keeps. The same mathematics as ``put`` in a program of its
        own (a chunk's masks are 100 MB a call, which the serving loop's
        programs do not write)."""
        if not (self._routed and self.model_config.index_topk):
            raise ValueError("the selection asked of a model with no learned indexer (index_topk == 0) or no "
                             "routed layer")
        self._hand_selected, self._selected_log = True, []
        try:
            logits, picks = self.put_with_picks(uids, token_lists)
            if not self._selected_log:
                return logits, picks, None
            kept, at = self._selected_log[-1]
            kept = np.asarray(kept)[at]
            if kept.ndim == 3:  # a program of one token a row hands out positions, [rows, layers, index_topk]
                from deepspeed_tpu.ops import dsa

                mask = np.zeros(kept.shape[:2] + (-(-self.max_pages * self.config.kv_block_size // 32) * 32,), bool)
                rows, layers, _ = np.nonzero(kept >= 0)
                mask[rows, layers, kept[kept >= 0]] = True
                kept = np.asarray(dsa.pack_mask(mask))[:, None]
            return logits, picks, [k[:len(t)] for k, t in zip(kept, token_lists)]
        finally:
            self._hand_selected, self._selected_log = False, None

    def _log_picks(self, picks, uids, rids, token_lists=None, flight=None, emitted=None, at=None) -> None:
        """While somebody asked (``picks_log`` is a list), note one dispatch's
        picks, still on the device, with what places them: the program's row
        of each uid, its first position and how many tokens it fed (a chain's
        picks are ``[K, rows, routed layers, k]``; its rows and where they
        start are ``flight``'s, what they fed is ``emitted``; a ``put``'s rows
        are its batch's ``at``: with state slots a sequence's row is its slot).
        A ``put`` calls it before ``seen_tokens`` advances. Costs the serving
        loop one comparison."""
        if self.picks_log is None or not picks:
            return
        chain = flight is not None
        where = flight.at if chain else at  # (a put without state slots: a slice, the rows in the order given)
        self.picks_log.append({
            "picks": picks[-1], "chain": chain,
            "rids": list(range(len(uids))) if rids is None else list(rids),
            "rows": [int(i) for i in where] if isinstance(where, np.ndarray) else list(range(len(uids))),
            "starts": [int(p) for p in flight.start] if chain
            else [self.state.get(u).seen_tokens for u in uids],
            "counts": [int(e) for e in emitted] if chain else [len(t) for t in token_lists]})

    def _picks_by_request(self, n_requests: int) -> List[np.ndarray]:
        """The logged dispatches' picks, fetched and laid out a request:
        ``[tokens fed to it, routed layers, k]`` in the order fed. A position
        computed twice (a request preempted and prefilled again) keeps the
        later picks, which are the ones its output came from."""
        rows: List[Dict[int, np.ndarray]] = [{} for _ in range(n_requests)]
        for rec in self.picks_log:
            picks = np.asarray(rec["picks"])
            for rid, i, start, n in zip(rec["rids"], rec["rows"], rec["starts"], rec["counts"]):
                for t in range(n):
                    rows[rid][start + t] = picks[t, i] if rec["chain"] else picks[i, t]
        width = (self.model_config.routed_layers, self.model_config.moe_top_k)
        return [np.stack([r[p] for p in sorted(r)]).astype(np.int32) if r
                else np.zeros((0,) + width, np.int32) for r in rows]

    def _with_picks(self, call, n_requests: int):
        """``call()``'s result and, out of the dispatches it made, the picks a request."""
        if not self._routed:
            raise ValueError("picks asked of a model with no routed layer")
        self.picks_log = []
        try:
            out = call()
            return out, self._picks_by_request(n_requests)
        finally:
            self.picks_log = None

    def put_with_picks(self, uids: Sequence[int], token_lists: Sequence[np.ndarray]
                       ) -> Tuple[np.ndarray, List[np.ndarray]]:
        """``put``, and out of the same call and compiled program the experts
        it sent each token fed to: ``picks[i]`` int32 ``[len(token_lists[i]),
        routed layers, k]``, leading dense layers not counted, the experts'
        own numbers. Only a routed model has any."""
        return self._with_picks(lambda: self.put(uids, token_lists), len(uids))

    def generate_with_picks(self, prompts: Sequence[np.ndarray], max_new_tokens: int = 32,
                            **kwargs) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """``generate``, and out of its own fused prefill and decode chains
        the picks of every token it fed: ``picks[i]`` int32 ``[len(prompt i) +
        len(out i) - 1, routed layers, k]`` (the last token generated is fed
        to nothing). The serving loop runs as it always does; the picks stay
        on the device until it has returned."""
        return self._with_picks(
            lambda: self.generate(prompts, max_new_tokens=max_new_tokens, **kwargs), len(prompts))

    def _span_rids(self, rids: Optional[Sequence[int]]) -> str:
        """Request indices as one span arg (spans of one request share an
        identifier); formatted only while somebody records spans."""
        if rids is None or not self._tracer.recording():
            return ""
        return " ".join(map(str, rids))

    def _span_fed(self, uids, token_lists) -> str:
        """Each row's first position and the tokens it is fed, ``start:count``, as one span arg of a prefill
        under a learned indexer (what a query scores and keeps follows from its position); formatted only
        while somebody records spans, before ``seen_tokens`` advances."""
        if not (self.model_config.index_topk or self.plan.rings is not None) or not self._tracer.recording():
            return {}
        return {"fed": " ".join(f"{self.state.get(u).seen_tokens}:{len(t)}" for u, t in zip(uids, token_lists))}

    def _ring_args(self, uids, start=None, share=None) -> Dict[str, int]:
        """For a ``serve:dispatch`` span of a model with a sliding kind, while somebody records spans: the pages
        the call's rows hold by class, summed over their layers (``ring_pages``: the sliding layers' rings,
        ``global_pages``: the full layers' pages), and ``one_class_pages``, what one class of page would hold
        for the same rows (every layer a page a block of positions). The same numbers go to the gauges
        ``serving/kv_pages_held_ring`` and ``serving/kv_pages_held_global``, and the two classes' BYTES, each page
        at its own class's geometry, go beside them (``ring_bytes_held``, ``global_bytes_held``). A chain
        (``start``: its rows' first positions, ``share``: the steps its budgets plan for each) says ``ring_tokens``
        too: the sum over its rows and steps of the tokens a sliding layer's ring holds for the query,
        ``min(position + 1, window)``; ``global_tokens``, the same sum of what a global layer's table holds,
        ``position + 1``; ``row_steps``, the (row, step) pairs both sum over; and ``ring_turns``, the ring pages a
        sliding layer of its rows starts writing over (``RingLayout.overwritten``: the blocks the chain opens
        past a ring's first round)."""
        if self.plan.rings is None or not self._tracer.recording():
            return {}
        cfg, rings = self.model_config, self.plan.rings
        seqs = [self.state.get(u) for u in uids]
        blocks = sum(s.n_summary for s in seqs)  # a page a block of positions: what a full layer holds
        ring, held = sum(s.n_window for s in seqs) * cfg.sliding_layers, blocks * cfg.attention_layers
        if self._tracer.enabled:
            self._tracer.registry.gauge("serving/kv_pages_held_ring").set(float(ring))
            self._tracer.registry.gauge("serving/kv_pages_held_global").set(float(held))
        page = [c.page_bytes(self.config.kv_block_size, jnp.dtype(self.config.kv_jax_dtype).itemsize)
                for c in self.plan.classes]
        args = {"ring_pages": ring, "global_pages": held, "one_class_pages": blocks * cfg.num_layers,
                "ring_bytes_held": ring * page[1], "global_bytes_held": held * page[0]}
        if start is not None:
            fed = np.arange(int(np.max(share, initial=0)))[None, :] < np.asarray(share)[:, None]
            seen = np.asarray(start)[:, None] + np.arange(fed.shape[1])[None, :] + 1
            args["ring_tokens"] = int(np.minimum(seen, rings.window)[fed].sum())
            args["global_tokens"], args["row_steps"] = int(seen[fed].sum()), int(fed.sum())
            args["ring_turns"] = sum(rings.overwritten(int(at), int(n)) for at, n in zip(start, share))
        return args

    def _flash_args(self, new_lens, chunk: int) -> Dict[str, int]:
        """For the ``serve:dispatch`` span of a call of fresh prompts of a model with a sliding kind, while
        somebody records spans and the chunks' attention is the flash forward: ``flash_cells_live``, the cells
        of its grids a head that hold a live query (the ones it runs since it is told the rows' lengths,
        ``ops/pallas/flash_attention.py::_flash_fwd``), and ``flash_cells_grid``, the grids' own, both summed
        over the call's rows and the pattern's layers by the kernels' own maps."""
        from deepspeed_tpu.ops.attention import resolves_to_flash
        from deepspeed_tpu.ops.pallas.flash_attention import forward_cells

        cfg = self.model_config
        rings = self.plan.rings
        if rings is None or chunk == 1 or not self._tracer.recording() or not resolves_to_flash(cfg.attn_impl):
            return {}
        by_kind = ((cfg.attention_layers, forward_cells(new_lens, chunk)),
                   (cfg.sliding_layers, forward_cells(new_lens, chunk, rings.window)))
        live, grid = (sum(layers * cells[i] for layers, cells in by_kind) for i in (0, 1))
        return {"flash_cells_live": live, "flash_cells_grid": grid}

    def _put_sample(self, uids, token_lists, rng, sample_kw: Tuple,
                    tracker: Optional[LifecycleTracker] = None,
                    rids: Optional[Sequence[int]] = None) -> Tuple[np.ndarray, jax.Array]:
        """Fused put+sample: push tokens, return (sampled next-token ids
        [len(uids)] host numpy, new rng). One dispatch, one host sync, no
        logits transfer."""
        batch = self._build_batch(uids, token_lists)
        step = self._sample_step_fn(batch.n_rows, batch.tokens.shape[1], sample_kw)
        self.last_prefill_kept = None
        call = self._log.open("prefill", -1, batch.n_rows, batch.tokens.shape[1])
        with self._dispatching(call, rows=batch.n_rows,
                               live=len(uids), tokens=int(batch.new_lens.sum()),
                               rids=self._span_rids(rids), **self._span_fed(uids, token_lists),
                               **self._eva_args(batch.positions, batch.new_lens),
                               **self._state_args(len(uids)), **self._ring_args(uids),
                               **self._flash_args(batch.new_lens, batch.tokens.shape[1])):
            if tracker is not None and rids is not None:
                tracker.mark_dispatch(rids, "prefill")
            toks, rng, self.pools, *picks = step(
                self.params, self.pools,
                jnp.asarray(batch.tokens), jnp.asarray(batch.positions),
                jnp.asarray(batch.new_lens), jnp.asarray(batch.block_tables),
                rng,
            )
        self.dispatch_count += 1
        self._log_picks(picks, uids, rids, token_lists, at=batch.at)
        self.windows_closed += self._advance(uids, map(len, token_lists))
        with self._fetching(call):
            out = self._rows_at(toks, batch.at)
            if len(picks) > 1 and batch.tokens.shape[1] > 1:
                # an indexed model's chunk: [rows, layers, 2], what each row's queries scored and kept (dead rows: 0)
                queries = int(batch.new_lens.sum())
                scored, kept = np.asarray(picks[0], np.float64).sum(axis=(0, 1)) / (
                    max(queries, 1) * self.model_config.num_layers)
                self.last_prefill_kept = (queries, float(scored), float(kept))
        self.host_sync_count += 1
        return out, rng

    # ---------------------------------------------------------------- chain
    def _chain_arrays(self, rows: int) -> Dict[str, np.ndarray]:
        buf = self._chain_buf.get(rows)
        if buf is None:
            buf = self._chain_buf[rows] = {
                "tokens": np.zeros((rows,), np.int32),
                "pos": np.zeros((rows,), np.int32),
                "tables": np.zeros((rows, self.max_pages), np.int32),
                "active": np.zeros((rows,), bool),
                "budgets": np.zeros((rows,), np.int32),
            }
        for name in ("tables", "active", "budgets"):
            buf[name][:] = 0
        return buf

    def _place(self, buf: Dict[str, np.ndarray], *names: str) -> Tuple[jax.Array, ...]:
        """COPIES of a chain's staging arrays, placed where the step programs'
        outputs land (jit keys its cache on placement, and a chain ahead takes
        such outputs for the same operands). Copies, because the staging is
        refilled for the next chain while this one may be unfetched, and a
        placed array can be the host's own memory (the CPU backend's)."""
        return jax.device_put(tuple(buf[name].copy() for name in names), self._replicated)

    def _dispatch_chain(self, uids, at, n_rows, budgets, k, rng, eos_id, sample_kw, chain_id,
                        last_tokens=None, before: Optional[_ChainInFlight] = None,
                        tracker=None, rids=None) -> _ChainInFlight:
        """Assemble and dispatch one chain over ``uids`` in the program's rows
        ``at``, each to emit at most ``k`` and its entry of ``budgets``. It
        starts from the host's ``last_tokens`` and ``seen_tokens``, or, ahead,
        from the carry of ``before``, the unfetched chain over the same rows.
        The program is given each row's WHOLE budget, not its share of this
        chain: its outputs are the same, and the ``active`` it hands back then
        means "goes on after this chain", which is what the next one needs."""
        ahead = before is not None
        with self._host_span("serve:assemble", kind="chain", rows=n_rows, chain=chain_id):
            # pre-extend every row's block table for its share of the K-token
            # window (capped by the row's remaining budget — no KV slots are
            # reserved past max_new_tokens) so the compiled program never
            # needs the allocator mid-chain
            buf = self._chain_arrays(n_rows)
            share = np.minimum(budgets, k)
            for i, uid, b in zip(at, uids, share):
                seq = self.state.extend(uid, int(b))
                seq.table_into(buf["tables"][i])
                buf["pos"][i] = seq.seen_tokens
            buf["budgets"][at] = budgets
            start = buf["pos"][at]
            if not ahead:
                buf["tokens"][at] = last_tokens
                buf["active"][at] = True
            eva_args = {} if self.plan.windows is None else self._eva_args(
                start[:, None] + np.arange(k)[None, :], share)
        chain = self._chain_fn(n_rows, k, eos_id, sample_kw)
        call = self._log.open("chain", chain_id, n_rows, k)
        with self._dispatching(call, rows=n_rows, live=len(uids),
                               k=k, chain=chain_id, ahead=int(ahead), **eva_args,
                               **self._state_args(share.sum()), **self._ring_args(uids, start, share)):
            if ahead:
                tokens, pos, active = before.carry
                tables, chain_budgets = self._place(buf, "tables", "budgets")
            else:
                if tracker is not None and rids is not None:
                    tracker.mark_dispatch(rids, "chain", now=call.dispatched_at)
                tokens, pos, tables, active, chain_budgets = self._place(
                    buf, "tokens", "pos", "tables", "active", "budgets")
            out, emitted, active, tok, pos, rng_out, self.pools, *routed = chain(
                self.params, self.pools, tokens, pos, tables, active, chain_budgets, rng)
        self.dispatch_count += 1
        if ahead:
            self.chains_ahead += 1
            self._tracer.count("serving/chains_ahead", 1.0)
        return _ChainInFlight(
            chain_id=chain_id, n_rows=n_rows, uids=list(uids), at=np.asarray(at), start=start,
            budgets=np.asarray(budgets), k=k, eos_id=eos_id, sample_kw=sample_kw, rng_in=rng,
            call=call, out=out, emitted=emitted, carry=(tok, pos, active),
            rng_out=rng_out, routed=tuple(routed), moved=np.zeros(len(uids), bool))

    def _chain_ahead(self, flight: _ChainInFlight, budgets: np.ndarray) -> Optional[_ChainInFlight]:
        """The chain after ``flight``, dispatched from ``flight``'s carry
        before ``flight``'s tokens are fetched; None where the boundary has to
        be serial. ``budgets`` are the caller's for ``flight``'s rows, whole."""
        k = flight.k
        keep = budgets > k  # the rows that outlast this chain
        n = int(keep.sum())
        if n == 0 or self.state.rows_of(np.asarray(flight.uids)[keep].tolist(),
                                        self.config.row_bucket)[1] != flight.n_rows:
            return None  # nothing to decode, or a smaller program would
        # Such a row emits exactly k tokens in ``flight``, or ends in it at an
        # EOS and rides the next chain dead: where it stands afterwards, its
        # budget and its pages (EVA: the windows it closes on the way) are
        # known now. The fetch sets an ended row back to where its tokens end.
        uids = [u for u, kept in zip(flight.uids, keep) if kept]
        self._advance(uids, [k] * n)
        flight.moved = keep
        left = budgets[keep] - k
        if not self._can_schedule_evicting(uids, self.chain_window(left, k)):
            return None  # the loop shrinks the chain or preempts, after the fetch
        return self._dispatch_chain(
            uids, flight.at[keep], flight.n_rows, left, k, flight.rng_out,
            flight.eos_id, flight.sample_kw, flight.chain_id + 1, before=flight)

    def _take_ahead(self, flight: _ChainInFlight, uids, last_tokens, budgets, k, rng, eos_id,
                    sample_kw) -> None:
        """The chain dispatched ahead has run (or is running) and has written
        the pool: this call has to be the one it was built for."""
        asked = {"uids": list(uids), "k": k, "eos_id": eos_id, "sample_kw": sample_kw,
                 "budgets": budgets.tolist(),
                 "last_tokens": np.asarray(last_tokens, np.int64).tolist(), "rng": id(rng)}
        built = {"uids": flight.uids, "k": flight.k, "eos_id": flight.eos_id,
                 "sample_kw": flight.sample_kw, "budgets": flight.budgets.tolist(),
                 "last_tokens": flight.last.tolist(), "rng": id(flight.rng_in)}
        wrong = [name for name in asked if asked[name] != built[name]]
        if wrong:
            raise RuntimeError(
                "decode_chain: a chain was dispatched ahead for the next call (ahead=True), and "
                "this call is not that chain: " + "; ".join(
                    f"{name} {asked[name]!r}, dispatched with {built[name]!r}" for name in wrong)
                + ". Pass the uids still live in the order given, their budgets less the chain's "
                "share, and the rng the last call returned.")

    def decode_chain(
        self,
        uids: Sequence[int],
        last_tokens: Sequence[int],
        budgets: Sequence[int],
        k: int,
        rng: jax.Array,
        eos_id: Optional[int] = None,
        sample_kw: Tuple = (("do_sample", False),),
        tracker: Optional[LifecycleTracker] = None,
        rids: Optional[Sequence[int]] = None,
        ahead: bool = False,
    ) -> Tuple[np.ndarray, np.ndarray, jax.Array]:
        """Run one K-step chained decode over ``uids``.

        Caller must have verified ``can_schedule(uids, [k]*len(uids))``.
        Returns ``(tokens [n, k], emitted [n], rng)`` where
        ``tokens[i, :emitted[i]]`` are the new tokens of ``uids[i]`` (the
        EOS token, when hit, is included and the row stops). seen_tokens
        advances by ``emitted[i]`` — exactly the KV slots written.

        ``ahead=True`` says that the caller's next call will be the next chain
        of the same rows: the uids this chain leaves live, in this order, their
        budgets less ``k``, the same ``k``, ``eos_id`` and ``sample_kw``, the
        tokens this call returns last and the rng it returns, and no ``put`` or
        ``flush`` of a live row in between. The engine may then dispatch that
        chain before it fetches this one's tokens, from this chain's own carry
        on the device, so the chip goes from one chain to the next without
        waiting for the host (a chain ahead). It does so where a row's budget
        outlasts this chain, the survivors fill the same row bucket and the
        pool covers their next window as it stands; else the boundary is
        serial, as without the argument. The next call is given that chain:
        it checks that it is the one described and raises if not, since the
        chain has written the pool by then. A row that ends at an EOS in this
        chain is dead in the next (the carry knows) and must not be passed
        again: flush it. If every row ends so, the next call passes no uid and
        fetches a chain that did nothing.
        """
        uids = list(uids)
        budgets = np.asarray(budgets, np.int32).reshape(-1)
        flight = self._ahead
        if flight is not None:  # (a call it refuses leaves it where it is)
            self._take_ahead(flight, uids, last_tokens, budgets, k, rng, eos_id, sample_kw)
            self._ahead = None
            if tracker is not None and rids is not None:
                tracker.mark_dispatch(rids, "chain", now=flight.call.dispatched_at)
        else:
            at, rows = self.state.rows_of(uids, self.config.row_bucket)
            flight = self._dispatch_chain(
                uids, at, rows, budgets, k, rng, eos_id, sample_kw,
                self.chain_steps,  # shared by every span of this chain
                last_tokens=last_tokens, tracker=tracker, rids=rids)
        if ahead:
            self._ahead = self._chain_ahead(flight, budgets)
        routed = flight.routed
        with self._fetching(flight.call, chain=flight.chain_id):
            # the padded outputs themselves, cut down in numpy: a slice on the
            # device is a program, and would wait behind the chain ahead
            out = np.asarray(flight.out)[flight.at]
            emitted = np.asarray(flight.emitted)[flight.at]
            if routed:
                # [K, routed layers, 3] beside the tokens: at the steps some row was live at, the distinct
                # experts a layer the live rows picked, the visits those got, and the distinct experts the
                # decode product read (every row's picks, dead or alive), each on average
                live_steps = max(int(emitted.max(initial=0)), 1)
                touched, visits, read = np.asarray(routed[0])[:live_steps].reshape(-1, 3).mean(axis=0)
                self.last_experts_touched, self.last_experts_read = float(touched), float(read)
                if self.model_config.expert_parallel is not None:
                    self.last_held_visits = float(visits)
                if len(routed) > 2:  # [K, 2] between the two: tokens scored and kept a step, over live rows and layers
                    row_layers = max(int(emitted.sum()), 1) * self.model_config.num_layers
                    scored, kept = np.asarray(routed[1])[:live_steps].sum(axis=0) / row_layers
                    self.last_tokens_scored, self.last_tokens_kept = float(scored), float(kept)
        self.host_sync_count += 1
        self._log_picks(routed, uids, rids, flight=flight, emitted=emitted)
        # a row moved already stands k further; one an EOS ended goes back
        moved = flight.moved
        for uid, short in zip(np.asarray(uids)[moved], (k - emitted)[moved]):
            self.state.get(int(uid)).seen_tokens -= int(short)
        self._advance([u for u, m in zip(uids, moved) if not m], emitted[~moved])
        if self.plan.windows is not None:
            window = self.plan.windows.window
            self.windows_closed += int(((flight.start + emitted) // window - flight.start // window).sum())
        if self._ahead is not None:
            # the rows of the chain ahead that hold a request are those this
            # chain left live; where each starts from is known only now
            last = out[np.arange(len(uids)), np.maximum(emitted, 1) - 1]
            live = (emitted == k) if eos_id is None else (emitted == k) & (last != eos_id)
            nxt, live = self._ahead, live[moved]
            nxt.uids = [u for u, alive in zip(nxt.uids, live) if alive]
            nxt.at, nxt.start, nxt.budgets = nxt.at[live], nxt.start[live], nxt.budgets[live]
            nxt.last, nxt.moved = last[moved][live], nxt.moved[live]
        return out, emitted, flight.rng_out

    def decode_spec_chain(
        self,
        uids: Sequence[int],
        last_tokens: Sequence[int],
        budgets: Sequence[int],
        k: int,
        rng: jax.Array,
        histories: Sequence[np.ndarray],
        eos_id: Optional[int] = None,
        tracker: Optional[LifecycleTracker] = None,
        rids: Optional[Sequence[int]] = None,
    ) -> Tuple[np.ndarray, np.ndarray, jax.Array]:
        """One speculative chain over ``uids``: ``k`` verify forwards, each
        proposing ``spec_decode`` n-gram drafts — up to ``k * (1+n_spec)``
        accepted tokens from ONE dispatch and ONE host sync. ``histories``
        are the rows' full token contexts (prompt + generated, INCLUDING the
        ``last_tokens`` entry) feeding the on-device proposer. Greedy only.

        Block tables are pre-extended for the emission window plus
        ``n_spec`` transient slots (rejected-draft KV writes land past the
        last accepted token and are overwritten by later steps).
        """
        n = len(uids)
        n_spec = self.config.spec_decode
        m = 1 + n_spec
        rows = -(-n // self.config.row_bucket) * self.config.row_bucket
        chain_id = self.chain_steps
        with self._host_span("serve:assemble", kind="spec_chain", rows=rows,
                             chain=chain_id):
            buf = self._chain_arrays(rows)
            sb = self._spec_buf.get(rows)
            if sb is None:
                sb = {"hist": np.zeros((rows, self.max_seq_len), np.int32),
                      "hist_len": np.zeros((rows,), np.int32)}
                self._spec_buf[rows] = sb
            else:
                sb["hist"][:] = 0
                sb["hist_len"][:] = 0
            for i, uid in enumerate(uids):
                window = min(k * m, int(budgets[i]))
                seq = self.state.extend(uid, window + n_spec)
                seq.table_into(buf["tables"][i])
                buf["pos"][i] = seq.seen_tokens
                h = histories[i]
                sb["hist"][i, : len(h)] = h
                sb["hist_len"][i] = len(h)
            buf["tokens"][:n] = last_tokens
            buf["active"][:n] = True
            buf["budgets"][:n] = np.minimum(budgets, k * m)
        chain = self._spec_chain_fn(rows, k, eos_id)
        call = self._log.open("spec_chain", chain_id, rows, k)
        with self._dispatching(call, rows=rows,
                               live=n, k=k, n_spec=n_spec, chain=chain_id):
            if tracker is not None and rids is not None:
                tracker.mark_dispatch(rids, "chain")
            out, emitted, _, steps, rng, self.pools = chain(
                self.params, self.pools,
                jnp.asarray(buf["tokens"]), jnp.asarray(buf["pos"]),
                jnp.asarray(buf["tables"]), jnp.asarray(buf["active"]),
                jnp.asarray(buf["budgets"]), rng,
                jnp.asarray(sb["hist"]), jnp.asarray(sb["hist_len"]),
            )
        self.dispatch_count += 1
        with self._fetching(call, chain=chain_id):
            out = np.asarray(out[:n])
            emitted = np.asarray(emitted[:n])
            steps = np.asarray(steps[:n])
        self.host_sync_count += 1
        for uid, e in zip(uids, emitted):
            self.state.get(uid).seen_tokens += int(e)
        self.spec_model_steps += int(steps.sum())
        self.spec_tokens_emitted += int(emitted.sum())
        return out, emitted, rng

    # ----------------------------------------------------------- numerics plane
    def _numerics_probe_chain(self, n_spec: int) -> None:
        """Serving-fidelity probes (telemetry/numerics.py plane 3), sampled
        at decode-chain boundaries: KV dequant round-trip error for the
        quantized pool formats, WOQ matmul error for the quantized weight
        format, and the spec-decode acceptance-rate trend alarm (PR-2
        median+MAD, low side). Standalone dispatches — the compiled decode
        programs are untouched; a single attribute check when disabled."""
        from deepspeed_tpu.telemetry import numerics as numerics_mod

        nm = numerics_mod.get_observatory()
        if not nm.enabled:
            return
        if n_spec > 0 and self.spec_model_steps:
            nm.note_spec_accept(
                (self.spec_tokens_emitted - self.spec_model_steps)
                / (self.spec_model_steps * n_spec))
        every = max(1, int(nm.config.sample_every))
        if self.chain_steps % every != 0:
            return
        kvq = self.config.kv_quant
        if kvq is not None:
            nm.kv_dequant_probe(kvq,
                                head_dim=self.model_config.dims_per_head)
        if self.config.quant.enabled:
            from deepspeed_tpu.inference.woq import woq_format

            nm.woq_matmul_probe(woq_format(self.config.quant))

    # ---------------------------------------------------------------- serving loop
    def generate(
        self,
        prompts: Sequence[np.ndarray],
        max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
        arrival_times: Optional[Sequence[float]] = None,
    ) -> List[np.ndarray]:
        """Convenience continuous-batching loop (the MII serving-layer analog).

        Admission and preemption happen at chain boundaries: each round
        admits pending prompts as one fused prefill+sample step, then decodes
        every active sequence with one K-step chained program (T3 discipline,
        arxiv 2401.16677 — the host prepares the next round while the device
        runs the current chain). When the pool cannot fit the next chain
        window, the chain first shrinks, then the youngest active sequence is
        preempted (flushed and re-queued with its full context, reference
        FastGen scheduler behavior) rather than crashing mid-generation.

        A chain ahead: where the next boundary has nothing to decide, the loop
        lets ``decode_chain`` dispatch chain N+1 from chain N's own carry on
        the device (each row's next token, position and whether it is still
        live) BEFORE it fetches chain N's tokens, so the chip runs on while
        the host fetches, accepts and plans. That is exact, not speculative: a
        row that survives a chain emitted ``min(k, budget)`` tokens in it, so
        its position, budget and pages afterwards are known beforehand, and
        what is not (the tokens, an EOS) the carry holds. A boundary is serial,
        as it always was, when the chain is speculative or was shrunk, when no
        row goes on, when a prompt waits that the boundary could admit (a free
        seat, or a budget that ends in this chain), when the rows left would
        fit a smaller program, and when the pool does not cover the next
        window as it stands (then the loop shrinks or preempts after the
        fetch). While a chain is in flight ahead the loop admits and preempts
        nothing, so a seat that an EOS frees while prompts wait is filled one
        chain later than a serial loop fills it. Greedy tokens are the serial
        loop's, always. Sampled tokens are too while the rows of a wave stay
        the same; once a row has ended, the chain ahead keeps its (dead) row
        where a serial loop closes the rows up, and after a late admission the
        key has gone through one chain more: the draws after that are other
        draws from the same distributions. ``chains_ahead`` of
        ``chain_steps`` (counters ``serving/chains_ahead`` of
        ``serving/chains``, ``ahead=1`` on the ``serve:dispatch`` span) says
        how many boundaries went so.

        ``arrival_times`` (seconds relative to the call, one per prompt)
        turns the batch call into an open-loop workload: a prompt enters the
        admission queue only once its arrival time has passed — this is what
        ``benchmarks/runners/serve.py`` drives to measure TTFT/queue-wait
        under a cell's arrival pattern. None (default) queues everything
        immediately, exactly the previous behavior.

        When the telemetry tracer is enabled (or ``flight_recorder`` is
        configured) every request is lifecycle-tracked (arrival -> admission
        -> first token -> chain boundaries -> finish): ``serving/*`` SLO
        metrics land in the shared registry and each finished request emits
        its own Perfetto track with flow arrows into the dispatch spans that
        served it (``inference/lifecycle.py``). Disabled, no per-request
        records are allocated and the loop is unchanged.
        """
        with self._tracer.span("serve:generate", requests=len(prompts),
                               max_new_tokens=max_new_tokens):
            try:
                return self._generate_loop(
                    prompts, max_new_tokens, eos_token_id, do_sample, temperature,
                    top_k, top_p, seed, arrival_times)
            finally:
                self._ahead = None  # set here only if the loop raised: nobody will take it

    def _generate_loop(self, prompts, max_new_tokens, eos_token_id, do_sample,
                       temperature, top_k, top_p, seed, arrival_times) -> List[np.ndarray]:
        # the call's own set-up: validation, the queue, the key, lifecycle records
        with self._tracer.span("serve:setup", requests=len(prompts)):
            prompts = [np.asarray(p, np.int32) for p in prompts]
            pool_tokens = self.num_kv_blocks * self.config.kv_block_size
            n_spec = self.config.spec_decode
            if n_spec > 0 and do_sample:
                raise ValueError(
                    "spec_decode is greedy-only (verify-and-accept compares "
                    "argmax targets); disable do_sample or set spec_decode=0")
            # spec chains write up to n_spec transient (rejected-draft) KV slots
            # past the last emitted token — the length guards carry that margin
            margin = n_spec
            for i, p in enumerate(prompts):
                if len(p) + max_new_tokens + margin > self.max_seq_len:
                    raise ValueError(
                        f"prompt {i} ({len(p)} tokens) + max_new_tokens={max_new_tokens} "
                        f"(+{margin} speculative slack) exceeds engine "
                        f"max_seq_len={self.max_seq_len}"
                    )
                lay = self.plan.windows
                if lay is not None:
                    # summaries of the windows it closes + one window's rows, at the most
                    total = len(p) + max_new_tokens
                    held = (total // lay.window * lay.per_closed
                            + min(lay.window_pages, -(-total // self.config.kv_block_size)))
                    fits = held <= self.num_kv_blocks
                else:
                    fits = len(p) + max_new_tokens + margin <= pool_tokens
                if not fits:
                    raise ValueError(
                        f"prompt {i} ({len(p)} tokens) + max_new_tokens={max_new_tokens} "
                        f"cannot ever fit the KV pool ({pool_tokens} slots); no amount of "
                        f"preemption can complete it"
                    )
            sample_kw = (("do_sample", do_sample), ("temperature", temperature),
                         ("top_k", top_k), ("top_p", top_p))
            t_start = self._clock()
            arr: Optional[List[float]] = None
            if arrival_times is not None:
                if len(arrival_times) != len(prompts):
                    raise ValueError(
                        f"arrival_times has {len(arrival_times)} entries for "
                        f"{len(prompts)} prompts")
                arr = [float(a) for a in arrival_times]
                queue: deque = deque(sorted(range(len(prompts)), key=lambda i: arr[i]))
            else:
                queue = deque(range(len(prompts)))  # idx, FIFO
            gen: Dict[int, List[int]] = {i: [] for i in range(len(prompts))}
            active: Dict[int, int] = {}  # uid -> idx
            order: Dict[int, None] = {}  # admission order (insertion-ordered set)
            outputs: Dict[int, np.ndarray] = {}
            # committed key, replicated like every step output: a fresh PRNGKey
            # is uncommitted, but the key a chain returns carries
            # NamedSharding(mesh, P()) — jit caches on that difference, so an
            # uncommitted first key makes the SECOND admission wave recompile
            # the prefill program mid-serving (a ~0.4s TTFT cliff under bursts)
            rng = jax.device_put(jax.random.PRNGKey(seed), self._replicated)
            next_uid = 0
            registry = self._tracer.registry if self._tracer.enabled else None

            # ---- per-request lifecycle tracking (None = nothing allocated)
            tracker: Optional[LifecycleTracker] = None
            if self._tracer.enabled or self._recorder is not None:
                tracker = LifecycleTracker(
                    self._tracer, slo=self.config.serving_slo,
                    labels={"k": self.config.decode_chain},
                    recorder=self._recorder)
                for i in range(len(prompts)):
                    tracker.arrive(i, now=t_start + (arr[i] if arr is not None else 0.0))
            self.lifecycle = tracker
            if registry is not None:
                # the cheap scheduler/pool gauges, refreshed at chain boundaries
                # (handles resolved once — the loop pays plain attribute sets)
                g_queue = registry.gauge("serving/queue_depth")
                g_occ = registry.gauge("serving/batch_occupancy")
                g_free = registry.gauge("serving/kv_pool_free_blocks")
                kv_name = self.config.kv_dtype_name
                g_util = registry.gauge("serving/kv_pool_utilization", dtype=kv_name)
                # quantized-serving capacity facts (set once — they are config,
                # not chain-boundary state): which storage the pool runs and what
                # one token slot costs, the number capacity plans divide HBM by
                registry.gauge("serving/kv_pool_dtype", dtype=kv_name).set(1.0)
                registry.gauge("serving/kv_bytes_per_token").set(
                    float(self.kv_bytes_per_token))
                c_preempt = registry.counter("serving/preemptions")
                c_tokens = registry.counter("serving/tokens_decoded")
                c_chains = registry.counter("serving/chains")
                h_chain_len = registry.histogram("serving/chain_len")
                g_pfx_hit = g_pfx_blocks = g_spec_acc = g_spec_tpf = None
                if self.prefix_cache is not None:
                    g_pfx_hit = registry.gauge("serving/prefix_hit_rate")
                    g_pfx_blocks = registry.gauge("serving/prefix_cached_blocks")
                if self.config.spec_decode > 0:
                    g_spec_acc = registry.gauge("serving/spec_accept_rate")
                    g_spec_tpf = registry.gauge("serving/spec_tokens_per_forward")

        def context(idx: int) -> np.ndarray:
            return np.concatenate([prompts[idx], np.asarray(gen[idx], np.int32)])

        def accept(u: int, t: int) -> None:
            """Record token t for uid u; retire the row if done."""
            idx = active[u]
            gen[idx].append(int(t))
            if len(gen[idx]) >= max_new_tokens or (
                eos_token_id is not None and int(t) == eos_token_id
            ):
                outputs[idx] = np.asarray(gen[idx], np.int32)
                active.pop(u)
                order.pop(u)
                self.flush(u)
                if tracker is not None:
                    tracker.finish(idx)

        pc = self.prefix_cache
        span = self._host_span
        token_budget = self.config.max_ragged_batch_size
        while queue or active or self._ahead is not None:
            # ---- admit pending prompts (fused prefill + first-token sample): one
            # call, or under a token budget (max_ragged_batch_size) as many calls,
            # one after another, as the queue and the pool allow, each within it.
            # Not while a chain is in flight ahead: its rows are settled, and a
            # seat that an EOS freed is seen one chain later.
            admitted = False
            not_due = False  # the queue's head had not arrived when the admission pass looked
            while self._ahead is None:
                adm_uids: List[int] = []
                adm_tokens: List[np.ndarray] = []
                adm_counts: List[int] = []
                adm_full: List[np.ndarray] = []  # full contexts, for cache insert
                with span("serve:admit", queue_len=len(queue)) as admit_span:
                    decoding = list(active.keys())  # reserve 1-token decode headroom
                    longest = 0  # of this call's prompts, for the token budget
                    call_full = False
                    while queue and len(active) < self.config.max_seqs:
                        idx = queue[0]
                        if arr is not None and self._clock() - t_start < arr[idx]:
                            not_due = True
                            break  # open-loop workload: not arrived yet
                        cand = context(idx)
                        if token_budget is not None and adm_uids:
                            longest = max(longest, len(cand))
                            rows = -(-(len(adm_uids) + 1) // self.config.row_bucket) * self.config.row_bucket
                            chunk = -(-longest // self.config.chunk_bucket) * self.config.chunk_bucket
                            if rows * chunk > token_budget:
                                call_full = True  # the rest go into the next call
                                break
                        longest = max(longest, len(cand))
                        suffix = self.try_admit(
                            next_uid, cand, decoding + adm_uids,
                            [1] * len(decoding) + adm_counts)
                        if suffix is None:
                            break
                        queue.popleft()
                        adm_uids.append(next_uid)
                        adm_tokens.append(suffix)
                        adm_counts.append(len(suffix))
                        adm_full.append(cand)
                        if tracker is not None:
                            tracker.admit(idx, next_uid)
                        active[next_uid] = idx
                        order[next_uid] = None
                        next_uid += 1
                    adm_rids = [active[u] for u in adm_uids]
                    admit_span.set_metadata(requests=len(adm_uids), tokens=sum(adm_counts),
                                            rids=self._span_rids(adm_rids))
                if adm_uids:
                    admitted = True
                    toks, rng = self._put_sample(adm_uids, adm_tokens, rng, sample_kw,
                                                 tracker=tracker, rids=adm_rids)
                    kept_args = {}
                    if self.last_prefill_kept is not None:
                        queries, scored, kept = self.last_prefill_kept
                        kept_args = dict(queries=queries, tokens_scored=scored, tokens_kept=kept)
                    with span("serve:accept", kind="prefill", emitted=len(adm_uids), **kept_args):
                        if pc is not None:
                            # index the freshly written full blocks (quantized bytes
                            # are in the pool now — hashes snapshot them as written)
                            for u, full in zip(adm_uids, adm_full):
                                self._insert_prefix(u, full)
                        if tracker is not None:
                            tracker.emitted_batch(adm_rids, (1,) * len(adm_rids))
                        for u, t in zip(adm_uids, toks):
                            accept(u, t)
                if not (call_full and adm_uids):
                    break
            if not active and self._ahead is None:
                if queue and not admitted:
                    if not_due:
                        wait = t_start + arr[queue[0]] - self._clock()
                        if wait > 0:  # idle until the next synthetic arrival
                            with self._tracer.span("serve:idle_wait", queue_len=len(queue)):
                                time.sleep(min(wait, 0.05))
                        continue  # (it may have fallen due since the pass looked: go round again)
                    raise RuntimeError(
                        f"KV pool too small for a single sequence "
                        f"({self.num_kv_blocks} blocks x {self.config.kv_block_size})"
                    )
                continue

            # ---- one chained decode over the active set. K stays pinned at
            # decode_chain so one compiled program serves every chain (per-row
            # budget masks inside the scan handle the max_new_tokens tail);
            # only KV-pool pressure shrinks the window, then preempts. With
            # speculative decoding each of the K forwards may emit up to
            # 1+n_spec tokens, so the KV window scales by that factor plus
            # the n_spec transient-write slack.
            chain_id = self.chain_steps  # every span of this chain carries it
            with span("serve:schedule", chain=chain_id) as schedule_span:
                uids = list(active.keys())
                budgets = [max_new_tokens - len(gen[active[u]]) for u in uids]
                k = self.config.decode_chain
                preempted = 0
                # (a chain in flight ahead was assembled when it was dispatched:
                # nothing to shrink, nobody to preempt)
                while self._ahead is None:
                    while k > 1 and not self._can_schedule_evicting(
                            uids, self.chain_window(budgets, k)):
                        k -= 1
                    if self._can_schedule_evicting(uids, self.chain_window(budgets, k)):
                        break
                    victim = next(reversed(order))
                    del order[victim]
                    i = uids.index(victim)
                    uids.pop(i)
                    budgets.pop(i)
                    idx = active.pop(victim)
                    self.flush(victim)
                    queue.appendleft(idx)
                    preempted += 1
                    if tracker is not None:
                        tracker.preempt(idx)
                    if registry is not None:
                        c_preempt.add(1.0)
                    if not uids:
                        raise RuntimeError(
                            f"KV pool too small for a single sequence "
                            f"({self.num_kv_blocks} blocks x {self.config.kv_block_size})"
                        )
                    k = self.config.decode_chain
                last = [gen[active[u]][-1] for u in uids]
                chain_rids = [active[u] for u in uids]
                histories = [context(active[u]) for u in uids] if n_spec > 0 else None
                # Ask for the next chain ahead of this one's fetch where the next
                # boundary, as far as the host can foresee it, has nothing to do
                # but dispatch it: a plain chain of the full length (a speculative
                # one emits what it accepts), some row goes on after it, and no
                # prompt can be admitted there (none waits, or every seat is taken
                # and no row's budget ends in this chain). The engine adds what it
                # knows of the pool (``decode_chain``).
                ask = (n_spec == 0 and k == self.config.decode_chain and bool(uids)
                       and max(budgets) > k
                       and (not queue or (len(active) >= self.config.max_seqs and min(budgets) > k)))
                schedule_span.set_metadata(active=len(uids), k=k, preempted=preempted)
            if n_spec > 0:
                out, emitted, rng = self.decode_spec_chain(
                    uids, last, budgets, k, rng, histories,
                    eos_id=eos_token_id, tracker=tracker, rids=chain_rids)
            else:
                out, emitted, rng = self.decode_chain(
                    uids, last, budgets, k, rng, eos_id=eos_token_id,
                    sample_kw=sample_kw, tracker=tracker, rids=chain_rids, ahead=ask)
            n_emitted = int(emitted.sum())
            routed_args = ({"experts_touched": self.last_experts_touched, "experts_read": self.last_experts_read}
                           if self._routed and n_spec == 0 else {})
            if routed_args and self.last_held_visits is not None:
                routed_args["held_visits"] = self.last_held_visits
            if routed_args and self.last_tokens_scored is not None:
                routed_args.update(tokens_scored=self.last_tokens_scored, tokens_kept=self.last_tokens_kept)
            with span("serve:accept", kind="chain", emitted=n_emitted, chain=chain_id,
                      **routed_args):
                self.tokens_decoded += n_emitted
                # serving liveness for /healthz + fleet heartbeats: a decode
                # chain is this engine's "step" (two plain writes)
                self.chain_steps += 1
                _fleet_note_step(self.chain_steps)
                if tracker is not None:
                    # ONE stamp per chain boundary; TPOT = boundary delta / tokens
                    now = time.perf_counter()
                    tracker.emitted_batch(chain_rids, emitted, now=now)
                    tracker.sample_gauges(now=now)
                if registry is not None:
                    c_tokens.add(n_emitted)
                    c_chains.add(1.0)
                    h_chain_len.observe(float(k))
                    g_queue.set(float(len(queue)))
                    g_occ.set(len(active) / self.config.max_seqs)
                    g_free.set(float(self.state.free_blocks))
                    g_util.set(self.state.utilization)
                    if g_pfx_hit is not None:
                        g_pfx_hit.set(pc.hit_rate)
                        g_pfx_blocks.set(float(len(pc)))
                    if g_spec_acc is not None and self.spec_model_steps:
                        g_spec_acc.set(
                            (self.spec_tokens_emitted - self.spec_model_steps)
                            / (self.spec_model_steps * n_spec))
                        g_spec_tpf.set(
                            self.spec_tokens_emitted / self.spec_model_steps)
                self._numerics_probe_chain(n_spec)
                for i, u in enumerate(uids):
                    for t in out[i, : emitted[i]]:
                        if u in active:
                            accept(u, t)
        with self._tracer.span("serve:finish", requests=len(prompts)):
            stall = self._log.settle()  # of the last call, if it was slow: none follows to witness it
            if stall is not None:
                self._stalled(stall)
            if tracker is not None:
                # final refresh: the last finishes land after the last chain
                # boundary's sample, so goodput/tokens-per-s see them here
                tracker.sample_gauges()
            if registry is not None:
                g_queue.set(0.0)
                g_occ.set(0.0)
                g_free.set(float(self.state.free_blocks))
                g_util.set(self.state.utilization)
            return [outputs[i] for i in range(len(prompts))]
