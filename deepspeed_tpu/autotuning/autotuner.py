"""Autotuner: search ZeRO stage × micro-batch for best throughput.

Reference: ``autotuning/autotuner.py:42 Autotuner`` (``tune()`` :404) with its
model-based pruning (``tuner/model_based_tuner.py``: estimate per-stage
memory, skip configs that cannot fit) and experiment runner
(``scheduler.py``). TPU differences: experiments run in-process (no
multi-node job launches — one SPMD program per candidate), the memory model
uses the real param count + XLA's compiled peak-memory when available, and
the search space is (zero stage, micro batch, remat), optionally crossed with
model-level overrides via ``model_factory`` (e.g. ``scan_layers``/``fused_ce``
on a ``TransformerConfig`` — the knobs PERF.md round 3 measured to dominate).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist, logger


def estimate_state_memory(n_params: int, zero_stage: int, dp_world: int,
                          dtype_bytes: int = 4, opt_factor: int = 2, *,
                          compute_dtype_bytes: int = 0,
                          accum_dtype_bytes: Optional[int] = None,
                          micro_batch: int = 0,
                          seq_len: int = 0,
                          hidden_size: int = 0,
                          num_layers: int = 0,
                          vocab_size: int = 0,
                          num_heads: int = 0,
                          remat: bool = True,
                          fused_ce: bool = False,
                          flash_attention: bool = False) -> int:
    """Bytes/device for params+grads+optimizer state under a ZeRO stage
    (reference ``tuner/model_based_tuner.py`` memory model; Adam opt_factor=2
    fp32 moments), plus — when the model/batch geometry is given — the
    transient terms the original model ignored and an out-of-memory at
    ~890M params proved load-bearing:

    - a compute-dtype parameter copy (``compute_dtype_bytes`` > 0): the
      engine casts fp32 masters to bf16 per step; under ZeRO-3 the gather
      materializes the full copy transiently
    - the gradient ACCUMULATOR in its own dtype (``accum_dtype_bytes``,
      default ``dtype_bytes``) — bf16 accumulation halves this term
    - activations: with remat, ~2 residuals of [micro, seq, hidden] per
      layer boundary; without, ~12 per layer (qkv/attn/mlp intermediates)
    - logits + CE softmax grad: [micro, seq, vocab] in fp32 ×2 — the single
      biggest transient for big-vocab models; fused (chunked) CE reduces it
      to ~1/8
    - XLA temp/fusion workspace (the blind spot PR-7's calibration surfaced:
      ``hbm/estimate_ratio`` ~5x on the bf16 stage-1 CPU bench config —
      ``temp_bytes`` dominated the peak while every term above tracked the
      persistent state). Three structural contributors, coefficients fitted
      against ``memory_analysis().temp_size_in_bytes`` over layer/seq/batch
      sweeps of the bench model (each within ~15%):
        * non-flash attention backward materializes the score matrix class
          ~5x in fp32 per layer ([micro, heads, seq, seq]: scores, probs,
          both grads + a cast copy) — one live layer under remat (scores
          are recomputed per layer), zero when ``flash_attention`` (the
          Pallas kernel never materializes scores, that being the point)
        * CE backward holds ~2 more fp32 logit-class arrays beyond the
          counted pair (log-softmax + dlogits), same 1/8 fused-CE discount
        * dense/MLP fusion gradients: ~8 fp32 [micro, seq, hidden] per
          layer un-remat (~4 with remat: one layer recomputes at a time,
          but boundary residual grads persist)

    The positional-args form is unchanged (grads term == accumulator at
    ``dtype_bytes``), so existing callers see identical estimates — the
    temp terms engage only when the model/batch geometry is given.
    """
    P = n_params
    params_b = P * dtype_bytes
    grads_b = P * (accum_dtype_bytes if accum_dtype_bytes is not None else dtype_bytes)
    opt_b = P * dtype_bytes * opt_factor
    if zero_stage >= 1:
        opt_b //= dp_world
    if zero_stage >= 2:
        grads_b //= dp_world
    if zero_stage >= 3:
        params_b //= dp_world
    total = params_b + grads_b + opt_b
    if compute_dtype_bytes:
        total += P * compute_dtype_bytes
    tokens = micro_batch * seq_len
    if tokens and hidden_size and num_layers:
        act_bytes = compute_dtype_bytes or 2
        per_layer = 2 if remat else 12
        total += tokens * hidden_size * act_bytes * num_layers * per_layer
        # XLA fusion-gradient workspace (fp32)
        total += tokens * hidden_size * 4 * num_layers * (4 if remat else 8)
    if tokens and vocab_size:
        logit_b = tokens * vocab_size * 4 * 2  # fp32 logits + softmax grad
        logit_b += tokens * vocab_size * 4 * 2  # CE bwd transients (temp)
        total += logit_b // 8 if fused_ce else logit_b
    if tokens and num_heads and seq_len and num_layers and not flash_attention:
        # materialized-attention backward workspace (fp32 score-matrix
        # class); under remat one layer's scores are recomputed/live at a
        # time, so the term must not scale with depth there — a 48-layer
        # remat'd model would otherwise be rejected by hundreds of GiB
        live_layers = 1 if remat else num_layers
        total += micro_batch * num_heads * seq_len * seq_len * 4 * live_layers * 5
    return total


@dataclass
class ExperimentResult:
    config: Dict
    throughput: float = 0.0  # samples/sec
    latency_s: float = 0.0
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None


class Autotuner:
    """In-process config search (reference ``Autotuner`` autotuner.py:42)."""

    def __init__(
        self,
        model_spec,
        base_config: Dict,
        micro_batch_candidates: Sequence[int] = (1, 2, 4, 8),
        stage_candidates: Sequence[int] = (0, 1, 2, 3),
        remat_candidates: Sequence[bool] = (False, True),
        memory_budget_bytes: Optional[int] = None,
        metric: str = "throughput",
        model_factory=None,
        model_override_candidates: Sequence[Dict] = ({},),
    ):
        """``model_factory(**overrides) -> model_spec`` extends the search to
        MODEL-level knobs the engine config cannot reach (e.g. a
        ``TransformerConfig``'s ``scan_layers``/``fused_ce`` — PERF.md round 3
        measured a ~25% wall-clock swing on scan_layers alone). Each dict in
        ``model_override_candidates`` multiplies the config space; with no
        factory, ``model_spec`` is used as-is."""
        self.model_spec = model_spec
        self.base_config = dict(base_config)
        self.micro_batch_candidates = list(micro_batch_candidates)
        self.stage_candidates = list(stage_candidates)
        self.remat_candidates = list(remat_candidates)
        self.memory_budget = memory_budget_bytes
        self.metric = metric
        self.model_factory = model_factory
        self.model_override_candidates = list(model_override_candidates)
        if not self.model_override_candidates:
            raise ValueError("model_override_candidates must not be empty (use ({},))")
        if model_factory is None and self.model_override_candidates != [{}]:
            raise ValueError("model_override_candidates needs model_factory")
        if not (self.micro_batch_candidates and self.stage_candidates and self.remat_candidates):
            raise ValueError("candidate lists must not be empty")
        self.results: List[ExperimentResult] = []
        self.best_overrides: Optional[Dict] = None
        self.best_model_spec = None

    # ------------------------------------------------------------ space
    def _candidates(self) -> List[Dict]:
        out = []
        for stage in self.stage_candidates:
            for mb in self.micro_batch_candidates:
                for remat in self.remat_candidates:
                    for overrides in self.model_override_candidates:
                        cfg = dict(self.base_config)
                        cfg.pop("train_batch_size", None)  # re-derived from micro
                        cfg["train_micro_batch_size_per_gpu"] = mb
                        zo = dict(cfg.get("zero_optimization", {}))
                        zo["stage"] = stage
                        cfg["zero_optimization"] = zo
                        ac = dict(cfg.get("activation_checkpointing", {}))
                        ac["enabled"] = remat  # remat=False must really disable it
                        if remat:
                            ac.setdefault("policy", "dots")  # keep a user's policy
                        cfg["activation_checkpointing"] = ac
                        if overrides:
                            # engine-config-invisible; popped before initialize
                            cfg["_model_overrides"] = dict(overrides)
                        out.append(cfg)
        return out

    def _fits_memory(self, cfg: Dict, n_params: int, dp_world: int) -> bool:
        need = estimate_state_memory(n_params, cfg["zero_optimization"]["stage"], dp_world)
        if need <= self.memory_budget:
            return True
        logger.info(
            f"autotuner: prune stage={cfg['zero_optimization']['stage']} "
            f"micro={cfg['train_micro_batch_size_per_gpu']} "
            f"(est {need/1e9:.2f} GB > budget {self.memory_budget/1e9:.2f} GB)"
        )
        return False

    # ------------------------------------------------------------ experiments
    def run_experiment(self, config: Dict, steps: int = 5, warmup: int = 2,
                       batch_fn=None, seed: int = 0) -> ExperimentResult:
        import deepspeed_tpu

        try:
            overrides = config.get("_model_overrides")
            model = self.model_factory(**overrides) if overrides else self.model_spec
            engine_cfg = {k: v for k, v in config.items() if k != "_model_overrides"}
            engine, *_ = deepspeed_tpu.initialize(model=model, config=engine_cfg, seed=seed)
            bs = engine.train_batch_size
            user_make = batch_fn or (lambda s: self._default_batch(bs, s))

            def make(s):
                # batch_fn cannot know each CANDIDATE's global batch (micro
                # varies across the sweep): hand it a pool and slice the
                # candidate's rows — a short pool is a real config error.
                b = user_make(s)
                lead = jax.tree_util.tree_leaves(b)[0].shape[0]
                if lead < bs:
                    raise ValueError(
                        f"batch_fn returned {lead} rows < candidate train_batch_size "
                        f"{bs}; return at least max(micro)*dp_world rows")
                return jax.tree_util.tree_map(lambda x: x[:bs], b) if lead > bs else b

            for i in range(warmup):
                engine.train_batch(make(seed + i))
            t0 = time.perf_counter()
            for i in range(steps):
                m = engine.train_batch(make(seed + warmup + i))
            np.asarray(m["loss"])  # sync
            dt = (time.perf_counter() - t0) / steps
            return ExperimentResult(config=config, throughput=bs / dt, latency_s=dt)
        except Exception as e:  # noqa: BLE001 - an infeasible config is a result
            return ExperimentResult(config=config, error=f"{type(e).__name__}: {e}")

    def _default_batch(self, batch_size: int, seed: int):
        raise ValueError("pass batch_fn= to tune()/run_experiment() — the autotuner "
                         "does not know your model's input schema")

    def _n_params_for(self, overrides: Optional[Dict]) -> int:
        """Parameter count for a candidate's model, shape-only (no compute)."""
        from deepspeed_tpu.runtime.model import as_model_spec

        spec = as_model_spec(self.model_factory(**overrides) if overrides else self.model_spec)
        shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)))

    def tune(self, steps: int = 5, batch_fn=None, seed: int = 0) -> Tuple[Dict, List[ExperimentResult]]:
        """Run the sweep, return (best_config, all_results) (reference
        ``tune()`` autotuner.py:404 + ``get_best_space_config``).

        The returned config is directly consumable by ``initialize``. When the
        winner used model overrides, ``self.best_overrides`` records them and
        ``self.best_model_spec`` is the rebuilt spec — pass THAT as ``model=``
        (the engine config cannot carry model-level knobs)."""
        import deepspeed_tpu

        if self.memory_budget is None:
            cfgs = self._candidates()
        else:
            from deepspeed_tpu.topology.mesh import get_data_parallel_world_size

            # probe: dp world from a throwaway engine on the base config
            probe_cfg = dict(self.base_config)
            probe_cfg.setdefault("train_micro_batch_size_per_gpu", self.micro_batch_candidates[0])
            engine, *_ = deepspeed_tpu.initialize(model=self.model_spec, config=probe_cfg, seed=seed)
            dp_world = get_data_parallel_world_size(engine.mesh)
            del engine
            # per-override param counts (overrides may resize the model);
            # repr-canonicalized keys tolerate unhashable override values
            n_params = {"": self._n_params_for(None)}
            for ov in self.model_override_candidates:
                if ov:
                    n_params[repr(sorted(ov.items()))] = self._n_params_for(ov)

            def params_of(cfg):
                ov = cfg.get("_model_overrides")
                return n_params[repr(sorted(ov.items())) if ov else ""]

            cfgs = [c for c in self._candidates()
                    if self._fits_memory(c, params_of(c), dp_world)]
        if not cfgs:
            raise RuntimeError("autotuner: every candidate exceeds the memory budget")
        self.results = [self.run_experiment(c, steps=steps, batch_fn=batch_fn, seed=seed) for c in cfgs]
        ok = [r for r in self.results if r.ok]
        if not ok:
            raise RuntimeError(
                "autotuner: all experiments failed; first error: " + self.results[0].error
            )
        best = max(ok, key=lambda r: r.throughput)
        self.best_overrides = best.config.get("_model_overrides")
        self.best_model_spec = (
            self.model_factory(**self.best_overrides) if self.best_overrides else self.model_spec
        )
        best_config = {k: v for k, v in best.config.items() if k != "_model_overrides"}
        if self.best_overrides:
            # The winning configuration includes MODEL-level overrides that the
            # returned config cannot carry: a caller who re-initializes with
            # their original model spec silently runs a non-winning model.
            logger.warning(
                "autotuner: best config includes model overrides %s — pass "
                "tuner.best_model_spec (NOT your original model spec) to "
                "initialize(), or the tuned model-level knobs are lost",
                self.best_overrides,
            )
        log_dist(
            f"autotuner: best stage={best.config['zero_optimization']['stage']} "
            f"micro={best.config['train_micro_batch_size_per_gpu']} "
            + (f"model_overrides={self.best_overrides} " if self.best_overrides else "")
            + f"({best.throughput:.1f} samples/s over {len(ok)}/{len(self.results)} viable)",
            ranks=[0],
        )
        return best_config, self.results
