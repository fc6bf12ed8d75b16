"""Inference engine (v1): TP-sharded generation over a device mesh.

TPU-native analog of the reference ``InferenceEngine`` (``inference/engine.py:40``)
+ ``init_inference`` (``__init__.py:291``). Where the reference mutates the
torch module (kernel injection via ``replace_transformer_layer``, weight
slicing per policy, CUDA-graph capture), here:

  - model-parallel "group creation" = building a mesh with a ``tp`` axis and
    placing params by the model's partition rules (the AutoTP analog —
    reference ``_create_model_parallel_group`` :247 + ``module_inject``)
  - "kernel injection" = the ops registry already routes attention/norms to
    Pallas TPU kernels; no module surgery
  - "CUDA graph capture" = ``jax.jit``: the whole generate loop (prefill +
    ``lax.scan`` over decode steps + sampling) is ONE compiled XLA program
  - prompt lengths are bucketed (``seq_bucket``) so recompiles are rare
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.model import KVCache, decode_step, init_cache, prefill
from deepspeed_tpu.inference.sampling import sample_logits
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig, causal_lm_partition_rules
from deepspeed_tpu.parallel.autotp import place_parameters
from deepspeed_tpu.inference.ragged import _round_up
from deepspeed_tpu.topology.mesh import build_mesh, set_mesh
from deepspeed_tpu.utils.logging import log_dist, logger


class InferenceEngine:
    """Generation engine over a TP(×DP) mesh (reference ``InferenceEngine``)."""

    def __init__(
        self,
        model_config: TransformerConfig,
        params: Any,
        config: InferenceConfig,
        mesh: Optional[Mesh] = None,
    ):
        self.model_config = model_config
        self.config = config
        tp = config.tensor_parallel.tp_size if config.tensor_parallel.enabled else 1
        if mesh is None:
            mesh = build_mesh(axis_sizes={"tp": tp, "dp": -1})
        self.mesh = mesh
        set_mesh(mesh)
        self.module = CausalLM(model_config)

        # Place params: TP partition rules over the mesh, inference dtype.
        dtype = config.jax_dtype
        if dtype == jnp.int8:
            raise ValueError(
                "dtype='int8' would truncate weights via astype; int8 weights "
                "are weight-only quantization — use quant={'enabled': True, 'bits': 8}"
            )
        nvme_mode = config.zero_inference.enabled and config.zero_inference.offload == "nvme"
        woq_on = config.quant.enabled and not nvme_mode
        tp_size = max(mesh.shape["tp"], 1)
        # WOQ ordering vs placement: on a tp=1 mesh quantization runs BEFORE
        # placement, so the dense weights never materialize on device and the
        # guard's quantized byte formula is the true placement peak. On tp>1
        # the pre-quantized flat layout can't ride the name-based dim rules
        # (it would place replicated — MORE per-device bytes than a dense tp
        # shard for tp>2), so those meshes keep the original flow: place the
        # dense shards, then quantize in place.
        pre_quant = woq_on and tp_size == 1
        if config.hbm_check != "off" and not config.zero_inference.enabled:
            # refuse/warn BEFORE placement, with the estimate in the
            # message; skipped when
            # zero_inference keeps the big weights off-device. With
            # pre-placement WOQ the estimate is the QUANTIZED byte formula
            # (values + scales through the same eligibility predicate the
            # real pass applies) — a model that only fits quantized must be
            # admitted; tp>1 keeps the dense-shard upper bound (that IS the
            # placement peak there).
            from deepspeed_tpu.utils.hbm import check_hbm_fit

            dtype_b = jnp.dtype(dtype).itemsize
            if pre_quant:
                from deepspeed_tpu.inference.woq import (
                    quantized_bytes_estimate,
                    woq_format,
                )

                need = quantized_bytes_estimate(
                    params, woq_format(config.quant),
                    min_size=config.quant.min_leaf_size,
                    classes=config.quant.tensor_classes, dense_itemsize=dtype_b)
            else:
                n_elems = sum(x.size for x in jax.tree_util.tree_leaves(params))
                need = n_elems * dtype_b // tp_size
            check_hbm_fit(need, what="init_inference param placement",
                          mode=config.hbm_check)
        if woq_on:
            # WOQ: int8/int4/fp8 bytes in HBM, dequant fused into each matmul
            # (reference inference/quantization + fp_quantizer; see woq.py).
            # In NVMe mode quantization happens per layer slice inside
            # NVMeStreamedParams instead (stacked-tree quant breaks slicing).
            from deepspeed_tpu.inference.woq import quantize_params, woq_bytes, woq_format

            fmt = woq_format(config.quant)
            min_size = config.quant.min_leaf_size
            classes = config.quant.tensor_classes
            dense_bytes = sum(
                x.size * jnp.dtype(dtype).itemsize
                for x in jax.tree_util.tree_leaves(params))
            if pre_quant:
                params = quantize_params(params, fmt, min_size=min_size,
                                         classes=classes)
                q_bytes = woq_bytes(params)
            self.params = place_parameters(params, mesh, causal_lm_partition_rules, dtype)
            if not pre_quant:
                # tp>1: quantize the placed shards (sharding preserved by the
                # jitted per-leaf math; transient peak = dense + quantized)
                self.params = jax.jit(lambda p: quantize_params(
                    p, fmt, min_size=min_size, classes=classes))(self.params)
                q_bytes = woq_bytes(self.params)
            log_dist(
                f"WOQ[{fmt}]: weights {dense_bytes/1e6:.0f} MB -> {q_bytes/1e6:.0f} MB",
                ranks=[0],
            )
        else:
            self.params = place_parameters(params, mesh, causal_lm_partition_rules, dtype)

        self._streamed = None  # NVMe mode: layer-streamed forward/generate
        if config.zero_inference.enabled:
            # ZeRO-Inference: big weights (quantized or dense) leave HBM.
            # 'cpu': pinned host memory behind stream-on-read wrappers — the
            # compiled forward transfers each layer's weights as it needs
            # them. 'nvme': weights live ON DISK through the AIO pool, at
            # most num_buffers layers in RAM — serves models larger than
            # host memory (reference partitioned_param_swapper.py:37). Both
            # compose with WOQ: 4x smaller weights -> 4x less link/disk
            # traffic, the reference's headline ZeRO-Inference + quant combo.
            zcfg = config.zero_inference
            if zcfg.offload == "cpu":
                from deepspeed_tpu.inference.woq import offload_params

                self.params = offload_params(self.params, min_size=zcfg.min_leaf_size)
            elif zcfg.offload == "nvme":
                if not zcfg.nvme_path:
                    raise ValueError("zero_inference.offload='nvme' requires 'nvme_path'")
                from deepspeed_tpu.inference.zero_inference import (
                    NVMeStreamedParams,
                    StreamedForward,
                )

                quant_fmt = None
                if config.quant.enabled:
                    from deepspeed_tpu.inference.woq import woq_format

                    quant_fmt = woq_format(config.quant)
                streamed_params = NVMeStreamedParams(
                    self.params, zcfg.nvme_path, num_buffers=zcfg.num_buffers,
                    quant_fmt=quant_fmt, quant_min_size=config.quant.min_leaf_size)
                self._streamed = StreamedForward(streamed_params, model_config, dtype)
                # only the resident (non-layer) params stay in self.params
                self.params = streamed_params.resident
            else:
                raise ValueError(f"zero_inference.offload={zcfg.offload!r} (cpu|nvme)")

        n_params = sum(x.size for x in jax.tree_util.tree_leaves(self.params))
        log_dist(f"InferenceEngine: {n_params/1e6:.1f}M params, mesh={dict(mesh.shape)}, dtype={config.dtype}")
        self._generate_cache: Dict[tuple, Any] = {}

        def fwd(p, batch):
            if config.quant.enabled or config.zero_inference.enabled:
                from deepspeed_tpu.inference.woq import dequantize_params

                p = dequantize_params(p, dtype)  # flax path needs plain arrays
            return self.module.apply({"params": p}, batch, train=False)

        # Recompile detection (diagnostics/recompile.py): the seq_bucket
        # design claims recompiles are rare — with the detector that claim is
        # checked on every dispatch, and a violation names the argument that
        # drifted (e.g. an unbucketed mask shape).
        self._fwd_detector = self._gen_detector = None
        if config.recompile_warnings:
            from deepspeed_tpu.diagnostics.recompile import RecompileDetector

            self._fwd_detector = RecompileDetector(
                "inference.forward", arg_names=("params", "batch"))
            self._gen_detector = RecompileDetector(
                "inference.generate", arg_names=("params", "ids", "mask", "rng"))
        self._forward = jax.jit(fwd)
        if self._fwd_detector is not None:
            self._forward = self._fwd_detector.wrap(self._forward)

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Release held resources (NVMe mode: AIO thread pool + layer files).

        Reference parity: the engine-loop teardown around
        ``AsyncPartitionedParameterSwapper``; safe to call on any engine."""
        if self._streamed is not None:
            self._streamed.p.close()
            self._streamed = None

    # ------------------------------------------------------------------
    def refresh_params(self, params: Any) -> None:
        """Swap in new parameter VALUES keeping placements and compiled
        functions (the hybrid-engine fast path: same shapes/shardings, so the
        jit caches stay valid — no retrace, no recompile)."""
        if self.config.quant.enabled or self.config.zero_inference.enabled:
            raise NotImplementedError(
                "refresh_params on a WOQ/ZeRO-Inference engine: the param tree "
                "holds wrapped (quantized/host-offloaded) leaves that cannot be "
                "value-swapped in place; run the hybrid engine without these modes"
            )
        dtype = self.config.jax_dtype

        def _replace(old, new):
            arr = jnp.asarray(new)
            if jnp.issubdtype(arr.dtype, jnp.floating):
                arr = arr.astype(dtype)
            return jax.device_put(arr, old.sharding)

        self.params = jax.tree_util.tree_map(_replace, self.params, params)

    # ------------------------------------------------------------------
    def forward(self, batch) -> jax.Array:
        """Full-sequence forward -> logits (teacher-forcing / scoring path)."""
        if self._streamed is not None:
            raise NotImplementedError(
                "full-sequence forward() under zero_inference.offload='nvme': "
                "the layer-streamed engine serves generate(); score with a "
                "cpu-offload or resident engine")
        if not isinstance(batch, dict):
            batch = {"input_ids": jnp.asarray(batch)}
        _, logits = self._forward(self.params, batch)
        return logits

    __call__ = forward

    # ------------------------------------------------------------------
    def _build_generate(self, B, S_pad, new_tokens, sample_cfg, eos_id, pad_id):
        cfg = self.model_config
        kv_dtype = self.config.kv_dtype
        max_len = S_pad + new_tokens

        def gen(params, ids, mask, rng):
            cache = init_cache(cfg, B, max_len, kv_dtype)
            logits, cache = prefill(params, cfg, cache, ids, mask)
            rngs = jax.random.split(rng, new_tokens)
            tok = sample_logits(logits, rngs[0], **sample_cfg)
            done = tok == eos_id if eos_id is not None else jnp.zeros((B,), jnp.bool_)

            def body(carry, step_rng):
                cache, tok, done = carry
                logits, cache = decode_step(params, cfg, cache, tok)
                nxt = sample_logits(logits, step_rng, **sample_cfg)
                if eos_id is not None:
                    nxt = jnp.where(done, pad_id, nxt)
                    done = done | (nxt == eos_id)
                return (cache, nxt, done), nxt

            (_, _, _), rest = jax.lax.scan(body, (cache, tok, done), rngs[1:])
            return jnp.concatenate([tok[:, None], rest.T], axis=1)  # [B, new_tokens]

        return jax.jit(gen)

    def generate(
        self,
        input_ids,
        attention_mask=None,
        max_new_tokens: int = 32,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_token_id: Optional[int] = None,
        pad_token_id: int = 0,
        seed: int = 0,
    ) -> np.ndarray:
        """Generate continuations for right-padded prompts.

        Returns the full sequences ``[B, S + max_new_tokens]`` (prompt + new
        tokens; rows stop emitting after ``eos_token_id``).
        """
        ids = np.asarray(input_ids)
        B, S = ids.shape
        if attention_mask is None:
            attention_mask = np.ones((B, S), np.bool_)
        amask = np.array(attention_mask, np.bool_)  # copy: never mutate caller's mask
        # Cache slots are written in order, so slot index must equal token
        # position: normalize HF-style left-padded rows to right-padding by
        # compacting each row's real tokens to the front.
        if not (amask[:, :-1] >= amask[:, 1:]).all():
            ids = ids.copy()
            for r in range(B):
                keep = ids[r, amask[r]]
                ids[r, : keep.size] = keep
                ids[r, keep.size:] = 0
                amask[r, : keep.size] = True
                amask[r, keep.size:] = False
        if self.config.max_out_tokens and max_new_tokens > self.config.max_out_tokens:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} exceeds config max_out_tokens={self.config.max_out_tokens}"
            )
        if self.config.max_batch_size and B > self.config.max_batch_size:
            raise ValueError(f"batch {B} exceeds config max_batch_size={self.config.max_batch_size}")
        S_pad = _round_up(max(S, 1), self.config.seq_bucket)
        if S_pad + max_new_tokens > self.model_config.max_seq_len:
            raise ValueError(
                f"prompt (padded to {S_pad}) + max_new_tokens={max_new_tokens} exceeds "
                f"model max_seq_len={self.model_config.max_seq_len}; position tables would clamp"
            )
        mask = np.zeros((B, S_pad), np.bool_)
        mask[:, :S] = amask
        padded = np.zeros((B, S_pad), ids.dtype)
        padded[:, :S] = ids

        sample_cfg = dict(do_sample=do_sample, temperature=temperature, top_k=top_k, top_p=top_p)
        if self._streamed is not None:
            from deepspeed_tpu.inference.zero_inference import streamed_generate

            new = streamed_generate(
                self._streamed, self.model_config, self.config.kv_dtype,
                padded, mask, max_new_tokens, sample_cfg,
                eos_token_id, pad_token_id, jax.random.PRNGKey(seed))
            return np.concatenate([ids, new], axis=1)
        key = (B, S_pad, max_new_tokens, tuple(sorted(sample_cfg.items())), eos_token_id, pad_token_id)
        if key not in self._generate_cache:
            gen_fn = self._build_generate(
                B, S_pad, max_new_tokens, sample_cfg, eos_token_id, pad_token_id
            )
            if self._gen_detector is not None:
                # each bucket's first compile is expected (that IS the
                # bucketing design); a compile after that on the same bucket
                # is a real recompile and warns with the shape diff
                gen_fn = self._gen_detector.wrap(
                    gen_fn, label=f"generate[B={B},S={S_pad},new={max_new_tokens}]")
                n = len(self._generate_cache) + 1
                if n > self.config.max_generate_buckets:
                    logger.warning(
                        f"generate compile cache at {n} programs (> "
                        f"max_generate_buckets={self.config.max_generate_buckets}):"
                        " unbounded (B, S_pad, max_new_tokens) variety defeats "
                        "the bucketing — coarsen seq_bucket or fix "
                        "max_new_tokens")
            self._generate_cache[key] = gen_fn
        rng = jax.random.PRNGKey(seed)
        new = np.asarray(self._generate_cache[key](self.params, jnp.asarray(padded), jnp.asarray(mask), rng))
        return np.concatenate([ids, new], axis=1)


def init_inference(
    model: Union[TransformerConfig, Any] = None,
    config: Union[InferenceConfig, Dict, None] = None,
    params: Any = None,
    model_config: Optional[TransformerConfig] = None,
    mesh: Optional[Mesh] = None,
    **kwargs,
) -> InferenceEngine:
    """Build an inference engine (reference ``deepspeed.init_inference``
    ``__init__.py:291``). Accepts a ``TransformerConfig`` + params pytree, or a
    training engine (its master params are reused — the HybridEngine-lite
    path)."""
    if config is None:
        config = {}
    if isinstance(config, dict):
        config = InferenceConfig(**{**config, **kwargs})
    # accept a training engine directly
    if hasattr(model, "state") and hasattr(model, "model"):
        engine = model
        params = jax.device_get(engine.state.params)
        mcfg = getattr(engine.model, "transformer_config", None) or model_config
        if mcfg is None:
            raise ValueError("pass model_config= when initializing from a training engine")
        return InferenceEngine(mcfg, params, config, mesh=mesh)
    if isinstance(model, TransformerConfig):
        if params is None:
            raise ValueError("params pytree required alongside a TransformerConfig")
        return InferenceEngine(model, params, config, mesh=mesh)
    raise TypeError(f"unsupported model argument {type(model)}")
