"""Inference config (reference ``deepspeed/inference/config.py`` —
``DeepSpeedInferenceConfig`` pydantic model, tp via ``DeepSpeedTPConfig``)."""

from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp
from pydantic import Field

from deepspeed_tpu.config.config_utils import DeepSpeedConfigModel

_DTYPES = {
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    "fp16": jnp.float16,
    "float16": jnp.float16,
    "half": jnp.float16,
    "fp32": jnp.float32,
    "float32": jnp.float32,
    "int8": jnp.int8,
}


class TPConfig(DeepSpeedConfigModel):
    """Tensor-parallel sizing (reference ``DeepSpeedTPConfig``)."""

    enabled: bool = True
    tp_size: int = 1


class QuantConfig(DeepSpeedConfigModel):
    """Weight-only quantization (reference ``QuantizationConfig`` int4/int8 +
    ``ops/fp_quantizer`` fp8; implementation ``inference/woq.py``)."""

    enabled: bool = False
    bits: int = 8
    group_size: int = 128
    qtype: str = "int"  # 'int' (int8/int4 by bits) | 'fp' (fp8)
    min_leaf_size: int = 1 << 16  # kernels smaller than this stay dense
    # Per-tensor-class selection (woq.TENSOR_CLASSES): which weight families
    # quantize — 'attn' (wq/wk/wv/wo), 'mlp' (w_up/w_gate/w_down), 'experts',
    # 'lm_head'. None = every eligible kernel (the legacy behavior).
    tensor_classes: Optional[list] = None


class ZeroInferenceConfig(DeepSpeedConfigModel):
    """ZeRO-Inference: weights live in host memory and stream through the
    forward (reference stage-3-for-inference + AIO, blogs/deepspeed-gds)."""

    enabled: bool = False
    offload: str = "cpu"  # 'cpu' (pinned host memory) | 'nvme' (AIO-streamed layers)
    min_leaf_size: int = 1 << 16  # leaves smaller than this stay on device (cpu mode)
    nvme_path: Optional[str] = None  # required for offload='nvme'
    num_buffers: int = 2  # layers resident at once in nvme mode (double buffer)


class ServingSLOConfig(DeepSpeedConfigModel):
    """``serving_slo`` block — the targets that turn per-request latency
    records into **goodput** (fraction of finished requests meeting SLO,
    the number a capacity plan is written against).

    A finished request meets its SLO when TTFT (arrival -> first token) is
    within ``ttft_ms`` AND its mean per-output-token latency is within
    ``tpot_ms``; a ``None`` target is not enforced. ``window_s`` bounds the
    rolling windows behind the ``serving/goodput``, ``serving/tokens_per_s``
    and ``serving/preemption_rate`` gauges (see ``inference/lifecycle.py``).

    Admission control (serving router, ISSUE 12): ``admission`` turns the
    TTFT target into a gate applied BEFORE dispatching a prefill — a request
    whose projected TTFT (wait so far + the replica's estimated time to
    first token) already exceeds ``ttft_ms * admission_ttft_factor`` is
    **shed** (rejected immediately, so it stops consuming queue capacity
    that on-budget requests could use) or **deferred** (left queued for a
    replica that can still make the budget; it sheds only when every replica
    is over). ``"none"`` admits everything — the engine-only behavior.
    """

    ttft_ms: Optional[float] = None  # time-to-first-token target
    tpot_ms: Optional[float] = None  # mean time-per-output-token target
    window_s: float = 30.0  # rolling window for goodput/rate gauges
    admission: str = "none"  # none | shed | defer (router-level gate)
    admission_ttft_factor: float = 1.0  # shed when projected TTFT > target*factor


class InferenceConfig(DeepSpeedConfigModel):
    """Reference ``DeepSpeedInferenceConfig`` (inference/config.py:77)."""

    dtype: str = "bf16"
    tensor_parallel: TPConfig = Field(default_factory=TPConfig)
    quant: QuantConfig = Field(default_factory=QuantConfig)
    zero_inference: ZeroInferenceConfig = Field(default_factory=ZeroInferenceConfig)
    max_out_tokens: int = 1024  # hard cap on generate(max_new_tokens=...)
    min_out_tokens: int = 1  # reserved (reference scheduler admission knob)
    max_batch_size: Optional[int] = None  # hard cap on generate batch size
    replace_with_kernel_inject: bool = True  # accepted for parity; Pallas ops
    # are selected via the ops registry rather than module swapping
    seq_bucket: int = 64  # pad prompt lengths up to a multiple (compile reuse)
    kv_cache_dtype: Optional[str] = None  # default: same as dtype
    # Recompile detection (diagnostics/recompile.py) on the engine's jitted
    # programs: the seq_bucket claim above ("recompiles are rare") is checked,
    # not hoped — a recompile of an already-compiled program warns with the
    # offending argument shape diff, and runaway bucket-cache growth warns
    # too. Host-side, one cache-size check per call; disable to shave that.
    recompile_warnings: bool = True
    # distinct compiled generate programs before the cache-growth warning
    max_generate_buckets: int = 16
    # Pre-flight HBM-fit check (utils/hbm.py) before param placement:
    # "warn" | "refuse" | "off"; the bench extras and chip_smoke.py run
    # "refuse". With WOQ enabled the estimate uses the
    # quantized byte formula (woq.quantized_bytes_estimate — values + scales
    # through the same eligibility predicate the real pass applies), so a
    # model that only fits quantized is admitted; zero_inference keeps the
    # big weights off-device and skips the check entirely.
    hbm_check: str = "warn"

    @property
    def jax_dtype(self) -> Any:
        return _DTYPES[self.dtype.lower()]

    @property
    def kv_dtype(self) -> Any:
        return _DTYPES[(self.kv_cache_dtype or self.dtype).lower()]
