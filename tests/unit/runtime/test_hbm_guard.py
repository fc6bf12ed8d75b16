"""Pre-flight HBM-fit guard + unverified-composition guards (ISSUE 4
satellites).

The guard must fire BEFORE any device materialization — an over-budget config
is refused with its estimate, not left to the allocator. These tests drive
the guard with an
explicit device-memory override (CPU backends report no budget)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.autotuning.autotuner import estimate_state_memory
from deepspeed_tpu.utils.hbm import HBMBudgetError, check_hbm_fit, device_memory_bytes

from ..simple_model import simple_model_spec


@pytest.fixture
def devices():
    import jax

    return jax.devices()


BASE_CFG = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "steps_per_print": 10_000,
}


# ------------------------------------------------------------ memory model
def test_estimate_adds_activation_and_logit_terms():
    base = estimate_state_memory(int(1e6), 0, dp_world=1)
    with_acts = estimate_state_memory(
        int(1e6), 0, dp_world=1, micro_batch=4, seq_len=1024,
        hidden_size=512, num_layers=8, remat=True)
    no_remat = estimate_state_memory(
        int(1e6), 0, dp_world=1, micro_batch=4, seq_len=1024,
        hidden_size=512, num_layers=8, remat=False)
    assert base < with_acts < no_remat

    with_logits = estimate_state_memory(
        int(1e6), 0, dp_world=1, micro_batch=4, seq_len=1024,
        vocab_size=50_000)
    fused = estimate_state_memory(
        int(1e6), 0, dp_world=1, micro_batch=4, seq_len=1024,
        vocab_size=50_000, fused_ce=True)
    # fp32 logits + softmax grad + the CE-backward temp pair (the round-9
    # calibration blind spot): 4 logit-class arrays
    assert with_logits - base == 4 * 1024 * 50_000 * 16
    assert base < fused < with_logits

    # bf16 accumulator halves the grads term; positional form is unchanged
    fp32 = estimate_state_memory(int(1e6), 0, dp_world=1)
    bf16 = estimate_state_memory(int(1e6), 0, dp_world=1, accum_dtype_bytes=2)
    assert fp32 - bf16 == int(1e6) * 2
    assert fp32 == int(1e6) * (4 + 4 + 8)


def test_estimate_attention_temp_term():
    """The materialized-attention backward workspace (the temp-buffer blind
    spot): 5 fp32 score-class arrays per layer, gone under flash attention
    (the kernel never materializes scores)."""
    kw = dict(micro_batch=4, seq_len=256, hidden_size=128, num_layers=2,
              remat=False)
    base = estimate_state_memory(int(5e5), 1, dp_world=8, **kw)
    with_attn = estimate_state_memory(int(5e5), 1, dp_world=8, num_heads=4, **kw)
    assert with_attn - base == 4 * 4 * 256 * 256 * 4 * 2 * 5
    flash = estimate_state_memory(int(5e5), 1, dp_world=8, num_heads=4,
                                  flash_attention=True, **kw)
    assert flash == base
    # remat recomputes scores one layer at a time: the workspace term must
    # not scale with depth (a 48L remat'd model is not 252 GiB of temps)
    kw_r = dict(kw, remat=True)
    base_r = estimate_state_memory(int(5e5), 1, dp_world=8, **kw_r)
    attn_r = estimate_state_memory(int(5e5), 1, dp_world=8, num_heads=4, **kw_r)
    assert attn_r - base_r == 4 * 4 * 256 * 256 * 4 * 1 * 5


def test_estimate_tracks_bench_config_peak():
    """Calibration closure for the round-9 finding: on the CPU bench config
    (2L x 128h, micro 4 x seq 256, bf16 + stage 1, materialized attention)
    the estimate must cover XLA's measured peak (67.4 MiB at dp=8) within
    the 1.2x warn threshold — it used to sit at ~5x."""
    est = estimate_state_memory(
        459392, 1, dp_world=8, compute_dtype_bytes=2, accum_dtype_bytes=4,
        micro_batch=4, seq_len=256, hidden_size=128, num_layers=2,
        vocab_size=512, num_heads=4, remat=False)
    measured_peak = 67_421_149  # memory_analysis() on this jax/XLA, dp=8
    assert measured_peak / est < 1.2, (est, measured_peak / est)
    # and it must not have ballooned into uselessness either
    assert est < 3 * measured_peak


def test_check_hbm_fit_modes():
    # no budget discoverable -> no-op regardless of size
    assert check_hbm_fit(1 << 60, what="x", mode="warn")
    assert check_hbm_fit(1 << 60, what="x", mode="refuse")

    budget = 16 << 30
    assert check_hbm_fit(10 << 30, what="x", mode="refuse", device_memory=budget)
    assert not check_hbm_fit(20 << 30, what="x", mode="warn", device_memory=budget)
    with pytest.raises(HBMBudgetError, match="GiB"):
        check_hbm_fit(20 << 30, what="x", mode="refuse", device_memory=budget)
    with pytest.raises(ValueError):
        check_hbm_fit(1, what="x", mode="bogus")


def test_device_memory_env_override(monkeypatch):
    monkeypatch.setenv("DSTPU_DEVICE_MEMORY_GB", "16")
    assert device_memory_bytes() == 16 << 30


# ------------------------------------------------------------ engine guard
def test_engine_refuses_over_budget_before_materialization(devices):
    cfg = dict(BASE_CFG)
    cfg["hbm_guard"] = {"enabled": True, "device_memory_gb": 1e-6}
    with pytest.raises(HBMBudgetError) as ei:
        deepspeed_tpu.initialize(model=simple_model_spec(), config=cfg)
    # the refusal carries the byte estimate and the budget
    assert ("GiB" in str(ei.value) or "MiB" in str(ei.value))
    assert "budget" in str(ei.value)


def test_engine_warns_by_default_and_proceeds(devices, monkeypatch):
    from deepspeed_tpu.utils import hbm as hbm_mod

    msgs = []
    monkeypatch.setattr(hbm_mod.logger, "warning",
                        lambda m, *a, **k: msgs.append(str(m)))
    cfg = dict(BASE_CFG)
    cfg["hbm_guard"] = {"device_memory_gb": 1e-6}  # enabled stays False
    engine, *_ = deepspeed_tpu.initialize(model=simple_model_spec(), config=cfg)
    assert engine is not None
    assert any("HBM pre-flight" in m for m in msgs)


def test_engine_fits_is_silent(devices):
    cfg = dict(BASE_CFG)
    cfg["hbm_guard"] = {"enabled": True, "device_memory_gb": 64.0}
    engine, *_ = deepspeed_tpu.initialize(model=simple_model_spec(), config=cfg)
    assert engine is not None


def test_v2_engine_refuses_over_budget(monkeypatch):
    from .. import simple_model  # noqa: F401  (import side effects none)
    from tests.unit.inference.test_inference_v2 import make_model

    cfg, _, params = make_model()
    monkeypatch.setenv("DSTPU_DEVICE_MEMORY_GB", "0.000001")
    from deepspeed_tpu.inference import InferenceEngineV2

    with pytest.raises(HBMBudgetError, match="KV pool"):
        InferenceEngineV2(cfg, params, {"dtype": "fp32", "hbm_check": "refuse"})
    # default mode warns but builds
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32"})
    assert eng is not None


def test_v1_engine_refuses_over_budget(monkeypatch):
    from tests.unit.inference.test_inference_v2 import make_model

    cfg, _, params = make_model()
    monkeypatch.setenv("DSTPU_DEVICE_MEMORY_GB", "0.000001")
    with pytest.raises(HBMBudgetError, match="param placement"):
        deepspeed_tpu.init_inference(model=cfg, params=params,
                                     config={"dtype": "fp32", "hbm_check": "refuse"})


# --------------------------------------------- quantized-serving byte math
def test_kv_byte_formulas():
    """The quantized pool/block formulas the guard, the engine sizing, and
    the capacity bench all share (utils/hbm.py)."""
    from deepspeed_tpu.utils.hbm import kv_blocks_for_bytes, kv_pool_bytes, kv_slot_bytes

    # head_dim=64: bf16 slot-head = 128 B; int8 = 64 + 4 (fp32 scale) = 68 B
    assert kv_slot_bytes(2, 2, 64, 2, None) == 2 * 2 * 2 * 128
    assert kv_slot_bytes(2, 2, 64, 2, "int8") == 2 * 2 * 2 * 68
    assert kv_slot_bytes(2, 2, 64, 2, "fp8") == kv_slot_bytes(2, 2, 64, 2, "int8")
    assert kv_pool_bytes(2, 100, 2, 64, 2, None) == 100 * kv_slot_bytes(2, 2, 64, 2)
    # at identical bytes, int8 yields >=1.8x the blocks (the capacity lever)
    budget = 1 << 22
    b_bf16 = kv_blocks_for_bytes(budget, 2, 16, 2, 64, 2, None)
    b_int8 = kv_blocks_for_bytes(budget, 2, 16, 2, 64, 2, "int8")
    assert b_int8 / b_bf16 >= 1.8


def test_v2_quantized_pool_fits_where_dense_refuses(monkeypatch):
    """The v2 pre-flight learns the quantized pool bytes: a budget the fp32
    pool blows is admitted with kv_cache_dtype='int8' — refuse-before-
    materialize with the REAL (smaller) byte count."""
    from tests.unit.inference.test_inference_v2 import make_model

    from deepspeed_tpu.inference import InferenceEngineV2

    cfg, _, params = make_model()
    # 4096 x 16 slots, head_dim 8: fp32 pool ~16.8 MB, int8 pool ~6.3 MB
    monkeypatch.setenv("DSTPU_DEVICE_MEMORY_GB", "0.012")  # ~12.9 MB budget
    v2_cfg = {"dtype": "fp32", "kv_block_size": 16, "num_kv_blocks": 4096,
              "hbm_check": "refuse"}
    with pytest.raises(HBMBudgetError, match="KV pool"):
        InferenceEngineV2(cfg, params, v2_cfg)
    eng = InferenceEngineV2(cfg, params, dict(v2_cfg, kv_cache_dtype="int8"))
    assert eng.pool.k.dtype.name == "int8" and eng.pool.k_scale is not None


def test_v2_woq_estimate_admits_where_dense_refuses(monkeypatch):
    """WOQ weights enter the pre-flight with the quantized byte formula
    (values + scales through the same eligibility predicate as the real
    pass): a model that only fits quantized is admitted."""
    from tests.unit.inference.test_inference_v2 import make_model

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.inference.woq import quantized_bytes_estimate, woq_bytes

    cfg, _, params = make_model(vocab_size=512, hidden_size=256,
                                intermediate_size=512)
    import jax

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    dense_mb = n_params * 4 / (1 << 20)
    est = quantized_bytes_estimate(params, "int8", min_size=0, dense_itemsize=4)
    assert est < 0.6 * n_params * 4  # the estimate reflects the shrink
    budget_gb = (est + 0.35 * (dense_mb * (1 << 20))) / (1 << 30) / 0.92
    monkeypatch.setenv("DSTPU_DEVICE_MEMORY_GB", f"{budget_gb:.6f}")
    v2_cfg = {"dtype": "fp32", "kv_block_size": 4, "num_kv_blocks": 8,
              "hbm_check": "refuse"}
    with pytest.raises(HBMBudgetError):
        InferenceEngineV2(cfg, params, v2_cfg)
    eng = InferenceEngineV2(cfg, params, dict(
        v2_cfg, quant={"enabled": True, "bits": 8, "min_leaf_size": 0}))
    # and the estimate the guard admitted on tracks what actually landed
    actual = woq_bytes(eng.params)
    assert actual <= est * 1.05


def test_v2_quantized_estimate_calibration_within_threshold():
    """The serving estimate with quantized pool bytes still covers the XLA
    peak of the captured decode program inside the 1.2x warn threshold
    (telemetry/programs.py calibration — the guard isn't flying blind on
    quantized configs)."""
    from tests.unit.inference.test_inference_v2 import make_model

    from deepspeed_tpu.inference import InferenceEngineV2
    from deepspeed_tpu.telemetry import get_tracer
    from deepspeed_tpu.telemetry.programs import get_program_registry

    tr = get_tracer()
    was = tr.enabled
    tr.configure(enabled=True)
    reg = get_program_registry()
    reg.reset()
    try:
        cfg, _, params = make_model()
        eng = InferenceEngineV2(cfg, params, {
            "dtype": "fp32", "kv_block_size": 4, "num_kv_blocks": 64,
            "chunk_bucket": 8, "decode_chain": 4, "hbm_check": "off",
            "kv_cache_dtype": "int8"})
        eng.generate([np.arange(6) % cfg.vocab_size], max_new_tokens=6)
        assert reg.hbm_estimate("serving")
        chains = [lbl for lbl in reg.labels() if lbl.startswith("v2:decode_chain")]
        assert chains, f"no decode-chain capture in {reg.labels()}"
        ratio = reg.latest(chains[0]).hbm_estimate_ratio
        assert ratio is not None and ratio < 1.2, ratio
    finally:
        tr.configure(enabled=was)
        reg.reset()
        if not was:
            tr.reset()


# ---------------------------------------------------- MoE x TP composition
def test_moe_tp_mesh_no_longer_refused(devices):
    """ISSUE 15 flips the old VERDICT-r5 refusal: ep×tp meshes build — MoE
    models route their token dispatch through the collective all_to_all
    (parallel/moe.py; trajectory + global-math pins live in
    test_ulysses_moe.py::TestMoETPComposition, unservable shapes still
    raise loudly there). A dense model on the same mesh simply trains."""
    cfg = dict(BASE_CFG)
    cfg["mesh"] = {"ep": 2, "tp": 2, "dp": -1}
    engine, *_ = deepspeed_tpu.initialize(model=simple_model_spec(), config=cfg)
    assert dict(engine.mesh.shape)["ep"] == 2 and dict(engine.mesh.shape)["tp"] == 2
