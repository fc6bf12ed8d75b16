"""Test harness: run every test on a virtual 8-device CPU mesh.

TPU-native analog of the reference's distributed-in-one-box harness
(``tests/unit/common.py`` — DistributedTest spawning N processes): JAX SPMD
needs no process-per-rank, so we instead force the host CPU platform to expose
8 virtual devices and run real multi-device sharding/collectives in-process.
Pallas kernels run in interpret mode here; what the chip's compiler accepts
is checked by ``tests/unit/ops/test_chip_compile.py`` and ``chip_smoke.py``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in flags:
    # The suite is COMPILE-dominated on the single-core driver lane and the
    # tests assert math, not codegen quality: level 1 compiles the
    # compile-heavy tests ~2-3x faster (round-5 measurement: heaviest test
    # 88 s -> ~31 s cold) WITHOUT level 0's interpreter-slow codegen, which
    # regressed runtime-heavy tests (LoCo EF test 69 s -> 98 s at O0). Keeps
    # the default tier near the 550 s cold budget. Perf numbers never come
    # from tests (bench.py runs without this conftest).
    flags = flags + " --xla_backend_optimization_level=1"
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

# the platform is pinned in code as well as by the driver's JAX_PLATFORMS=cpu:
# a bare ``pytest tests/`` must not reach for an accelerator
jax.config.update("jax_platforms", "cpu")
# Persistent compilation cache: the suite is dominated by XLA compiles of
# near-identical tiny programs (round-2 verdict: 186 tests no longer fit one
# 550 s run). Cache survives across pytest invocations in the repo tree.
#
# Keyed per HEAD sha (ISSUE 18): jax's entry keys hash the traced program,
# not the python that built it, so a source change that alters runtime
# behavior without changing the HLO (donation tweaks, compile options read
# from the environment, jax version-adjacent serialization drift) can serve
# a stale executable across commits. One subdir per HEAD commit makes the
# cache's validity domain explicit; stale sibling dirs (and pre-keying flat
# entries) are pruned so the tree holds at most one commit's cache.
_CACHE_ROOT = os.path.join(os.path.dirname(__file__), ".jax_cache")


def _head_sha():
    """Short HEAD sha of the repo this conftest sits in, or None when git
    is unavailable / not a checkout (then the cache keys to 'nogit')."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10.0)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def jax_cache_dir(root=None, sha=None):
    """The compilation-cache dir for one commit: ``<root>/<short-sha>``."""
    return os.path.join(root or _CACHE_ROOT, sha or _head_sha() or "nogit")


def _prune_stale_cache(keep, root=None):
    """Remove sibling cache dirs from other commits and legacy flat cache
    files from the pre-keyed layout. Returns the entry names removed."""
    import shutil

    root = root or _CACHE_ROOT
    if not os.path.isdir(root):
        return []
    removed = []
    for entry in os.listdir(root):
        path = os.path.join(root, entry)
        if os.path.abspath(path) == os.path.abspath(keep):
            continue
        try:
            if os.path.isdir(path):
                shutil.rmtree(path)
            else:
                os.remove(path)
            removed.append(entry)
        except OSError:
            pass  # racing a parallel pytest: its key is the same sha anyway
    return removed


# JAX_COMPILATION_CACHE_DIR, when the environment sets it, wins here as it
# does everywhere (utils/compile_cache.py): jax reads it itself, and a
# directory placed from outside is neither re-pointed nor pruned.
_PLACED = os.environ.get("JAX_COMPILATION_CACHE_DIR")
_CACHE_DIR = _PLACED or jax_cache_dir()
if not _PLACED:
    _prune_stale_cache(keep=_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402

# ---------------------------------------------------------------- tiering
# Reference parity (tests/pytest.ini:1-14): the default run excludes the slow
# tier (`nightly`) so one cold single-core run stays under the 550 s budget;
# `pytest -m nightly tests/` runs the deep tier. Central registry (matched as
# nodeid substrings) so the tiering is auditable in one place. POLICY: every
# subsystem keeps at least one canonical parity test in the default tier —
# nightly holds the deep/duplicate/trajectory coverage, never the only
# coverage of a feature.
NIGHTLY_NODE_SUBSTRINGS = [
    # deep checkpoint/trajectory coverage (canonical: test_universal basic
    # roundtrips, test_offload_nvme_roundtrip, zpp[2-knobs0])
    "test_universal_checkpoint_moe_expert_params",
    "test_universal_checkpoint_streams_atoms",
    "test_offload_optimizer_cpu_trajectory_matches_fused",
    "test_offload_zero3_with_param_offload",
    "test_offload_checkpoint_roundtrip",
    "test_hpz_trajectory_matches_stage3",
    "test_hpz_gathers_ride_small_axis",
    "test_zpp_trajectory_close_to_exact[3-knobs1]",
    "test_zpp_trajectory_close_to_exact[3-knobs2]",
    "test_zpp_parity_path_uses_quantized_comm",
    "test_mics_trajectory_matches_full_fsdp",
    "test_onebit_close_to_uncompressed",
    "test_onebit_universal_checkpoint_excludes_residuals",
    "test_onebit_trains_and_ships_uint8",
    "test_activation_checkpointing_changes_program_not_math",
    # parallelism deep tier (canonical: sp_matches_dp_baseline, moe_trains,
    # ring_attention_matches_dense, pipelined_causal_lm_matches_plain)
    "test_expert_parallel_matches_dense_ep",
    "test_pyramid_moe_per_layer_experts",
    "test_pr_moe_residual_trains",
    "test_sp_with_zero3",
    "test_causal_lm_with_ring_sp",
    "test_ring_attention_contiguous_fallback",
    "test_pipelined_engine_end_to_end",
    "test_interleaved_causal_lm_trains",
    "test_zero3_tp_composition",
    "test_hf_flax_gpt2_autotp_exactness",
    # models deep tier (canonical: test_tp_matches_pure_dp)
    "test_remat_and_no_scan_match",
    "test_tiny_llama_trains",
    "test_gpt2_style_trains",
    # ops deep tier (canonical: flash/sparse parity + bwd tests)
    "test_causal_lm_fused_ce_matches_unfused",
    "test_layout_cache_eviction_safe_under_grad",
    # inference deep tier (canonical: cached_decode[overrides0],
    # nvme_generate_matches_resident, paged_matches_dense_v1[overrides0])
    "test_cached_decode_matches_full_forward[overrides1]",
    "test_cached_decode_matches_full_forward[overrides2]",
    "test_cached_decode_matches_full_forward[overrides3]",
    "test_cached_decode_matches_full_forward[overrides4]",
    "test_ragged_prompts_right_padded",
    "test_moe_inference_forward",
    "test_woq_generate_close_to_dense",
    "test_nvme_composes_with_woq",
    # aux deep tier (canonical kept in default: autotuner_picks_viable_config,
    # agent_restarts_without_failed_host)
    "test_autotuner_model_factory_overrides",
    "test_agent_keeps_terminated_survivors",
    "test_agent_gives_up_after_budget",
    # ---- tranche 2 (single-core budget: default must fit one cold <550 s
    # run; canonical parity anchors that STAY default are listed in each
    # subsystem comment above plus: sp_matches_dp_baseline,
    # cached_decode[overrides0], tp_matches_pure_dp, moe_trains,
    # llama_ingestion, offload_nvme_roundtrip, nvme_generate_matches_resident,
    # paged_matches_dense_v1[overrides0], packaging, padding_mask,
    # sparse-attention gradient parity, flash grads[False]) ----
    "test_ring_attention_matches_dense",       # deep ring; zigzag/unit ring tests stay
    "test_pipelined_causal_lm_matches_plain",  # interleaved_pipeline_gradients stays
    "test_zpp_trajectory_close_to_exact[2-knobs0]",
    "test_onebit_error_feedback_state",
    "test_offload_state_not_on_mesh",
    "test_param_only_offload_is_not_a_silent_noop",
    "test_hybrid_engine_train_generate_flip",
    "test_sharded_init_matches_eager_init",
    "test_woq_memory_shrinks",
    "test_nvme_generate_matches_resident_sampled_eos",
    "test_ragged_forward_uses_kernel_consistently",
    "test_initialize_training_from_hf",
    "test_num_params_matches_init[4-1-True]",
    "test_paged_matches_dense_v1[overrides1]",
    "test_paged_matches_dense_v1[overrides2]",
    "test_paged_matches_dense_v1[overrides3]",
    "test_grads_match_xla[True]",
    "test_masked_grads_match_xla[8-8]",
    "test_unequal_blocks_dense_grid",
    # flash+alibi deep grid/GQA gradient variants (canonical [False-8-8] stays)
    "TestFlashAlibi::test_grads_match_xla[False-16-8]",
    "TestFlashAlibi::test_grads_match_xla[True-8-8]",
    # HF greedy-generate comparisons (deep tier; each family's logits-parity
    # test plus the kernel/v2 parity suites stay default)
    "test_gptj_generate_matches_hf",
    "test_bloom_generate_matches_hf",
    "test_paged_matches_dense_v1[overrides4]",
    # round-4 deep engine-level compositions (ops-level parity for the same
    # features stays default: sparse kernel tests, ring-alibi parity,
    # gpt_neox parallel / gptj / bloom logits parity, megatron split/merge +
    # TP-semantics tests)
    "test_sparse_attention_model_trains",
    "test_alibi_model_under_sp_matches_dp",
    "test_codegen_ingestion_logits_parity",
    "test_gpt_neox_sequential_residual_parity",
    "test_megatron_load_convert_logits_consistent",
    "test_pipelined_alibi_embed_norm_matches_plain",
    # sibling-covered variants (the kept sibling is named): opt keeps [relu],
    # qwen2's qkv-bias is covered by gpt2+llama, phi's partial rotary by
    # gptj, the contiguous ring-alibi by the zigzag [64] case
    "test_opt_ingestion_logits_parity[gelu",
    "test_qwen2_ingestion_logits_parity",
    "test_phi_ingestion_logits_parity",
    "test_ring_attention_alibi_matches_dense[52]",
    # ---- tranche 3 (trim to the 550 s budget; measured 570 s cold) ----
    "test_zpp_comm_bytes_reduced",            # zpp config/validation tests stay
    "test_schedule_executor_matches_sequential[2-4]",  # other params stay
    "test_ring_attention_jits_in_train_context",  # zigzag unit tests stay
    "test_paged_pallas_gqa_grouping",         # paged parity params stay
    # ---- tranche 4 (round 5): engine-level trajectory/composition variants;
    # default keeps each feature's canonical proof — FPDT: attention fwd+grad
    # parity + model parity (+ the nightly memory contract); sparse grads:
    # grad-equals-take + manual-scale regression + the HLO comm-pattern
    # assertion; LoCo: the EF property test; zpp x ulysses is also covered by
    # multichip dryrun D every round ----
    "test_k_splits_matches_unsplit[4-16-16]",  # splits=2 squashed-grid case stays (see tranche 6)
    "test_fpdt_engine_sp2_trajectory",
    "test_engine_sparse_gradients_trajectory",
    "test_sparse_gradients_compose_with_zeropp",
    "test_loco_trajectory_close_to_exact",
    "test_zpp_composes_with_ulysses_sp",
    # ---- tranche 5 (round 5: the default tier hit 735 s cold after the
    # round-5 features landed; the moves below are sibling-covered kernel
    # param variants + duplicate compositions, never a feature's only proof.
    # Kept defaults named per line) ----
    "test_fpdt_model_host_offload_parity",     # fpdt_model_parity stays
    # k_splits: [2-16-16] (squashed triangle grid — the PRODUCTION branch,
    # block_q == block_k) stays default; the dense-grid [2-16-8] moves
    # (dense grid + mask + bwd already default via masked_grads[16-8])
    "test_k_splits_matches_unsplit[2-16-8]",
    "test_pallas_sparse_matches_dense_masked[fixed-kw1]",    # local/variable/bslongformer stay
    "test_pallas_sparse_matches_dense_masked[bigbird-kw2]",
    "TestFlashAttention::test_forward_matches_xla[False-16]",  # ragged -100 pair stays
    "TestFlashAttention::test_forward_matches_xla[True-16]",
    "TestFlashAttention::test_padding_mask",   # masked_grads[16-8] (fwd+bwd) stays
    "test_paged_pallas_matches_xla[2-",        # [1] (MQA) and [8] stay... [8] moved too: gqa covered by alibi[2-8]
    "test_paged_pallas_matches_xla[8-",
    "test_paged_pallas_alibi_matches_xla[8-8]",  # [2-8] stays
    "test_paged_pallas_alibi_matches_xla[2-2]",
    "TestFlashAlibi::test_forward_matches_xla[16-8]",  # [8-8] stays
    "test_pipeline_module_matches_pp1[4]",     # [2] stays
    "test_zero_inference_offload_generate",    # composes_with_woq + nvme tests stay
    "test_sampling_shapes_and_determinism",    # eos + cached_decode[overrides0] stay
    "test_attention_pair_bias_and_alibi",      # evoformer_attention test stays
    "test_fpdt_attention_noncausal_parity",    # causal+alibi combos stay
    # the venv pip-install trio (20 s module fixture); the metadata
    # entry-point check stays default
    "test_editable_install_exposes_all_cli_entry_points",
    "test_ds_elastic_runs_outside_checkout",
    "test_dstpu_help_runs_outside_checkout",
    # ---- tranche 6 (round 5, second pass to the <550 s budget; kept
    # default sibling named per move) ----
    "test_sparse_composes_with_alibi_and_padding",  # model-level sparse x alibi x padding stays
    "test_safe_optimizer_state_roundtrip",     # fragment get_full_grad + get_set_fp32 stay
    "test_nvme_ram_budget_is_num_buffers_layers",  # nvme_generate_matches_resident stays
    "test_sparse_lookup_grad_scale_inside_manual_shard_map",  # comm_pattern + grad_equals_take stay
    "test_fpdt_chunk_major_zero_copy_layout",  # fpdt_longer_than_typical_hbm_tile stays
    "test_chunked_attention_non_causal_and_offset",  # chunked_attention_alibi + ring tests stay
    "test_zero_inference_composes_with_woq",   # woq_stacked + nvme_generate stay
    "TestMoE::test_top1_gating",               # gating_capacity_and_aux + moe_trains stay
    "test_pipeline_module_interleaved_matches_pp1",  # interleaved_pipeline_gradients stays
    "test_interleaved_pipeline_matches_sequential",  # ditto (gradients subsumes forward)
    "test_spmd_pipeline_matches_sequential",   # spmd_pipeline_gradients stays
    "test_deepspeed_io_curriculum_filters_batches",  # curriculum scheduler unit tests stay
    "TestUlysses::test_distributed_attention_class",  # sp_matches_dp_baseline stays
    "TestFlashAlibi::test_masked_forward_matches_xla",  # alibi fwd[8-8] + grads[False-8-8] + masked_grads stay
    "test_fused_ce_pad_mask_and_uneven_chunks",  # fused_ce_matches_naive stays
    "test_gpt_bigcode_ingestion_logits_parity[False]",  # MQA [True] variant stays
    "test_woq_stacked_layers_survive_scan",    # r4-bug regression; woq pytree + zero-inference woq composition stay
    "test_safe_get_set_fp32_param_across_shards",  # fragment get_full_grad + tiled_linear stay
    # build_hf_engine is 4-line glue over load_hf_checkpoint (13 family
    # parity tests) + InferenceEngineV2 (continuous-batching parity suite);
    # its engine-compile cost stays out of the default tier
    "test_build_hf_engine_v2_from_checkpoint",
    # Twin-Flow: structure + nvme-reject + fragment-visibility stay default;
    # the two-engine trajectory comparisons are the nightly depth
    "test_twin_flow_trajectory_matches_fused",
    "test_twin_flow_fp16_dynamic_scale_matches_fused",
    "test_v2_moe_generate_matches_v1",  # v1 moe_inference_forward + ragged-prefill parity stay the cheaper anchors
    "test_offload_bf16_grad_transfer_close_to_fp32",  # default keeps bf16_grad_accum_dtype_knob (fused path)
]


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(s in item.nodeid for s in NIGHTLY_NODE_SUBSTRINGS):
            item.add_marker(pytest.mark.nightly)
    # Default-tier deselection. Done here instead of addopts so that
    # (a) an explicit -m expression takes full control, and (b) running a
    # specific node-id (`pytest tests/...::test_x`) executes it even if it
    # is nightly — addopts would silently report "no tests collected".
    if config.option.markexpr:
        return
    if any("::" in str(a) for a in config.args):
        return
    kept = [i for i in items if i.get_closest_marker("nightly") is None]
    deselected = [i for i in items if i.get_closest_marker("nightly") is not None]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = kept


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _perf_ledger_in_tmp(tmp_path_factory, monkeypatch):
    """Tests never write into tracked files: perf-ledger rows (the sweep and
    the emitters append to ``$DSTPU_PERF_LEDGER_DIR``, default
    ``<repo>/perf/ledger``) land in a per-test temporary directory."""
    monkeypatch.setenv("DSTPU_PERF_LEDGER_DIR",
                       str(tmp_path_factory.mktemp("perf_ledger")))


@pytest.fixture(autouse=True)
def _clear_mesh_state():
    yield
    from deepspeed_tpu.topology import mesh as mesh_mod

    mesh_mod._ACTIVE_MESH = None
