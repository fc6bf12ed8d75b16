"""HF checkpoint ingestion (reference module_inject/load_checkpoint.py +
inference/v2/engine_factory.py): safetensors -> param pytree -> engines.

Ground truth is the transformers implementation itself: a tiny random HF model
is saved with save_pretrained, ingested, and must reproduce the HF logits.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.checkpoint.hf import (
    config_from_hf,
    convert_hf_state,
    detect_family,
    load_hf_checkpoint,
)
from deepspeed_tpu.models import CausalLM

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


def _save_tiny_llama(tmp_path, tie=False, moe=False):
    if moe:
        cfg = transformers.MixtralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
            num_local_experts=4, num_experts_per_tok=2,
            tie_word_embeddings=tie,
        )
        model = transformers.MixtralForCausalLM(cfg)
    else:
        cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
            tie_word_embeddings=tie,
        )
        model = transformers.LlamaForCausalLM(cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    return model


def test_llama_ingestion_logits_parity(tmp_path):
    hf_model = _save_tiny_llama(tmp_path)
    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.norm == "rmsnorm" and cfg.num_kv_heads == 2

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_qwen2_ingestion_logits_parity(tmp_path):
    """qwen2 = llama graph + qkv biases; family auto-detected from bias keys."""
    cfg_hf = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    hf_model = transformers.Qwen2ForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.qkv_bias is True
    assert "bias" in params["layers"]["attn"]["wq"]

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("act,want_act", [("relu", "relu"), ("gelu", "gelu_exact")])
def test_opt_ingestion_logits_parity(tmp_path, act, want_act):
    """OPT: layernorm + relu/exact-gelu + learned positions (offset-2 rows)."""
    cfg_hf = transformers.OPTConfig(
        vocab_size=128, hidden_size=32, ffn_dim=64, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        word_embed_proj_dim=32, activation_function=act,
    )
    hf_model = transformers.OPTForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.activation == want_act and cfg.position == "learned"
    assert params["pos_embed"].shape == (64, 32)  # offset rows stripped

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_falcon_ingestion_logits_parity(tmp_path):
    """Falcon-7B style: parallel attn+MLP block, MQA, fused qkv, bias-free."""
    cfg_hf = transformers.FalconConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, new_decoder_architecture=False,
        multi_query=True, parallel_attn=True, bias=False, alibi=False,
        max_position_embeddings=64,
    )
    hf_model = transformers.FalconForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.parallel_block and cfg.kv_heads == 1 and cfg.dense_bias is False
    assert "mlp_norm" not in params["layers"]

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_phi_ingestion_logits_parity(tmp_path):
    """Phi: parallel block + partial rotary + biased head and projections."""
    cfg_hf = transformers.PhiConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, partial_rotary_factor=0.5,
    )
    hf_model = transformers.PhiForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.parallel_block and cfg.rotary_dim == 4 and cfg.lm_head_bias
    assert "bias" in params["lm_head"]

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_gpt2_ingestion_logits_parity(tmp_path):
    cfg_hf = transformers.GPT2Config(
        vocab_size=96, n_embd=32, n_layer=2, n_head=4, n_positions=64)
    hf_model = transformers.GPT2LMHeadModel(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.norm == "layernorm" and cfg.tie_embeddings

    ids = np.random.default_rng(1).integers(0, 96, (2, 10))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()
    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("multi_query", [True, False])
def test_gpt_bigcode_ingestion_logits_parity(tmp_path, multi_query):
    """starcoder/santacoder-style (round 5; reference module_inject bigcode
    containers): Linear-oriented c_attn, one shared KV head when multi_query.
    The MHA variant pins the [3h, h]-vs-[h, 3h] family detection."""
    cfg_hf = transformers.GPTBigCodeConfig(
        vocab_size=96, n_embd=32, n_layer=2, n_head=4, n_positions=64,
        multi_query=multi_query, activation_function="gelu_pytorch_tanh")
    hf_model = transformers.GPTBigCodeForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.kv_heads == (1 if multi_query else 4)
    assert cfg.norm == "layernorm" and cfg.tie_embeddings

    ids = np.random.default_rng(3).integers(0, 96, (2, 10))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()
    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_mixtral_ingestion_structure(tmp_path):
    """Mixtral converts to the exact tree the in-repo MoE CausalLM expects
    (logits parity is not pinned: HF routes without capacity dropping)."""
    _save_tiny_llama(tmp_path, moe=True)
    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.num_experts == 4

    module = CausalLM(cfg)
    batch = {"input_ids": jnp.zeros((2, 8), jnp.int32)}
    want = jax.eval_shape(
        lambda: module.init({"params": jax.random.PRNGKey(0)}, batch, train=False)["params"])
    got = jax.tree_util.tree_map(jnp.asarray, params)
    want_flat = jax.tree_util.tree_flatten_with_path(want)[0]
    got_flat = {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(got)[0]}
    for k, leaf in want_flat:
        ks = jax.tree_util.keystr(k)
        assert ks in got_flat, f"missing {ks}"
        assert got_flat[ks].shape == leaf.shape, f"{ks}: {got_flat[ks].shape} != {leaf.shape}"
    # and it runs
    loss, _ = module.apply({"params": got}, {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (2, 8)), jnp.int32)}, train=False)
    assert np.isfinite(float(loss))


def test_init_inference_tp2_from_hf(tmp_path, devices):
    """VERDICT round-2 'done' bar: tiny llama safetensors -> init_inference
    (tp=2) on the CPU mesh -> generate."""
    import deepspeed_tpu

    _save_tiny_llama(tmp_path)
    cfg, params = load_hf_checkpoint(str(tmp_path))
    engine = deepspeed_tpu.init_inference(
        cfg, config={"tensor_parallel": {"tp_size": 2}, "dtype": "float32", "seq_bucket": 8},
        params=params)
    out = engine.generate(np.asarray([[5, 6, 7]]), max_new_tokens=4, do_sample=False)
    assert out.shape == (1, 7)


def test_build_hf_engine_v2_from_checkpoint(tmp_path):
    """One-call HF dir -> v2 continuous-batching engine (reference
    ``inference/v2/engine_factory.py:69 build_hf_engine``); greedy output
    matches the v1 engine on the same checkpoint."""
    import deepspeed_tpu

    _save_tiny_llama(tmp_path)
    eng = deepspeed_tpu.build_hf_engine(
        str(tmp_path), {"dtype": "fp32", "kv_block_size": 4, "num_kv_blocks": 32})
    prompt = np.asarray([5, 6, 7], dtype=np.int32)
    out = eng.generate([prompt], max_new_tokens=4)[0]
    assert out.shape == (4,) and out.dtype == np.int32
    # v2-output-vs-v1 parity itself is pinned by
    # test_continuous_batching_interleaves; this test owns the factory glue:
    # config ingestion produced a generatable engine with clean bookkeeping
    assert len(eng.state._seqs) == 0


def test_initialize_training_from_hf(tmp_path, devices):
    """HF params feed initialize(model_parameters=...) and train."""
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm_spec

    _save_tiny_llama(tmp_path)
    cfg, params = load_hf_checkpoint(str(tmp_path))
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=8),
        model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2}, "steps_per_print": 100},
    )
    batch = {"input_ids": np.random.default_rng(0).integers(0, 128, (8, 8), dtype=np.int32)}
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_detect_family():
    assert detect_family({"model.layers.0.self_attn.q_proj.weight": 0}) == "llama"
    # c_attn orientation separates gpt2 (Conv1D [in, 3in]) from gpt_bigcode
    # ([out, in] Linear; out = 3in for MHA, in + 2*head_dim for MQA)
    assert detect_family({"h.0.attn.c_attn.weight": np.zeros((8, 24))}) == "gpt2"
    assert detect_family({"h.0.attn.c_attn.weight": np.zeros((24, 8))}) == "gpt_bigcode"
    assert detect_family({"h.0.attn.c_attn.weight": np.zeros((12, 8))}) == "gpt_bigcode"
    assert detect_family({"model.layers.0.block_sparse_moe.gate.weight": 0}) == "mixtral"
    with pytest.raises(ValueError):
        detect_family({"bogus": 0})


def test_config_from_hf_rejects_unknown():
    with pytest.raises(ValueError, match="model_type"):
        config_from_hf({"model_type": "resnet"})


def test_gpt_neox_ingestion_logits_parity(tmp_path):
    """GPT-NeoX: per-head fused QKV (fusedqkv_utils 'glmtype' ordering),
    partial rotary, parallel residual with SEPARATE mlp norm."""
    cfg_hf = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.5, rotary_emb_base=10000,
        use_parallel_residual=True, hidden_act="gelu",
        tie_word_embeddings=False,
    )
    hf_model = transformers.GPTNeoXForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.parallel_block and cfg.parallel_mlp_norm
    assert cfg.rotary_dim == 4  # 0.5 * head_dim(8)
    assert "mlp_norm" in params["layers"]

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_gpt_neox_sequential_residual_parity(tmp_path):
    cfg_hf = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=False, tie_word_embeddings=False,
    )
    hf_model = transformers.GPTNeoXForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)
    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert not cfg.parallel_block

    ids = np.random.default_rng(1).integers(0, 128, (1, 10))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()
    _, logits = CausalLM(cfg).apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_bloom_ingestion_logits_parity(tmp_path):
    """Bloom: ALiBi position biases, embedding layernorm, per-head fused QKV
    ('bloomtype' ordering), tied head."""
    cfg_hf = transformers.BloomConfig(
        vocab_size=128, hidden_size=32, n_layer=2, n_head=4,
        layer_norm_epsilon=1e-5, tie_word_embeddings=True,
    )
    hf_model = transformers.BloomForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.position == "alibi" and cfg.embed_norm and cfg.tie_embeddings
    assert "embed_norm" in params

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_bloom_generate_matches_hf(tmp_path):
    """The DECODE path's alibi (slopes * cache-slot position) must agree with
    HF greedy generation, not just teacher-forcing logits."""
    import deepspeed_tpu

    cfg_hf = transformers.BloomConfig(
        vocab_size=128, hidden_size=32, n_layer=2, n_head=4,
        tie_word_embeddings=True,
    )
    hf_model = transformers.BloomForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    eng = deepspeed_tpu.init_inference(
        cfg, params=params, config={"dtype": "float32", "seq_bucket": 8})

    ids = np.random.default_rng(0).integers(5, 128, (1, 6))
    with torch.no_grad():
        want = hf_model.generate(
            torch.tensor(ids), max_new_tokens=6, do_sample=False,
            pad_token_id=0).numpy()
    got = eng.generate(ids, max_new_tokens=6, do_sample=False)
    np.testing.assert_array_equal(got, want)


def test_gptj_ingestion_logits_parity(tmp_path):
    """GPT-J: INTERLEAVED rotary (rotate_every_two), parallel block with one
    shared ln_1, bias-free attention + biased MLP, biased untied lm_head."""
    cfg_hf = transformers.GPTJConfig(
        vocab_size=128, n_embd=32, n_layer=2, n_head=4, n_positions=64,
        rotary_dim=4, n_inner=None, activation_function="gelu_new",
        tie_word_embeddings=False,
    )
    hf_model = transformers.GPTJForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.rope_interleaved and cfg.parallel_block and not cfg.parallel_mlp_norm
    assert cfg.rotary_dim == 4 and cfg.mlp_bias and cfg.lm_head_bias
    assert "bias" not in params["layers"]["attn"]["wq"]
    assert "bias" in params["layers"]["mlp"]["w_up"]

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)


def test_gptj_generate_matches_hf(tmp_path):
    """Decode path with interleaved partial rotary must agree with HF greedy."""
    import deepspeed_tpu

    cfg_hf = transformers.GPTJConfig(
        vocab_size=128, n_embd=32, n_layer=2, n_head=4, n_positions=64,
        rotary_dim=4, tie_word_embeddings=False)
    hf_model = transformers.GPTJForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    eng = deepspeed_tpu.init_inference(
        cfg, params=params, config={"dtype": "float32", "seq_bucket": 8})
    ids = np.random.default_rng(1).integers(5, 128, (1, 6))
    with torch.no_grad():
        want = hf_model.generate(torch.tensor(ids), max_new_tokens=6,
                                 do_sample=False, pad_token_id=0).numpy()
    got = eng.generate(ids, max_new_tokens=6, do_sample=False)
    np.testing.assert_array_equal(got, want)


def test_codegen_ingestion_logits_parity(tmp_path):
    """CodeGen: gpt-j graph + the mp_num-blocked fused QKV (reference
    fusedqkv_utils 'codegentype' — q|V|K order inside each of 4 groups)."""
    # n_head=8 > mp_num=4: TWO heads per mp group, so the blocked layout is
    # exercised in its non-degenerate form (intra-group head ordering)
    cfg_hf = transformers.CodeGenConfig(
        vocab_size=128, n_embd=32, n_layer=2, n_head=8, n_positions=64,
        rotary_dim=2, activation_function="gelu_new",
        tie_word_embeddings=False,
    )
    hf_model = transformers.CodeGenForCausalLM(cfg_hf)
    hf_model.eval()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = load_hf_checkpoint(str(tmp_path))
    assert cfg.rope_interleaved and cfg.parallel_block and cfg.mlp_bias

    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with torch.no_grad():
        want = hf_model(torch.tensor(ids)).logits.numpy()

    module = CausalLM(cfg)
    _, logits = module.apply(
        {"params": jax.tree_util.tree_map(jnp.asarray, params)},
        {"input_ids": jnp.asarray(ids, jnp.int32)}, train=False)
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-3, atol=2e-4)
