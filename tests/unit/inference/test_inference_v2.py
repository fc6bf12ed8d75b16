"""FastGen-analog engine correctness.

Baselines mirror the reference v2 test suite (tests/unit/inference/v2/):
allocator/state-manager unit behavior, and end-to-end parity of the paged
ragged path against the dense v1 KV-cache path (itself proven against the
training forward in test_inference_v1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, init_inference
from deepspeed_tpu.inference.ragged import BlockedAllocator, StateManager, build_ragged_batch
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig


def make_model(seed=0, **overrides):
    base = dict(
        vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=128,
    )
    base.update(overrides)
    cfg = TransformerConfig(**base)
    module = CausalLM(cfg)
    rng = jax.random.PRNGKey(seed)
    params = module.init({"params": rng, "dropout": rng},
                         {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    return cfg, module, params


# ----------------------------------------------------------- host-side units
def test_blocked_allocator():
    a = BlockedAllocator(4)
    got = a.allocate(3)
    assert len(set(got)) == 3 and a.free_blocks == 1
    with pytest.raises(RuntimeError):
        a.allocate(2)
    a.free(got[:2])
    assert a.free_blocks == 3
    with pytest.raises(ValueError):
        a.free([got[0]])  # double free


def test_state_manager_admission_and_flush():
    m = StateManager(num_blocks=4, block_size=8, max_seqs=2)
    assert m.can_schedule([1], [30])  # 30 tokens -> 4 blocks
    assert not m.can_schedule([1], [33])  # 5 blocks > 4
    m.extend(1, 30)
    assert m.free_blocks == 0
    assert not m.can_schedule([2], [1])
    m.get(1).seen_tokens = 30
    m.flush(1)
    assert m.free_blocks == 4 and m.get(1) is None
    # max_seqs cap
    m.extend(2, 1)
    m.extend(3, 1)
    assert not m.can_schedule([4], [1])


def test_build_ragged_batch_shapes():
    m = StateManager(num_blocks=16, block_size=4, max_seqs=8)
    b = build_ragged_batch(m, [7, 9], [np.arange(5), np.arange(1)],
                           max_pages=8, row_bucket=4, chunk_bucket=8)
    assert b.tokens.shape == (4, 8) and b.new_lens.tolist() == [5, 1, 0, 0]
    assert (b.positions[0, :5] == np.arange(5)).all()
    # second put for uid 7 continues positions from seen_tokens
    m.get(7).seen_tokens = 5
    b2 = build_ragged_batch(m, [7], [np.arange(1)], max_pages=8)
    assert b2.positions[0, 0] == 5


# ----------------------------------------------------------- device parity
@pytest.mark.parametrize("overrides", [
    {},
    {"norm": "layernorm", "activation": "gelu_exact", "num_kv_heads": 1,
     "qkv_bias": False, "dense_bias": False, "parallel_block": True,
     "tie_embeddings": True},  # falcon-style: parallel block through ragged
    {"norm": "layernorm", "activation": "gelu", "position": "alibi",
     "embed_norm": True, "tie_embeddings": True},  # bloom-style: alibi + embed norm
    {"norm": "layernorm", "activation": "gelu_exact", "parallel_block": True,
     "parallel_mlp_norm": True, "rotary_dim": 4},  # gpt-neox-style parallel ln2
    {"norm": "layernorm", "activation": "gelu", "parallel_block": True,
     "rotary_dim": 4, "rope_interleaved": True, "qkv_bias": False,
     "dense_bias": False, "mlp_bias": True},  # gpt-j-style interleaved rotary
])
def test_paged_matches_dense_v1(overrides):
    """Staggered prefill+decance through v2 == per-prompt v1 greedy decode."""
    cfg, module, params = make_model(**overrides)
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32", "kv_block_size": 4,
                                          "num_kv_blocks": 64, "chunk_bucket": 8})
    v1 = init_inference(model=cfg, params=params, config={"dtype": "fp32", "seq_bucket": 8})

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (7, 3, 5)]
    outs = eng.generate(prompts, max_new_tokens=6)

    for prompt, out in zip(prompts, outs):
        ref = v1.generate(prompt[None, :], max_new_tokens=6)[0, len(prompt):]
        np.testing.assert_array_equal(out, ref)


def test_put_query_flush_api():
    cfg, _, params = make_model()
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32", "kv_block_size": 4,
                                          "num_kv_blocks": 16, "max_seqs": 4})
    assert eng.can_schedule([0], [10])
    logits = eng.put([0], [np.arange(10) % cfg.vocab_size])
    assert logits.shape == (1, cfg.vocab_size)
    seen, free = eng.query(0)
    assert seen == 10
    logits2 = eng.put([0], [[3]])
    assert eng.query(0)[0] == 11
    eng.flush(0)
    assert eng.query(0)[0] == 0 and eng.query(0)[1] == 16 * 4


def test_kv_exhaustion_raises():
    cfg, _, params = make_model()
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32", "kv_block_size": 4,
                                          "num_kv_blocks": 2, "max_seqs": 4})
    with pytest.raises(RuntimeError):
        eng.put([0], [np.zeros(9, np.int32)])  # needs 3 blocks, only 2 exist


def test_continuous_batching_interleaves():
    """Sequences of very different lengths share the pool; late arrivals are
    admitted as blocks free up (tiny pool forces queueing)."""
    cfg, module, params = make_model()
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32", "kv_block_size": 4,
                                          "num_kv_blocks": 12, "max_seqs": 2})
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (6, 6, 6)]
    outs = eng.generate(prompts, max_new_tokens=4)
    v1 = init_inference(model=cfg, params=params, config={"dtype": "fp32", "seq_bucket": 8})
    for prompt, out in zip(prompts, outs):
        ref = v1.generate(prompt[None, :], max_new_tokens=4)[0, len(prompt):]
        np.testing.assert_array_equal(out, ref)


def test_preemption_under_kv_pressure():
    """Pool sized so concurrent decode overflows mid-generation: the youngest
    sequence must be preempted and re-prefilled, and final outputs still match
    the dense v1 baseline."""
    cfg, module, params = make_model()
    # 6 blocks x 4 slots = 24 KV slots; two 8-token prompts + 8 new tokens
    # each = 32 slots needed at peak -> forced preemption
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32", "kv_block_size": 4,
                                          "num_kv_blocks": 6, "max_seqs": 4})
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (8,)) for _ in range(2)]
    outs = eng.generate(prompts, max_new_tokens=8)
    v1 = init_inference(model=cfg, params=params, config={"dtype": "fp32", "seq_bucket": 8})
    for prompt, out in zip(prompts, outs):
        ref = v1.generate(prompt[None, :], max_new_tokens=8)[0, len(prompt):]
        np.testing.assert_array_equal(out, ref)
    # everything released at the end
    assert eng.state.free_blocks == 6


def test_v2_moe_generate_matches_v1():
    """The ragged v2 engine serves MoE models (FastGen serves Mixtral): the
    paged forward routes each layer through the expert mixer, and greedy
    output matches the dense v1 engine on the same params."""
    cfg, _, params = make_model(num_experts=4, moe_top_k=2)
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32", "kv_block_size": 4,
                                          "num_kv_blocks": 64})
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (6, 9)]
    outs = eng.generate(prompts, max_new_tokens=5)
    v1 = init_inference(model=cfg, params=params, config={"dtype": "fp32", "seq_bucket": 16})
    for prompt, out in zip(prompts, outs):
        ref = v1.generate(prompt[None, :], max_new_tokens=5)[0, len(prompt):]
        np.testing.assert_array_equal(out, ref)


def test_generate_rejects_overlong():
    cfg, _, params = make_model()
    eng = InferenceEngineV2(cfg, params, {"dtype": "fp32", "kv_block_size": 4,
                                          "num_kv_blocks": 64, "max_seq_len": 16})
    with pytest.raises(ValueError):
        eng.generate([np.zeros(12, np.int32)], max_new_tokens=8)


# ------------------------------------------------- expert-parallel serving
def test_v2_expert_parallel_decode_identical():
    """Acceptance (ISSUE 15): an ep>1 v2 engine serves greedy decode
    TOKEN-IDENTICAL to the ep=1 engine on the same checkpoint (bf16), with
    expert weights actually sharded over ep and the MoE dispatch/combine
    routed through the collective all_to_all path."""
    import deepspeed_tpu.parallel.moe as pmoe

    cfg, _, params = make_model(num_experts=4, moe_top_k=2)
    base = {"dtype": "bf16", "kv_block_size": 4, "num_kv_blocks": 64}
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (6, 9, 4)]
    ref = InferenceEngineV2(cfg, params, dict(base)).generate(
        prompts, max_new_tokens=8)
    calls = []
    orig = pmoe.collective_moe_apply
    try:
        pmoe.collective_moe_apply = lambda *a, **k: (calls.append(1),
                                                     orig(*a, **k))[1]
        ep_eng = InferenceEngineV2(cfg, params, dict(base, ep_size=2))
        outs = ep_eng.generate(prompts, max_new_tokens=8)
    finally:
        pmoe.collective_moe_apply = orig
    assert calls, "ep>1 engine did not trace the collective dispatch"
    assert ep_eng.mesh.shape["ep"] == 2
    w = ep_eng.params["layers"]["moe"]["experts"]["w_up"]
    assert "ep" in str(w.sharding.spec), w.sharding.spec
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)


def test_v2_expert_parallel_through_unchanged_router():
    """The serving tier is oblivious to expert parallelism: ep-sharded
    replicas serve through the STOCK ServingRouter with greedy output
    matching a single ep=1 engine."""
    from deepspeed_tpu.inference import ServingRouter

    cfg, _, params = make_model(num_experts=4, moe_top_k=2)
    base = {"dtype": "bf16", "kv_block_size": 4, "num_kv_blocks": 64}
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (7, 3, 5, 6)]
    ref = InferenceEngineV2(cfg, params, dict(base)).generate(
        prompts, max_new_tokens=6)
    router = ServingRouter.build(cfg, params, dict(base, ep_size=2),
                                 replicas=2)
    outs = router.serve(prompts, max_new_tokens=6)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    assert all(d > 0 for d in router.stats()["dispatches"])


def test_v2_ep_size_validation():
    cfg, _, params = make_model(num_experts=4, moe_top_k=2)
    dense_cfg, _, dense_params = make_model()
    with pytest.raises(ValueError, match="not divisible"):
        InferenceEngineV2(cfg, params, {"ep_size": 3, "kv_block_size": 4,
                                        "num_kv_blocks": 16})
    with pytest.raises(ValueError, match="dense model"):
        InferenceEngineV2(dense_cfg, dense_params,
                          {"ep_size": 2, "kv_block_size": 4,
                           "num_kv_blocks": 16})
