"""The cache plan (``inference/cache.py``) against what the engine of PR 58's
commit sized, built and refused BEFORE the plan existed: ``data/
cache_plan_at_pr58.json`` was written by that commit's engine (a toy of every
kind of cache under the engine configuration beside it: bytes a token, the
ring's bytes, the pre-flight guard's ``need``, the ``log_dist`` line, the
pools' shapes, ``stats()``; and every row of the five refusal tables, asked
alone and all of a kind at once, with the migration's). Nothing here runs a
model: parameters are zeros of the right shapes."""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.checkpoint.hf import config_from_hf
from deepspeed_tpu.inference import cache, engine_v2
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2, RaggedInferenceConfig
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.utils import hbm

from .test_latent_routed import GPT_NEOX

with open(os.path.join(os.path.dirname(__file__), "data", "cache_plan_at_pr58.json")) as f:
    RECORDED = json.load(f)
MODULES = {"glm4_moe_lite": "test_latent_routed", "granitemoehybrid": "test_hybrid", "qwen3_next": "test_qwen3_next",
           "evabyte": "test_eva", "cohere2_moe": "test_cohere2_moe", "glm_moe_dsa": "test_glm_moe_dsa",
           "xing4_0": "test_xing"}
REFUSAL_BASE = dict(dtype="fp32", max_seqs=8, kv_block_size=16, num_kv_blocks=64, row_bucket=4, chunk_bucket=32,
                    max_seq_len=128, hbm_check="off")
REFUSAL_BLOCKS = {"evabyte": 4, "cohere2_moe": 8, "granitemoehybrid": 4, "glm_moe_dsa": 8}


def published(toy):
    name = toy.split("-")[0]
    return GPT_NEOX if name == "gpt_neox" else importlib.import_module(f"tests.unit.inference.{MODULES[name]}").TOY


def zeros_for(cfg):
    return jax.tree_util.tree_map(lambda s: np.zeros(s.shape, s.dtype), jax.eval_shape(
        lambda k: CausalLM(cfg).init({"params": k}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"],
        jax.random.PRNGKey(0)))


def shapes(tree):
    return [[list(a.shape), str(a.dtype)] for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("toy", sorted(RECORDED["sizes"]))
def test_the_plan_s_bytes_and_pools_are_the_parent_engine_s(toy):
    """From the model's config alone: a token's bytes, the ring's, the table's width and the pools' shapes."""
    want = RECORDED["sizes"][toy]
    conf = RaggedInferenceConfig(**want["engine"])
    plan = cache.cache_plan(config_from_hf(published(toy)), conf.kv_block_size, conf.max_seq_len)
    assert plan.bytes_per_token(conf.kv_jax_dtype, conf.kv_quant) == want["kv_bytes_per_token"]
    ring_blocks = conf.max_seqs * plan.ring_columns
    assert (ring_blocks, plan.ring_bytes(ring_blocks, conf.kv_jax_dtype)) == (want["ring_blocks"], want["ring_bytes"])
    assert plan.max_pages == want["max_pages"]
    pools = jax.eval_shape(lambda: plan.init(want["num_kv_blocks"], ring_blocks, conf.max_seqs, conf.kv_jax_dtype,
                                             kv_quant=conf.kv_quant, state_dtype=conf.jax_dtype))
    assert shapes(pools) == want["pools"]
    assert (pools.state is None) == (plan.state is None) and (pools.ring is None) == (plan.ring is None)


@pytest.mark.parametrize("toy", sorted(RECORDED["sizes"]))
def test_the_engine_s_need_and_its_log_line_are_the_parent_s(toy, monkeypatch):
    """Through the engine: the blocks a byte budget buys, the HBM guard's ``need``, the line it logs, ``stats()``."""
    want, seen = RECORDED["sizes"][toy], {}
    monkeypatch.setattr(engine_v2, "log_dist", lambda line, **kw: seen.setdefault("lines", []).append(line))
    monkeypatch.setattr(hbm, "check_hbm_fit", lambda need, **kw: seen.__setitem__("need", int(need)))
    cfg = config_from_hf(published(toy))
    eng = InferenceEngineV2(cfg, zeros_for(cfg), dict(want["engine"], hbm_check="warn"))
    assert (eng.num_kv_blocks, seen["need"]) == (want["num_kv_blocks"], want["need"])
    mesh = re.compile(r"mesh=\{[^}]*\}")  # (as many devices as the process has: not the cache's to say)
    assert [mesh.sub("", line) for line in seen["lines"] if line.startswith("InferenceEngineV2")] == [
        mesh.sub("", want["line"])]
    assert shapes(eng.pools) == want["pools"] and eng.stats() == want["stats"]
    assert eng.pool is eng.pools.kv  # what the benchmark's runner and the router read


def _refusal_id(case):
    asked = case.get("migration") or ",".join(f"{k}={v}" for k, v in {**case["engine"], **case["model"]}.items())
    return f"{case['toy']}-{asked}"


@pytest.mark.parametrize("case", RECORDED["refusals"], ids=_refusal_id)
def test_every_refusal_is_the_parent_s_word_for_word(case):
    cfg = config_from_hf(dict(published(case["toy"]), **case.get("model", {})))
    conf = {**REFUSAL_BASE, "kv_block_size": REFUSAL_BLOCKS.get(case["toy"], 16), **case.get("engine", {})}
    if "migration" not in case:  # refused before the parameters are looked at
        with pytest.raises(ValueError) as said:
            InferenceEngineV2(cfg, None, conf)
        assert str(said.value) == case["said"]
        return
    eng = InferenceEngineV2(cfg, zeros_for(cfg), conf)
    refused = eng.plan.migration_refusal(importing=case["migration"] == "import")
    assert refused == case["said"]  # (None: an import into an EVA model or a sliding kind is not refused by name)
    if refused is not None:
        with pytest.raises(ValueError) as said:
            eng.export_request(0) if case["migration"] == "export" else eng.import_request(0, {})
        assert str(said.value) == refused


def test_a_plan_names_its_classes_its_layout_and_its_state():
    """What the plan says of each kind, in the words the modules beside it read."""
    plans = {toy: cache.cache_plan(config_from_hf(published(toy)), 8, 128) for toy in ["gpt_neox"] + sorted(MODULES)}
    assert {toy: plan.kind for toy, plan in plans.items()} == {
        "gpt_neox": "plain", "glm4_moe_lite": "latent", "granitemoehybrid": "plain", "qwen3_next": "plain",
        "evabyte": "eva", "cohere2_moe": "windowed", "glm_moe_dsa": "indexed", "xing4_0": "latent"}
    assert {toy: plan.state for toy, plan in plans.items() if plan.state} == {
        "granitemoehybrid": "mamba", "qwen3_next": "linear_attention"}
    assert [type(plans[toy].layout) for toy in ("gpt_neox", "evabyte", "cohere2_moe")] == [
        cache.PlainLayout, cache.WindowLayout, cache.RingLayout]
    assert plans["gpt_neox"].layout.pages(5, 12) == (3, 0) and plans["gpt_neox"].layout.width == 16
    assert [c.name for c in plans["cohere2_moe"].classes] == ["kv", "ring"] and plans["cohere2_moe"].layout.classes == (0, 1)
    # a PagedKVPool's ``v`` is index keys under an indexer: said by the class, nowhere else
    assert [plans[toy].classes[0].second_holds for toy in ("gpt_neox", "glm4_moe_lite", "glm_moe_dsa")] == [
        "values", "", "index keys"]
    assert [plans[toy].classes[0].quantized for toy in ("gpt_neox", "evabyte", "glm4_moe_lite")] == [True, False, False]
    assert all(set(cache.Pools._fields) >= {c.name for c in plan.classes} for plan in plans.values())
