"""The sub-layer names in the compiled programs (since PR 37): inside a layer
the serving path names each piece by the parameter key it reads
(``models/transformer.py::reading``), which is the name flax gives the module
that owns it in training; the train step besides names the layer scan, the
micro-batch accumulator and the gradients' norm. Read from the ``op_name``
metadata of toy programs compiled on the CPU: the ``step`` and ``chain`` of a
``gpt_neox``, a ``glm4_moe_lite`` and an ``evabyte`` toy (the toys of
``test_latent_routed.py`` and ``test_eva.py``) and a toy train step. A scope
is metadata: that it adds no primitive is what ``test_latent_routed.py -k
parents`` holds against the censuses recorded at PR 36."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.lib import scopes, sublayers
from deepspeed_tpu.checkpoint.hf import config_from_hf
from deepspeed_tpu.inference import cache, paged
from deepspeed_tpu.models import CausalLM, causal_lm_spec
from tests.unit.inference.test_eva import TOY as EVA_TOY
from tests.unit.inference.test_latent_routed import TOY as GLM_TOY

NEOX_TOY = dict(
    model_type="gpt_neox", vocab_size=256, hidden_size=64, intermediate_size=256, num_hidden_layers=2,
    num_attention_heads=4, max_position_embeddings=128, rotary_pct=0.25, rotary_emb_base=10000,
    layer_norm_eps=1e-5, use_parallel_residual=True, hidden_act="gelu", tie_word_embeddings=False)
# (published toy, page size, table columns, prefill chunk, keywords of the two programs)
TOYS = {"gpt_neox": (NEOX_TOY, 16, 8, 32, {}),
        "glm4_moe_lite": (GLM_TOY, 16, 8, 32, {"with_picks": True}),
        "evabyte": (EVA_TOY, 4, 2 * 2 + 8, 40, {})}  # two closed windows' summary pages + an open window's
# names that are not parameter keys: the rotary embedding, and the train step's three
NOT_KEYS = {"rope", "layer_scan", "grad_accum", "grad_norm"}
# a matrix product that reads no layer weight: attention's own (scores and values, directly
# under the attention's name), the head, the router and the routed experts, a window's summaries
OTHER_PRODUCTS = {"attn", "mla", "eva", "eva_prefill", "eva_close", "paged_attn", "lm_head", "lm_head_ce",
                  "moe_router", "moe_experts", "moe_shared"}


def op_names(text):
    """Every whole ``op_name`` of a compiled program's text (an instruction
    merged from two carries both, ``a;b``; a reduction's sub-computation
    carries a path's tail alone and is left out)."""
    return {name for joined in re.findall(r'op_name="([^"]*)"', text) for name in joined.split(";")
            if name.startswith("jit(")}


def shapes_of(cfg):
    return jax.eval_shape(lambda k: CausalLM(cfg).init(
        {"params": k}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"], jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=sorted(TOYS))
def serving(request):
    """(architecture, the parameter tree's shapes, {program: its op_names})."""
    toy, bs, cols, chunk, kw = TOYS[request.param]
    cfg = config_from_hf(toy)
    params = shapes_of(cfg)
    pool = jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, 32, bs, jnp.float32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    step = jax.jit(lambda p, pool, t, pos, n, bt: paged.ragged_forward(p, cfg, pool, t, pos, n, bt, bs, **kw)).lower(
        params, pool, i32(4, chunk), i32(4, chunk), i32(4), i32(4, cols))
    chain = jax.jit(lambda p, pool, t, pos, bt, a, b, r: paged.ragged_decode_chain(
        p, cfg, pool, t, pos, bt, bs, a, b, r, 4, None, **kw)).lower(
        params, pool, i32(4), i32(4), i32(4, cols), jax.ShapeDtypeStruct((4,), jnp.bool_), i32(4),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return request.param, params, {"step": op_names(step.compile().as_text()),
                                   "chain": op_names(chain.compile().as_text())}


@pytest.fixture(scope="module")
def train_names():
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(config_from_hf(NEOX_TOY), example_seq_len=16),
        config={"train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": 2, "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}, "zero_optimization": {"stage": 1}})
    batch = engine._shard_global_batch({"input_ids": np.zeros((engine.train_batch_size, 16), np.int32)})
    return op_names(engine._train_step.lower(engine.state, batch).compile().as_text())


def products(names):
    return [n for n in names if n.endswith("/dot_general")]


def unnamed_products(names):
    """The matrix products that no weight names and that are not one of ``OTHER_PRODUCTS``."""
    out = []
    for name in products(names):
        own = [c for c in sublayers.components(name) if c in sublayers.PROGRAM_NAMES]
        if sublayers.weight_of(name) is None and not set(own[-2:]) & OTHER_PRODUCTS:
            out.append(name)
    return out


def tree_paths(tree, prefix=()):
    """Every contiguous run of keys along a root-to-leaf path of ``tree``."""
    out = set()
    if isinstance(tree, dict):
        for key, sub in tree.items():
            below = tree_paths(sub, prefix + (key,))
            out |= below
    else:
        for i in range(len(prefix)):
            for j in range(i + 1, len(prefix) + 1):
                out.add(prefix[i:j])
    return out


@pytest.mark.parametrize("program", ["step", "chain"])
def test_every_product_of_a_serving_layer_carries_its_weight_s_name(serving, program):
    architecture, _, names = serving
    assert len(products(names[program])) >= 6
    assert not unnamed_products(names[program])
    found = {sublayers.weight_of(n) for n in products(names[program])} - {None}
    want = {"gpt_neox": {"wq", "wk", "wv", "wo", "w_up", "w_down"},
            "glm4_moe_lite": {"wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_up", "w_gate", "w_down"},
            "evabyte": {"wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down"}}[architecture]
    assert found == want


@pytest.mark.parametrize("program", ["step", "chain"])
def test_serving_names_are_the_flax_tree_s_own(serving, program):
    """What the serving path writes of the new names, in the order it nests
    them, is a run of keys of the tree ``CausalLM.init`` makes (``attn`` >
    ``wq``; ``mlp`` > ``w_down``; ``final_norm``): nothing is spelled twice."""
    _, params, names = serving
    keys = tree_paths(params)
    seen = set()
    for name in names[program]:
        run = tuple(c for c in sublayers.components(name) if c in sublayers.SUBLAYERS and c not in NOT_KEYS)
        if run:
            assert run in keys, (name, run)
            seen |= set(run)
    assert {"attn", "mlp", "attn_norm", "mlp_norm", "final_norm", "wo", "w_down"} <= seen
    assert any("rope" in sublayers.components(n) for n in names[program])
    # the scopes that were there keep their names and their nesting
    old = {"gpt_neox": {"layer", "kv_write", "pool_scan", "lm_head", "embed"},
           "glm4_moe_lite": {"layer", "kv_write", "pool_scan", "lm_head", "embed", "mla", "moe", "moe_router",
                             "moe_experts", "moe_shared"},
           "evabyte": {"layer", "kv_write", "pool_scan", "lm_head", "embed", "eva", "eva_close"}}[serving[0]]
    assert old <= {c for n in names[program] for c in sublayers.components(n)}
    nesting = [("kv_write", "layer"), ("moe_experts", "moe"), ("mla", "layer"), ("eva_close", "eva")]
    if serving[0] != "glm4_moe_lite":  # its leading dense layer is a ``layer`` before the scan
        nesting.append(("layer", "pool_scan"))
    for name in names[program]:
        parts = sublayers.components(name)
        for inner, outer in nesting:
            assert inner not in parts or outer in parts[:parts.index(inner)], name


def test_no_new_name_is_one_of_the_closed_list_s():
    """``lib/scopes.py`` attributes by the innermost of ITS names: a new name
    among them would move an accepted metric."""
    assert not set(sublayers.SUBLAYERS) & set(scopes.SCOPES + scopes.KERNELS)
    for name in sublayers.SUBLAYERS:
        assert scopes.innermost_scope(f"jit(chain)/pool_scan/while/body/layer/{name}/mul") == "layer"
        assert scopes.innermost_scope(f"jit(train_step)/jvp(CausalLM)/layers/{name}/mul") == "layers"


def test_the_train_step_names_its_scan_its_accumulator_and_its_norm(train_names):
    parts = {c for n in train_names for c in sublayers.components(n)}
    assert {"layer_scan", "grad_accum", "grad_norm", "layers", "optimizer", "lm_head_ce"} <= parts
    # the scan's own work reads layer_scan and no layers; a layer's reads both, layer_scan outside
    assert any(sublayers.is_scan_stacking(n) and "dynamic_update_slice" in n for n in train_names)
    for name in products(train_names):
        own = sublayers.components(name)
        if "layers" in own:
            assert "layer_scan" in own[:own.index("layers")], name
    assert any(n.endswith("grad_accum/add") for n in train_names)


def test_every_product_of_a_trained_layer_carries_its_weight_s_name(train_names):
    assert not unnamed_products(train_names)
    for wrapper in ("jvp(CausalLM)", "transpose(jvp(CausalLM))"):  # forward and transposed
        found = {sublayers.weight_of(n) for n in products(train_names) if wrapper + "/" in n} - {None}
        assert found == {"wq", "wk", "wv", "wo", "w_up", "w_down"}, wrapper


def remade(names):
    """The instructions of a backward that make a sub-layer's inside again
    (``jax.checkpoint`` names its second run ``rematted_computation``)."""
    return [n for n in names if "rematted_computation" in sublayers.components(n)]


def test_what_a_trained_layer_makes_again_carries_its_sub_layer_s_name(train_names):
    """Since PR 38 the norms and the activation keep their inputs alone and
    run again in the backward: those instructions read ``attn_norm``,
    ``mlp_norm`` (``final_norm``) and ``mlp``, under ``layers`` inside the
    scan, so ``unnamed_time_share.train`` and ``scan_stack_time_share.train``
    cannot fill with them. The erf form (the toy's) keeps its slope and makes
    nothing again; a ``silu_glu`` layer's sigmoid is made again under ``mlp``."""
    again = remade(train_names)
    assert {sublayers.label(n) for n in again} == {"layers/attn_norm", "layers/mlp_norm", "final_norm"}
    assert not any(sublayers.is_scan_stacking(n) for n in again)
    # the slope is computed in the forward and applied in the backward, both under ``mlp``
    under_mlp = {n.rsplit("/", 1)[1]: n for n in train_names if sublayers.label(n) == "layers/mlp"}
    assert {"erfc", "exp"} <= set(under_mlp) and "jvp(CausalLM)/" in under_mlp["exp"]

    llama = dataclasses.replace(config_from_hf(NEOX_TOY), norm="rmsnorm", activation="silu_glu", parallel_block=False,
                                parallel_mlp_norm=False)
    batch = {"input_ids": jnp.zeros((1, 16), jnp.int32)}
    grad = jax.jit(jax.grad(lambda p: CausalLM(llama).apply({"params": p}, batch, train=True)[0]))
    again = remade(op_names(grad.lower(shapes_of(llama)).compile().as_text()))
    assert {sublayers.label(n) for n in again} == {"layers/attn_norm", "layers/mlp_norm", "layers/mlp", "final_norm"}
    assert any(n.endswith("mlp/checkpoint/rematted_computation/jit(silu)/exp") for n in again)


def test_the_routed_prefill_s_two_halves_have_names_of_their_own(serving):
    """PR 40: around its grouped matmuls the ragged path gathers twice, and a
    trace tells the two apart by name: ``moe_dispatch`` (the sort, the group
    sizes, the tokens' rows into expert order) and ``moe_combine`` (the sort's
    inverse, each token's k rows back, the sum), both under ``moe_experts``,
    the matmuls and the activation directly under it. Nothing under ``moe_combine`` scatters. A
    chain takes the dense path and has neither name."""
    architecture, _, names = serving
    halves = {half: {n for n in names["step"] if half in sublayers.components(n)}
              for half in ("moe_dispatch", "moe_combine")}
    if architecture != "glm4_moe_lite":
        assert not halves["moe_dispatch"] and not halves["moe_combine"]
        return
    for half, mine in halves.items():
        assert mine and all(sublayers.components(n)[sublayers.components(n).index(half) - 1] == "moe_experts"
                            for n in mine), (half, mine)
        assert any(n.endswith("/gather") for n in mine), (half, mine)
    assert any(n.endswith("/sort") for n in halves["moe_combine"])  # the inverse permutation
    assert not [n for n in halves["moe_combine"] if "scatter" in n.rpartition("/")[2]]
    between = [n for n in names["step"] if sublayers.components(n)[-2:-1] == ["moe_experts"]]
    assert between  # the grouped matmuls and the activation (the CPU lowers ``ragged_dot`` to plain ops)
    assert not [n for n in names["chain"] if set(sublayers.components(n)) & set(halves)]


def test_the_tool_prints_the_two_halves():
    """``tools/traced_cell.py::moe_halves`` on rows as ``hlo_stats`` gives them:
    a half's seconds, its layer-calls (its largest instruction's count) and its
    largest instructions; an instruction under neither name is left out."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location("traced_cell", os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "tools", "traced_cell.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    row = lambda name, path, us, n: {"hlo_op_name": name, "tf_op_name": path + ":", "total_self_time": us,  # noqa: E731
                                     "occurrences": n, "hlo_op_expression": f"%{name} = bf16[8,8] fusion()"}
    lines = list(tool.moe_halves([
        row("fusion.1", "jit(step)/layer/moe/moe_experts/moe_combine/gather", 30000.0, 30),
        row("fusion.2", "jit(step)/layer/moe/moe_experts/moe_combine/convert_element_type", 15000.0, 30),
        row("fusion.3", "jit(step)/layer/moe/moe_experts/moe_dispatch/gather", 90000.0, 30),
        row("gmm.1", "jit(step)/layer/moe/moe_experts/jit(gmm)/pallas_call", 99000.0, 30)]))
    assert lines[0].startswith("moe_half=moe_dispatch device_s=0.09 layer_calls=30 ms_a_layer_call=3.0")
    assert [line.split()[1] for line in lines if line.startswith("moe_half_op=moe_combine")] == [
        "instruction=fusion.1", "instruction=fusion.2"]
    assert "moe_half=moe_combine device_s=0.045 layer_calls=30 ms_a_layer_call=1.5" in lines[2]
    assert not [line for line in lines if "gmm.1" in line]

