"""HuggingFace checkpoint ingestion: safetensors -> CausalLM param pytree.

TPU-native analog of the reference's model-implementation/checkpoint-loading
stack: ``module_inject/load_checkpoint.py`` (name-mapped weight copy into
injected modules), ``inference/v2/engine_factory.py`` (per-family policies:
llama/mistral/mixtral/...), and ``inference/engine.py:301``
(``load_model_with_checkpoint``, sharded/meta checkpoints). Instead of
surgically rewriting torch modules, we translate the HF state dict into the
framework's stacked-scan param tree once; AutoTP placement then shards it over
the mesh (``parallel/autotp.place_parameters``).

Supported families: llama (incl. mistral — same graph), qwen2 (llama graph
+ qkv biases), gpt2, opt, falcon (7b-style parallel block, MQA), phi (parallel
block + partial rotary), mixtral, gpt_neox (per-head fused QKV, parallel
residual with separate MLP norm), bloom (ALiBi + embedding layernorm), gptj
(interleaved rotary, parallel block, biased MLP/head), codegen (gptj graph +
mp_num-blocked fused QKV).
Sharded checkpoints (``model.safetensors.index.json``) are read shard-by-shard
into one host dict before conversion — peak host memory is the full fp* model
plus the stacked copy being built. A per-layer streaming path (convert and
free as each shard arrives) is the upgrade if host RAM ever binds.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.transformer import TransformerConfig


# --------------------------------------------------------------------- load

def load_safetensors_state(path: str) -> Dict[str, np.ndarray]:
    """Read a .safetensors file / HF checkpoint dir into {name: ndarray}."""
    from safetensors import safe_open

    def read_file(fp):
        out = {}
        with safe_open(fp, framework="np") as f:
            for k in f.keys():
                out[k] = f.get_tensor(k)
        return out

    if os.path.isfile(path):
        return read_file(path)
    index = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        state: Dict[str, np.ndarray] = {}
        for shard in sorted(set(weight_map.values())):
            state.update(read_file(os.path.join(path, shard)))
        return state
    single = os.path.join(path, "model.safetensors")
    if os.path.exists(single):
        return read_file(single)
    files = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    state = {}
    for f in files:
        state.update(read_file(os.path.join(path, f)))
    return state


def _latent_routed(hf_config: Dict[str, Any], mt: str) -> Dict[str, Any]:
    """What ``glm4_moe_lite`` and ``xing4_0`` share, as TransformerConfig
    keywords: the DeepSeek-V3 family's latent attention and routed experts
    under that family's key names."""
    if hf_config.get("n_group", 1) != 1 or hf_config.get("topk_group", 1) != 1:
        raise ValueError(f"{mt} with grouped expert choice (n_group > 1) is unsupported")
    if hf_config.get("partial_rotary_factor", 1) != 1:
        raise ValueError(f"{mt} with partial_rotary_factor != 1 is unsupported")
    dtype = hf_config.get("dtype", hf_config.get("torch_dtype"))
    # newer files of the family keep the rotary's base under ``rope_parameters``
    rope = hf_config.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"{mt} with rope_parameters.rope_type={rope['rope_type']!r} is unsupported (a scaled "
                         "rotary is taken as rope_scaling of type 'yarn', for xing4_0 alone)")
    # ``n_routed_experts`` are the experts HELD here: with ``expert_parallel: {size, rank}`` the router scores
    # ``size`` times as many (a chip's share of a routed layer, ``TransformerConfig.expert_parallel``)
    share = hf_config.get("expert_parallel")
    if share:
        from deepspeed_tpu.models.transformer import ExpertParallel

        share = ExpertParallel(int(share["size"]), int(share.get("rank", 0)))
    return dict(
        vocab_size=hf_config["vocab_size"],
        hidden_size=hf_config["hidden_size"],
        intermediate_size=hf_config["intermediate_size"],
        num_layers=hf_config["num_hidden_layers"],
        num_heads=hf_config["num_attention_heads"],
        max_seq_len=hf_config.get("max_position_embeddings", 4096),
        norm="rmsnorm",
        activation="silu_glu",
        position="rope",
        rope_theta=float(rope.get("rope_theta", hf_config.get("rope_theta", 10000.0))),
        expert_parallel=share or None,
        # the family's stored layout rotates adjacent pairs (its modelling
        # code de-interleaves q and k alike before a half-split rotation:
        # the same scores)
        rope_interleaved=bool(hf_config.get("rope_interleave", True)),
        norm_eps=float(hf_config.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
        qkv_bias=bool(hf_config.get("attention_bias", False)),
        q_lora_rank=hf_config["q_lora_rank"],
        kv_lora_rank=hf_config["kv_lora_rank"],
        qk_nope_head_dim=hf_config["qk_nope_head_dim"],
        qk_rope_head_dim=hf_config["qk_rope_head_dim"],
        v_head_dim=hf_config["v_head_dim"],
        first_dense_layers=hf_config.get("first_k_dense_replace", 0),
        num_experts=hf_config["n_routed_experts"],
        moe_top_k=hf_config["num_experts_per_tok"],
        moe_intermediate_size=hf_config["moe_intermediate_size"],
        moe_shared_experts=hf_config.get("n_shared_experts") or 0,
        moe_router="sigmoid",
        moe_renormalize=bool(hf_config.get("norm_topk_prob", True)),
        moe_routed_scale=float(hf_config.get("routed_scaling_factor", 1.0)),
        moe_drop_tokens=False,
        param_dtype={"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(dtype, jnp.float32),
    )


def config_from_hf(hf_config: Dict[str, Any]) -> TransformerConfig:
    """Map an HF ``config.json`` dict to a TransformerConfig."""
    mt = hf_config.get("model_type", "llama")
    if mt == "gpt2":
        h = hf_config["n_embd"]
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("n_inner") or 4 * h,
            num_layers=hf_config["n_layer"],
            num_heads=hf_config["n_head"],
            max_seq_len=hf_config.get("n_positions", 1024),
            norm="layernorm",
            activation="gelu",
            position="learned",
            tie_embeddings=True,
        )
    if mt in ("llama", "mistral", "mixtral", "qwen2"):
        kw = dict(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_layers=hf_config["num_hidden_layers"],
            num_heads=hf_config["num_attention_heads"],
            num_kv_heads=hf_config.get("num_key_value_heads"),
            head_dim=hf_config.get("head_dim"),
            max_seq_len=hf_config.get("max_position_embeddings", 4096),
            norm="rmsnorm",
            activation="silu_glu",
            position="rope",
            rope_theta=float(hf_config.get("rope_theta", 10000.0)),
            norm_eps=float(hf_config.get("rms_norm_eps", 1e-5)),
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
        )
        if mt == "mixtral":
            kw.update(
                num_experts=hf_config["num_local_experts"],
                moe_top_k=hf_config.get("num_experts_per_tok", 2),
            )
        # HF llama-format configs may carry qkv biases (attention_bias);
        # qwen2 always does
        kw["qkv_bias"] = True if mt == "qwen2" else bool(hf_config.get("attention_bias", False))
        return TransformerConfig(**kw)
    if mt == "evabyte":
        # a llama-shaped decoder over bytes with EVA attention: an exact
        # window beside chunk summaries, norms that store their offset from
        # one, an fp32 residual stream, num_pred_heads output heads in one
        # kernel. Multi-byte self-speculation over the further heads is not built
        if hf_config.get("attention_class", "eva") != "eva":
            raise ValueError(f"evabyte with attention_class={hf_config['attention_class']!r} is unsupported")
        if hf_config.get("rope_scaling"):
            raise ValueError("evabyte with rope_scaling is unsupported (type 'yarn' is taken, by the "
                             "latent attention of xing4_0 alone)")
        if hf_config.get("num_chunks"):
            raise ValueError("evabyte with num_chunks (a fixed number of chunks a window) is unsupported")
        dtype = hf_config.get("dtype", hf_config.get("torch_dtype"))
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_layers=hf_config["num_hidden_layers"],
            num_heads=hf_config["num_attention_heads"],
            num_kv_heads=hf_config.get("num_key_value_heads"),
            max_seq_len=hf_config.get("max_position_embeddings", 32768),
            norm="rmsnorm",
            activation="silu_glu",
            position="rope",
            rope_theta=float(hf_config.get("rope_theta", 100000.0)),
            norm_eps=float(hf_config.get("rms_norm_eps", 1e-5)),
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
            qkv_bias=bool(hf_config.get("attention_bias", False)),
            eva_window=hf_config["window_size"],
            eva_chunk=hf_config["chunk_size"],
            norm_unit_offset=bool(hf_config.get("norm_add_unit_offset", False)),
            fp32_residual=bool(hf_config.get("fp32_skip_add", False)),
            num_pred_heads=int(hf_config.get("num_pred_heads", 1)),
            param_dtype={"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(dtype, jnp.float32),
        )
    if mt in ("glm4_moe_lite", "xing4_0", "glm_moe_dsa"):
        # latent attention, leading dense layers before the routed stack, a
        # sigmoid router with a correction bias and a shared expert
        # (``_latent_routed``); xing4_0 besides wraps every sublayer in a
        # hyper-connection over hc_mult residual streams (mHC) and scales its
        # rotary frequencies (YaRN); glm_moe_dsa besides has in EVERY layer a
        # learned indexer that keeps index_topk cached tokens a query
        # (``ops/dsa.py``). The next-token-prediction layers
        # (num_nextn_predict_layers) are not built: no serving path runs them
        kw = _latent_routed(hf_config, mt)
        scaling = hf_config.get("rope_scaling")
        if mt == "glm_moe_dsa":
            refused = [(bool(scaling), "rope_scaling"),
                       (hf_config.get("index_topk", 0) <= 0, f"index_topk={hf_config.get('index_topk')} (no indexer: "
                        "that is glm4_moe_lite)"),
                       (not hf_config.get("indexer_rope_interleave", True), "indexer_rope_interleave=false"),
                       (hf_config.get("index_key_dtype", "bfloat16") not in ("bfloat16", "float32"),
                        f"index_key_dtype={hf_config.get('index_key_dtype')!r} (an fp8 or int8 index key: the "
                        "pool keeps the keys in the cache's own type)"),
                       (hf_config.get("hidden_act", "silu") != "silu", f"hidden_act={hf_config.get('hidden_act')!r}"),
                       (hf_config.get("scoring_func", "sigmoid") != "sigmoid",
                        f"scoring_func={hf_config.get('scoring_func')!r}")]
            refused = [what for bad, what in refused if bad]
            if refused:
                raise ValueError("glm_moe_dsa with " + "; ".join(refused) + " is unsupported")
            kw.update(index_heads=hf_config["index_n_heads"], index_head_dim=hf_config["index_head_dim"],
                      index_topk=hf_config["index_topk"])
            return TransformerConfig(**kw)
        if mt == "xing4_0":
            kw.update(  # a rope_scaling of another type than yarn: TransformerConfig refuses it by name
                hc_mult=hf_config["hc_mult"],
                hc_sinkhorn_iters=hf_config["hc_sinkhorn_iters"],
                hc_eps=float(hf_config["hc_eps"]),
                hc_res_clamp=(float(hf_config["mhc_h_res_clamp_min"]), float(hf_config["mhc_h_res_clamp_max"])),
                rope_scaling=dict(scaling) if scaling else None)
        elif scaling:
            raise ValueError("glm4_moe_lite with rope_scaling is unsupported (type 'yarn' is taken for "
                             "xing4_0 alone)")
        return TransformerConfig(**kw)
    if mt == "granitemoehybrid":
        # a layer PATTERN (layer_types): Mamba-2 state-space mixers beside GQA
        # attention with no positional term at all, four scalar multipliers,
        # every mixer followed by one dense silu-GLU (shared_intermediate_size)
        # and, in the routed variants (num_local_experts > 0), beside it by a
        # softmax router over experts of width ``intermediate_size`` (the config
        # has no key of its own for it: the family's code builds input_linear
        # [E, 2 x intermediate_size, hidden]), top-k THEN softmax, which is the
        # softmax over all renormalised over the picks; the shared MLP has no
        # gate. ``num_local_experts`` are the experts HELD here: with
        # ``expert_parallel: {size, rank}`` the router scores ``size`` times as many
        held, width = hf_config.get("num_local_experts", 0), hf_config["intermediate_size"]
        share = hf_config.get("expert_parallel") if held else None
        picks, scored = hf_config.get("num_experts_per_tok", 0), held * int((share or {"size": 1})["size"])
        shared_width = hf_config.get("shared_intermediate_size", width)
        refused = [(held > 0 and not 1 <= picks <= scored,
                    f"num_local_experts={held} and num_experts_per_tok={picks} (1 to the {scored} the router scores)"),
                   (held > 0 and shared_width % width != 0,
                    "shared_intermediate_size not a multiple of intermediate_size (an expert's width)"),
                   (hf_config.get("position_embedding_type", "nope") != "nope",
                    f"position_embedding_type={hf_config.get('position_embedding_type')!r} (only 'nope')"),
                   (bool(hf_config.get("rope_scaling")), "rope_scaling"),
                   (bool(hf_config.get("attention_bias")) or bool(hf_config.get("mamba_proj_bias")),
                    "attention_bias / mamba_proj_bias"),
                   (not hf_config.get("mamba_conv_bias", True), "mamba_conv_bias=false"),
                   (hf_config.get("hidden_act", "silu") != "silu", f"hidden_act={hf_config.get('hidden_act')!r}"),
                   (hf_config.get("normalization_function", "rmsnorm") != "rmsnorm",
                    f"normalization_function={hf_config.get('normalization_function')!r}"),
                   (hf_config["mamba_n_heads"] * hf_config["mamba_d_head"]
                    != hf_config["mamba_expand"] * hf_config["hidden_size"],
                    "mamba_n_heads x mamba_d_head != mamba_expand x hidden_size")]
        refused = [what for bad, what in refused if bad]
        if refused:
            raise ValueError("granitemoehybrid with " + "; ".join(refused) + " is unsupported")
        from deepspeed_tpu.models.transformer import ExpertParallel, SSMConfig

        dtype = hf_config.get("dtype", hf_config.get("torch_dtype"))
        routed = dict(
            num_experts=held,
            expert_parallel=ExpertParallel(int(share["size"]), int(share.get("rank", 0))) if share else None,
            moe_top_k=picks,
            moe_intermediate_size=width,
            moe_shared_experts=shared_width // width,
            moe_router="softmax",
            moe_renormalize=True,
            moe_drop_tokens=False,
        ) if held else {}
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=shared_width,
            num_layers=hf_config["num_hidden_layers"],
            num_heads=hf_config["num_attention_heads"],
            num_kv_heads=hf_config.get("num_key_value_heads"),
            max_seq_len=hf_config.get("max_position_embeddings", 131072),
            norm="rmsnorm",
            activation="silu_glu",
            position="none",
            norm_eps=float(hf_config.get("rms_norm_eps", 1e-5)),
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", True)),
            qkv_bias=False,
            layer_types=tuple(hf_config["layer_types"]),
            ssm=SSMConfig(n_heads=hf_config["mamba_n_heads"], head_dim=hf_config["mamba_d_head"],
                          d_state=hf_config["mamba_d_state"], n_groups=hf_config.get("mamba_n_groups", 1),
                          d_conv=hf_config.get("mamba_d_conv", 4),
                          chunk_size=hf_config.get("mamba_chunk_size", 256)),
            embedding_multiplier=float(hf_config.get("embedding_multiplier", 1.0)),
            attention_multiplier=float(hf_config["attention_multiplier"]),
            residual_multiplier=float(hf_config.get("residual_multiplier", 1.0)),
            logits_scaling=float(hf_config.get("logits_scaling", 1.0)),
            param_dtype={"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(dtype, jnp.float32),
            **routed,
        )
    if mt == "qwen3_next":
        # a ROUTED layer pattern: three Gated DeltaNet (linear-attention) layers
        # to every gated softmax-attention layer (per-head QK-norm, rotary on
        # part of a head, the output times sigmoid of a gate the query
        # projection carries), in every layer a softmax router over
        # ``num_experts`` beside one shared expert behind a sigmoid gate; every
        # norm but the DeltaNet's gated one multiplies by 1 + w. ``num_experts``
        # are the experts HELD here: with ``expert_parallel: {size, rank}`` the
        # router scores ``size`` times as many. The multi-token-prediction layer
        # of the checkpoints is not built: no serving path runs it
        L = hf_config["num_hidden_layers"]
        interval = hf_config.get("full_attention_interval", 4)
        kinds = tuple(hf_config.get("layer_types") or
                      ("full_attention" if (i + 1) % interval == 0 else "linear_attention" for i in range(L)))
        refused = [(bool(hf_config.get("mlp_only_layers")), "mlp_only_layers (dense layers among the routed)"),
                   (hf_config.get("decoder_sparse_step", 1) != 1, "decoder_sparse_step != 1"),
                   (bool(hf_config.get("rope_scaling")), "rope_scaling"),
                   (bool(hf_config.get("attention_bias")), "attention_bias"),
                   (bool(hf_config.get("use_sliding_window")), "use_sliding_window"),
                   (hf_config.get("hidden_act", "silu") != "silu", f"hidden_act={hf_config.get('hidden_act')!r}"),
                   (not set(kinds) <= {"full_attention", "linear_attention"}, f"layer_types of {sorted(set(kinds))}"),
                   (hf_config.get("shared_expert_intermediate_size", 0) % hf_config["moe_intermediate_size"] != 0,
                    "shared_expert_intermediate_size not a multiple of moe_intermediate_size")]
        refused = [what for bad, what in refused if bad]
        if refused:
            raise ValueError("qwen3_next with " + "; ".join(refused) + " is unsupported")
        from deepspeed_tpu.models.transformer import ExpertParallel, GDNConfig

        hd = hf_config.get("head_dim") or hf_config["hidden_size"] // hf_config["num_attention_heads"]
        share, shared_width = hf_config.get("expert_parallel"), hf_config.get("shared_expert_intermediate_size", 0)
        dtype = hf_config.get("dtype", hf_config.get("torch_dtype"))
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_layers=L,
            num_heads=hf_config["num_attention_heads"],
            num_kv_heads=hf_config.get("num_key_value_heads"),
            head_dim=hd,
            max_seq_len=hf_config.get("max_position_embeddings", 262144),
            norm="rmsnorm",
            activation="silu_glu",
            position="rope",
            rope_theta=float(hf_config.get("rope_theta", 10000000.0)),
            rotary_dim=int(hf_config.get("partial_rotary_factor", 0.25) * hd),
            norm_eps=float(hf_config.get("rms_norm_eps", 1e-6)),
            norm_unit_offset=True,
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
            qkv_bias=False,
            attn_output_gate=True,
            qk_norm=True,
            layer_types=tuple("attention" if kind == "full_attention" else kind for kind in kinds),
            gdn=GDNConfig(n_k_heads=hf_config["linear_num_key_heads"], n_v_heads=hf_config["linear_num_value_heads"],
                          head_k_dim=hf_config["linear_key_head_dim"], head_v_dim=hf_config["linear_value_head_dim"],
                          d_conv=hf_config.get("linear_conv_kernel_dim", 4)),
            num_experts=hf_config["num_experts"],
            expert_parallel=ExpertParallel(int(share["size"]), int(share.get("rank", 0))) if share else None,
            moe_top_k=hf_config["num_experts_per_tok"],
            moe_intermediate_size=hf_config["moe_intermediate_size"],
            moe_shared_experts=shared_width // hf_config["moe_intermediate_size"],
            moe_shared_gate=shared_width > 0,
            moe_router="softmax",
            moe_renormalize=bool(hf_config.get("norm_topk_prob", True)),
            moe_drop_tokens=False,
            param_dtype={"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(dtype, jnp.float32),
        )
    if mt == "cohere2_moe":
        # a PATTERN of two attention kinds in ONE parallel block with a routed MLP: ``sliding_attention`` layers
        # (a band of ``sliding_window`` keys, rotary in adjacent pairs: ``rope_gptj``) beside ``full_attention``
        # layers with no position term at all; attention, the routed experts (sigmoid scores, the largest k,
        # renormalised, no correction bias) and ``num_shared_experts`` shared experts AVERAGED all read the one
        # bias-free LayerNorm of the layer's input; a tied embedding, logits times ``logit_scale``.
        # ``intermediate_size`` is the width of ONE expert, routed or shared. ``num_experts`` are the experts
        # HELD here: with ``expert_parallel: {size, rank}`` the router scores ``size`` times as many. The
        # config alone is mapped: no checkpoint's key names are in the repository, so no state dict is converted
        L = hf_config["num_hidden_layers"]
        switch = hf_config.get("layer_switch", 4)
        kinds = tuple(hf_config.get("layer_types") or
                      ("full_attention" if (i + 1) % switch == 0 else "sliding_attention" for i in range(L)))
        refused = [
            (hf_config.get("first_k_dense_replace", 0) != 0, "first_k_dense_replace != 0 (leading dense layers)"),
            (bool(hf_config.get("attention_bias")), "attention_bias"),
            (bool(hf_config.get("use_qk_norm")), "use_qk_norm"),
            (not hf_config.get("use_parallel_block", True), "use_parallel_block false (a sequential block)"),
            (not hf_config.get("use_gated_activation", True), "use_gated_activation false"),
            (hf_config.get("hidden_act", "silu") != "silu", f"hidden_act={hf_config.get('hidden_act')!r}"),
            (hf_config.get("expert_selection_fn", "sigmoid") != "sigmoid",
             f"expert_selection_fn={hf_config.get('expert_selection_fn')!r}"),
            (hf_config.get("shared_expert_combination_strategy", "average") != "average",
             f"shared_expert_combination_strategy={hf_config.get('shared_expert_combination_strategy')!r}"),
            (hf_config.get("position_embedding_type", "rope_gptj") != "rope_gptj",
             f"position_embedding_type={hf_config.get('position_embedding_type')!r}"),
            (hf_config.get("rotary_pct", 1) != 1, "rotary_pct != 1 (a partial rotary)"),
            ((hf_config.get("rope_parameters") or {}).get("rope_type", "default") != "default", "rope scaling"),
            (not set(kinds) <= {"full_attention", "sliding_attention"}, f"layer_types of {sorted(set(kinds))}"),
            (not hf_config.get("sliding_window"), "no sliding_window"),
        ]
        refused = [what for bad, what in refused if bad]
        if refused:
            raise ValueError("cohere2_moe with " + "; ".join(refused) + " is unsupported")
        from deepspeed_tpu.models.transformer import ExpertParallel, SlidingConfig

        share = hf_config.get("expert_parallel")
        theta = (hf_config.get("rope_parameters") or {}).get("rope_theta", hf_config.get("rope_theta", 50000.0))
        dtype = hf_config.get("dtype", hf_config.get("torch_dtype"))
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_layers=L,
            num_heads=hf_config["num_attention_heads"],
            num_kv_heads=hf_config.get("num_key_value_heads"),
            head_dim=hf_config.get("head_dim"),
            max_seq_len=hf_config.get("max_position_embeddings", 200000),
            norm="layernorm",
            norm_bias=False,
            norm_eps=float(hf_config.get("layer_norm_eps", 1e-5)),
            activation="silu_glu",
            qkv_bias=False,
            dense_bias=False,
            parallel_block=True,
            position="rope",
            rope_theta=float(theta),
            rope_interleaved=True,
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", True)),
            logits_scaling=1.0 / float(hf_config.get("logit_scale", 1.0)),
            layer_types=tuple("attention" if kind == "full_attention" else kind for kind in kinds),
            sliding=SlidingConfig(window=int(hf_config["sliding_window"]), global_rope=False),
            num_experts=hf_config["num_experts"],
            expert_parallel=ExpertParallel(int(share["size"]), int(share.get("rank", 0))) if share else None,
            moe_top_k=hf_config["num_experts_per_tok"],
            moe_intermediate_size=hf_config["intermediate_size"],
            moe_shared_experts=hf_config.get("num_shared_experts", 0),
            moe_shared_average=hf_config.get("num_shared_experts", 0) > 1,
            moe_router="sigmoid",
            moe_router_bias=False,
            moe_renormalize=bool(hf_config.get("norm_topk_prob", True)),
            moe_drop_tokens=False,
            param_dtype={"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(dtype, jnp.float32),
        )
    if mt == "mimo_v2":
        # a PATTERN of two attention kinds that differ in more than the band (``hybrid_layer_pattern``: 0 a global
        # layer, 1 a sliding one): the sliding kind has its own kv heads, head widths and rotary base and a learned
        # sink a head in its softmax (``swa_*``, ``add_swa_attention_sink_bias``); keys of ``head_dim`` beside values
        # of ``v_head_dim`` in both; rotary over the first ``partial_rotary_factor`` of a head, in halves; values
        # times ``attention_value_scale``; a sequential pre-norm block with RMSNorm; the leading layers that
        # ``moe_layer_freq`` marks 0 carry a dense MLP, the others a sigmoid router with a correction bias choosing
        # (``noaux_tc``) over ``n_routed_experts`` experts HELD here (``expert_parallel: {size, rank}``: the router
        # scores ``size`` times as many), no shared expert. The config alone is mapped: no checkpoint's key names
        # are in the repository, so no state dict is converted (``attention_projection_layout`` says how one
        # stores q, k and v). The vision and audio towers and the multi-token-prediction layers have no key here.
        L = hf_config["num_hidden_layers"]
        pattern = list(hf_config.get("hybrid_layer_pattern") or [0] * L)
        freq = list(hf_config.get("moe_layer_freq") or [1] * L)
        dense = next((i for i, f in enumerate(freq) if f), L)
        rope = hf_config.get("rope_scaling") or {}
        refused = [
            (len(pattern) != L or len(freq) != L, f"hybrid_layer_pattern / moe_layer_freq not of {L} entries"),
            (not set(pattern) <= {0, 1}, f"hybrid_layer_pattern of {sorted(set(pattern))}"),
            (1 not in pattern, "no sliding layer in hybrid_layer_pattern"),
            (dense == L or not all(freq[dense:]), "moe_layer_freq that is not leading dense layers, then routed ones"),
            (bool(hf_config.get("attention_bias")), "attention_bias"),
            (bool(hf_config.get("add_full_attention_sink_bias")), "add_full_attention_sink_bias (a sink in the "
             "global layers)"),
            (hf_config.get("swa_num_attention_heads", hf_config["num_attention_heads"])
             != hf_config["num_attention_heads"], "swa_num_attention_heads != num_attention_heads"),
            (hf_config.get("hidden_act", "silu") != "silu", f"hidden_act={hf_config.get('hidden_act')!r}"),
            (hf_config.get("scoring_func", "sigmoid") != "sigmoid", f"scoring_func={hf_config.get('scoring_func')!r}"),
            (hf_config.get("topk_method", "noaux_tc") != "noaux_tc", f"topk_method={hf_config.get('topk_method')!r}"),
            (hf_config.get("n_group", 1) != 1 or hf_config.get("topk_group", 1) != 1, "grouped expert choice "
             "(n_group > 1)"),
            (bool(hf_config.get("n_shared_experts")), "n_shared_experts"),
            (rope.get("rope_type", rope.get("type", "default")) != "default", "rope scaling"),
            (hf_config.get("attention_chunk_size") not in (None, hf_config.get("sliding_window")),
             "attention_chunk_size apart from sliding_window"),
            (not hf_config.get("sliding_window"), "no sliding_window"),
        ]
        refused = [what for bad, what in refused if bad]
        if refused:
            raise ValueError("mimo_v2 with " + "; ".join(refused) + " is unsupported")
        from deepspeed_tpu.models.transformer import ExpertParallel, SlidingConfig

        share = hf_config.get("expert_parallel")
        hd = hf_config["head_dim"]
        rotary = int(hf_config.get("partial_rotary_factor", 1.0) * hd) // 2 * 2
        dtype = hf_config.get("dtype", hf_config.get("torch_dtype"))
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=hf_config["hidden_size"],
            intermediate_size=hf_config["intermediate_size"],
            num_layers=L,
            num_heads=hf_config["num_attention_heads"],
            num_kv_heads=hf_config.get("num_key_value_heads"),
            head_dim=hd,
            v_head_dim=0 if hf_config.get("v_head_dim", hd) == hd else hf_config["v_head_dim"],
            max_seq_len=hf_config.get("max_position_embeddings", 262144),
            norm="rmsnorm",
            # a norm's weight is kept as its offset from one, drawn off zero: none sits at a constant
            norm_unit_offset=True,
            norm_eps=float(hf_config.get("layernorm_epsilon", 1e-5)),
            activation="silu_glu",
            qkv_bias=False,
            dense_bias=False,
            position="rope",
            rope_theta=float(hf_config.get("rope_theta", 10000000.0)),
            rotary_dim=0 if rotary == hd else rotary,
            value_multiplier=float(hf_config.get("attention_value_scale") or 1.0),
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
            layer_types=tuple("sliding_attention" if kind else "attention" for kind in pattern),
            sliding=SlidingConfig(
                window=int(hf_config["sliding_window"]), global_rope=True,
                num_kv_heads=hf_config.get("swa_num_key_value_heads"), head_dim=hf_config.get("swa_head_dim"),
                v_head_dim=hf_config.get("swa_v_head_dim"),
                rope_theta=float(hf_config.get("swa_rope_theta", hf_config.get("rope_theta", 10000.0))),
                sink=bool(hf_config.get("add_swa_attention_sink_bias"))),
            first_dense_layers=dense,
            num_experts=hf_config["n_routed_experts"],
            expert_parallel=ExpertParallel(int(share["size"]), int(share.get("rank", 0))) if share else None,
            moe_top_k=hf_config["num_experts_per_tok"],
            moe_intermediate_size=hf_config["moe_intermediate_size"],
            moe_router="sigmoid",
            moe_renormalize=bool(hf_config.get("norm_topk_prob", True)),
            moe_routed_scale=float(hf_config.get("routed_scaling_factor") or 1.0),
            moe_drop_tokens=False,
            param_dtype={"bfloat16": jnp.bfloat16, "float16": jnp.float16}.get(dtype, jnp.float32),
        )
    if mt == "opt":
        if not hf_config.get("do_layer_norm_before", True):
            raise ValueError("OPT post-layernorm variants (do_layer_norm_before=false) are unsupported")
        h = hf_config["hidden_size"]
        if hf_config.get("word_embed_proj_dim", h) != h:
            raise ValueError("OPT word_embed_proj_dim != hidden_size (e.g. opt-350m) is unsupported")
        act = hf_config.get("activation_function", "relu")
        if act not in ("relu", "gelu", "gelu_new"):
            raise ValueError(f"unsupported OPT activation_function {act!r}")
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config["ffn_dim"],
            num_layers=hf_config["num_hidden_layers"],
            num_heads=hf_config["num_attention_heads"],
            max_seq_len=hf_config.get("max_position_embeddings", 2048),
            norm="layernorm",
            # HF 'gelu' is exact erf-gelu; 'gelu_new' is the tanh approx
            activation={"relu": "relu", "gelu": "gelu_exact", "gelu_new": "gelu"}[act],
            position="learned",
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", True)),
        )
    if mt == "falcon":
        if hf_config.get("new_decoder_architecture", False):
            raise ValueError("falcon new_decoder_architecture (40b/180b) is unsupported")
        if not hf_config.get("parallel_attn", True):
            raise ValueError("falcon without parallel_attn is unsupported")
        if hf_config.get("alibi", False):
            raise ValueError("falcon alibi position biases are unsupported (rope only)")
        if not hf_config.get("multi_query", True):
            raise ValueError(
                "falcon multi_query=False is unsupported (HF interleaves q/k/v per "
                "head in the fused projection for that variant)")
        if hf_config.get("bias", False):
            raise ValueError("falcon bias=True variants are unsupported")
        h = hf_config["hidden_size"]
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("ffn_hidden_size") or 4 * h,
            num_layers=hf_config["num_hidden_layers"],
            num_heads=hf_config["num_attention_heads"],
            num_kv_heads=1,  # multi_query guaranteed by the guard above
            max_seq_len=hf_config.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation="gelu_exact",
            position="rope",
            rope_theta=float(hf_config.get("rope_theta", 10000.0)),
            norm_eps=float(hf_config.get("layer_norm_epsilon", 1e-5)),
            qkv_bias=False,  # bias=True rejected above
            dense_bias=False,
            parallel_block=True,
            # falcon ties by default (FalconConfig.tie_word_embeddings=True)
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", True)),
        )
    if mt == "phi":
        if hf_config.get("qk_layernorm", False):
            raise ValueError("phi qk_layernorm=True is unsupported")
        h = hf_config["hidden_size"]
        heads = hf_config["num_attention_heads"]
        kvh = hf_config.get("num_key_value_heads") or heads
        if kvh != heads:
            raise ValueError("phi with GQA (num_key_value_heads != num_attention_heads) is unsupported")
        act = hf_config.get("hidden_act", "gelu_new")
        if act not in ("gelu_new", "gelu", "relu"):
            raise ValueError(f"unsupported phi hidden_act {act!r}")
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config["intermediate_size"],
            num_layers=hf_config["num_hidden_layers"],
            num_heads=heads,
            max_seq_len=hf_config.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation={"gelu_new": "gelu", "gelu": "gelu_exact", "relu": "relu"}[act],
            position="rope",
            rope_theta=float(hf_config.get("rope_theta", 10000.0)),
            rotary_dim=int(hf_config.get("partial_rotary_factor", 0.5) * (h // heads)),
            norm_eps=float(hf_config.get("layer_norm_eps", 1e-5)),
            qkv_bias=True,
            dense_bias=True,
            lm_head_bias=True,
            parallel_block=True,
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
        )
    if mt == "gpt_neox":
        h = hf_config["hidden_size"]
        heads = hf_config["num_attention_heads"]
        act = hf_config.get("hidden_act", "gelu")
        if act not in ("gelu", "gelu_new", "relu"):
            raise ValueError(f"unsupported gpt_neox hidden_act {act!r}")
        parallel = bool(hf_config.get("use_parallel_residual", True))
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config["intermediate_size"],
            num_layers=hf_config["num_hidden_layers"],
            num_heads=heads,
            max_seq_len=hf_config.get("max_position_embeddings", 2048),
            norm="layernorm",
            # HF ACT2FN 'gelu' is the exact erf gelu; 'gelu_new' the tanh form
            activation={"gelu": "gelu_exact", "gelu_new": "gelu", "relu": "relu"}[act],
            position="rope",
            # newer transformers serialize rope_theta/partial_rotary_factor in
            # place of the legacy neox spellings — accept either, legacy first
            rope_theta=float(hf_config.get("rotary_emb_base")
                             or hf_config.get("rope_theta", 10000.0)),
            # neox ropes only the first rotary_pct of each head
            rotary_dim=int((hf_config.get("rotary_pct")
                            or hf_config.get("partial_rotary_factor", 0.25))
                           * (h // heads)),
            norm_eps=float(hf_config.get("layer_norm_eps", 1e-5)),
            qkv_bias=True,
            dense_bias=True,
            parallel_block=parallel,
            parallel_mlp_norm=parallel,  # neox parallel uses ln2 for the MLP
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
        )
    if mt == "bloom":
        h = hf_config.get("hidden_size") or hf_config.get("n_embed")
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=4 * h,
            num_layers=hf_config.get("num_hidden_layers") or hf_config.get("n_layer"),
            num_heads=hf_config.get("num_attention_heads") or hf_config.get("n_head"),
            max_seq_len=hf_config.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation="gelu",  # bloom uses the tanh-approx gelu
            position="alibi",
            norm_eps=float(hf_config.get("layer_norm_epsilon", 1e-5)),
            qkv_bias=True,
            dense_bias=True,
            embed_norm=True,  # word_embeddings_layernorm
            tie_embeddings=True,  # bloom always ties lm_head to embeddings
        )
    if mt in ("gptj", "codegen"):
        # codegen reuses the gpt-j graph (interleaved partial rotary, shared
        # ln_1 parallel block, biased MLP + untied biased head); only its
        # fused-QKV storage differs (mp_num blocking, handled in the converter)
        h = hf_config["n_embd"]
        heads = hf_config["n_head"]
        act = hf_config.get("activation_function", "gelu_new")
        if act not in ("gelu_new", "gelu", "relu"):
            raise ValueError(f"unsupported {mt} activation_function {act!r}")
        if hf_config.get("tie_word_embeddings", False):
            # the lm_head keeps its BIAS even when tied; our tied path
            # computes x @ embed.T with no bias, which would silently drop
            # it. Real GPT-J/CodeGen checkpoints are untied.
            raise ValueError(f"{mt} with tie_word_embeddings=true is unsupported "
                             "(the tied head would drop lm_head.bias)")
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("n_inner") or 4 * h,
            num_layers=hf_config["n_layer"],
            num_heads=heads,
            max_seq_len=hf_config.get("n_positions", 2048),
            norm="layernorm",
            activation={"gelu_new": "gelu", "gelu": "gelu_exact", "relu": "relu"}[act],
            position="rope",
            rope_theta=10000.0,
            rotary_dim=hf_config.get("rotary_dim") or (h // heads),
            rope_interleaved=True,  # rotate_every_two convention
            norm_eps=float(hf_config.get("layer_norm_epsilon", 1e-5)),
            qkv_bias=False,
            dense_bias=False,   # attention projections are bias-free...
            mlp_bias=True,      # ...but fc_in/fc_out carry biases
            lm_head_bias=True,  # the lm_head carries a bias
            parallel_block=True,  # one shared ln_1 feeds attn AND mlp
            tie_embeddings=False,  # tied variant rejected above (bias drop)
        )
    if mt == "gpt_bigcode":
        # starcoder/santacoder (reference module_inject bigcode containers):
        # gpt2 graph but nn.Linear storage ([out, in]) and, with multi_query,
        # a single shared KV head fused into c_attn
        h = hf_config["n_embd"]
        act = hf_config.get("activation_function", "gelu_pytorch_tanh")
        if act not in ("gelu_pytorch_tanh", "gelu_new", "gelu", "relu"):
            raise ValueError(f"unsupported gpt_bigcode activation_function {act!r}")
        return TransformerConfig(
            vocab_size=hf_config["vocab_size"],
            hidden_size=h,
            intermediate_size=hf_config.get("n_inner") or 4 * h,
            num_layers=hf_config["n_layer"],
            num_heads=hf_config["n_head"],
            num_kv_heads=1 if hf_config.get("multi_query", True) else None,
            max_seq_len=hf_config.get("n_positions", 1024),
            norm="layernorm",
            # HF gelu_pytorch_tanh == gelu_new == the tanh approx
            activation={"gelu_pytorch_tanh": "gelu", "gelu_new": "gelu",
                        "gelu": "gelu_exact", "relu": "relu"}[act],
            position="learned",
            norm_eps=float(hf_config.get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=bool(hf_config.get("tie_word_embeddings", True)),
        )
    raise ValueError(
        f"unsupported HF model_type {mt!r} (supported: llama/mistral/mixtral/"
        "qwen2/gpt2/opt/falcon/phi/gpt_neox/bloom/gptj/codegen/gpt_bigcode/"
        "glm4_moe_lite/evabyte/xing4_0/granitemoehybrid/qwen3_next/glm_moe_dsa/cohere2_moe/mimo_v2)")


def detect_family(state: Dict[str, np.ndarray]) -> str:
    keys = state.keys()
    if any("kv_a_proj_with_mqa" in k for k in keys) and any("e_score_correction_bias" in k for k in keys):
        # (the same latent attention and router; the indexer's leaves tell them apart)
        return "glm_moe_dsa" if any("self_attn.indexer." in k for k in keys) else "glm4_moe_lite"
    if any("adaptive_phi" in k for k in keys):
        return "evabyte"
    if any("linear_attn.in_proj_qkvz" in k for k in keys):
        return "qwen3_next"
    if any("block_sparse_moe" in k for k in keys):
        return "mixtral"
    if any("decoder.embed_positions" in k for k in keys) and not any("encoder." in k for k in keys):
        return "opt"
    if any("word_embeddings_layernorm" in k for k in keys):
        return "bloom"
    if any("attention.query_key_value" in k and "self_attention" not in k for k in keys):
        return "gpt_neox"
    if any("self_attention.query_key_value" in k for k in keys):
        return "falcon"
    if any("self_attn.dense.weight" in k for k in keys):
        return "phi"
    if any("self_attn.q_proj.bias" in k for k in keys):
        return "qwen2"
    if any("self_attn.q_proj" in k for k in keys):
        return "llama"
    if any("attn.qkv_proj" in k for k in keys):
        return "codegen"
    if any("mlp.fc_in" in k for k in keys):
        return "gptj"
    for k in keys:
        if k.endswith("attn.c_attn.weight"):
            # gpt2 stores Conv1D [in, 3*in]; gpt_bigcode stores nn.Linear
            # [out, in] where out is 3*in (MHA) or in + 2*head_dim (MQA) —
            # the orientation/width separates them (np.shape also tolerates
            # non-array placeholders, treated as gpt2)
            shape = np.shape(state[k])
            if len(shape) == 2 and shape[1] != 3 * shape[0]:
                return "gpt_bigcode"
            return "gpt2"
    raise ValueError("cannot detect model family from checkpoint keys")


# ------------------------------------------------------------------ convert

def _getter(state: Dict[str, np.ndarray], prefixes: Tuple[str, ...]):
    """Tensor lookup tolerant of checkpoint-dependent top-level prefixes."""
    def g(name):
        for pre in prefixes:
            if pre + name in state:
                return np.asarray(state[pre + name])
        tried = ", ".join(repr(pre + name) for pre in prefixes)
        raise KeyError(f"checkpoint is missing tensor (tried {tried})")
    return g


def _stack(fn: Callable[[int], Dict[str, Any]], L: int) -> Dict[str, Any]:
    """Per-layer subtree -> stacked [L, ...] leaves (the nn.scan layout)."""
    import jax

    per = [fn(i) for i in range(L)]
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *per)


def _convert_llama(state, cfg: TransformerConfig) -> Dict[str, Any]:
    h, hd = cfg.hidden_size, cfg.dims_per_head
    H, Hkv = cfg.num_heads, cfg.kv_heads

    def g(name):
        return np.asarray(state[name])

    def layer(i):
        p = f"model.layers.{i}."
        attn = {
            # torch Linear stores [out, in]; flax DenseGeneral wants
            # [in, heads, head_dim]
            "wq": {"kernel": g(p + "self_attn.q_proj.weight").T.reshape(h, H, hd)},
            "wk": {"kernel": g(p + "self_attn.k_proj.weight").T.reshape(h, Hkv, hd)},
            "wv": {"kernel": g(p + "self_attn.v_proj.weight").T.reshape(h, Hkv, hd)},
            "wo": {"kernel": g(p + "self_attn.o_proj.weight").T.reshape(H, hd, h)},
        }
        if p + "self_attn.q_proj.bias" in state:  # qwen2-style qkv biases
            attn["wq"]["bias"] = g(p + "self_attn.q_proj.bias").reshape(H, hd)
            attn["wk"]["bias"] = g(p + "self_attn.k_proj.bias").reshape(Hkv, hd)
            attn["wv"]["bias"] = g(p + "self_attn.v_proj.bias").reshape(Hkv, hd)
        blk = {
            "attn_norm": {"scale": g(p + "input_layernorm.weight")},
            "mlp_norm": {"scale": g(p + "post_attention_layernorm.weight")},
            "attn": attn,
        }
        if cfg.num_experts > 0:
            ex = p + "block_sparse_moe."
            blk["moe"] = {
                "gate": {"wg": {"kernel": g(ex + "gate.weight").T}},
                "experts": {
                    "w_gate": np.stack([g(f"{ex}experts.{e}.w1.weight").T for e in range(cfg.num_experts)]),
                    "w_up": np.stack([g(f"{ex}experts.{e}.w3.weight").T for e in range(cfg.num_experts)]),
                    "w_down": np.stack([g(f"{ex}experts.{e}.w2.weight").T for e in range(cfg.num_experts)]),
                },
            }
        else:
            blk["mlp"] = {
                "w_gate": {"kernel": g(p + "mlp.gate_proj.weight").T},
                "w_up": {"kernel": g(p + "mlp.up_proj.weight").T},
                "w_down": {"kernel": g(p + "mlp.down_proj.weight").T},
            }
        return blk

    params: Dict[str, Any] = {
        "embed": {"embedding": g("model.embed_tokens.weight")},
        "final_norm": {"scale": g("model.norm.weight")},
        "layers": _stack(layer, cfg.num_layers),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": g("lm_head.weight").T}
    return params


def _convert_gpt2(state, cfg: TransformerConfig) -> Dict[str, Any]:
    h, hd, H = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads

    # HF sometimes prefixes with "transformer."
    g = _getter(state, ("", "transformer."))

    def layer(i):
        p = f"h.{i}."
        # GPT-2 Conv1D stores [in, out] (already flax orientation)
        ca_w, ca_b = g(p + "attn.c_attn.weight"), g(p + "attn.c_attn.bias")
        q_w, k_w, v_w = np.split(ca_w, 3, axis=1)
        q_b, k_b, v_b = np.split(ca_b, 3)
        return {
            "attn_norm": {"scale": g(p + "ln_1.weight"), "bias": g(p + "ln_1.bias")},
            "mlp_norm": {"scale": g(p + "ln_2.weight"), "bias": g(p + "ln_2.bias")},
            "attn": {
                "wq": {"kernel": q_w.reshape(h, H, hd), "bias": q_b.reshape(H, hd)},
                "wk": {"kernel": k_w.reshape(h, H, hd), "bias": k_b.reshape(H, hd)},
                "wv": {"kernel": v_w.reshape(h, H, hd), "bias": v_b.reshape(H, hd)},
                "wo": {"kernel": g(p + "attn.c_proj.weight").reshape(H, hd, h),
                       "bias": g(p + "attn.c_proj.bias")},
            },
            "mlp": {
                "w_up": {"kernel": g(p + "mlp.c_fc.weight"), "bias": g(p + "mlp.c_fc.bias")},
                "w_down": {"kernel": g(p + "mlp.c_proj.weight"), "bias": g(p + "mlp.c_proj.bias")},
            },
        }

    return {
        "embed": {"embedding": g("wte.weight")},
        "pos_embed": g("wpe.weight"),
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
        "layers": _stack(layer, cfg.num_layers),
    }


def _convert_opt(state, cfg: TransformerConfig) -> Dict[str, Any]:
    h, hd, H = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads

    # checkpoints may or may not carry the top-level "model." prefix
    g = _getter(state, ("model.", ""))

    def layer(i):
        p = f"decoder.layers.{i}."
        return {
            "attn_norm": {"scale": g(p + "self_attn_layer_norm.weight"),
                          "bias": g(p + "self_attn_layer_norm.bias")},
            "mlp_norm": {"scale": g(p + "final_layer_norm.weight"),
                         "bias": g(p + "final_layer_norm.bias")},
            "attn": {
                "wq": {"kernel": g(p + "self_attn.q_proj.weight").T.reshape(h, H, hd),
                       "bias": g(p + "self_attn.q_proj.bias").reshape(H, hd)},
                "wk": {"kernel": g(p + "self_attn.k_proj.weight").T.reshape(h, H, hd),
                       "bias": g(p + "self_attn.k_proj.bias").reshape(H, hd)},
                "wv": {"kernel": g(p + "self_attn.v_proj.weight").T.reshape(h, H, hd),
                       "bias": g(p + "self_attn.v_proj.bias").reshape(H, hd)},
                "wo": {"kernel": g(p + "self_attn.out_proj.weight").T.reshape(H, hd, h),
                       "bias": g(p + "self_attn.out_proj.bias")},
            },
            "mlp": {
                "w_up": {"kernel": g(p + "fc1.weight").T, "bias": g(p + "fc1.bias")},
                "w_down": {"kernel": g(p + "fc2.weight").T, "bias": g(p + "fc2.bias")},
            },
        }

    params: Dict[str, Any] = {
        "embed": {"embedding": g("decoder.embed_tokens.weight")},
        # OPT's learned positions carry a legacy offset of 2 rows
        "pos_embed": g("decoder.embed_positions.weight")[2:],
        "final_norm": {"scale": g("decoder.final_layer_norm.weight"),
                       "bias": g("decoder.final_layer_norm.bias")},
        "layers": _stack(layer, cfg.num_layers),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.asarray(state["lm_head.weight"]).T}
    return params


def _convert_falcon(state, cfg: TransformerConfig) -> Dict[str, Any]:
    h, hd, H, Hkv = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads, cfg.kv_heads
    g = _getter(state, ("transformer.", ""))

    def layer(i):
        p = f"h.{i}."
        # fused qkv rows: H query heads, then Hkv key heads, then Hkv value
        qkv = g(p + "self_attention.query_key_value.weight")  # [(H+2Hkv)*hd, h]
        wq = qkv[: H * hd]
        wk = qkv[H * hd: (H + Hkv) * hd]
        wv = qkv[(H + Hkv) * hd:]
        attn = {
            "wq": {"kernel": wq.T.reshape(h, H, hd)},
            "wk": {"kernel": wk.T.reshape(h, Hkv, hd)},
            "wv": {"kernel": wv.T.reshape(h, Hkv, hd)},
            "wo": {"kernel": g(p + "self_attention.dense.weight").T.reshape(H, hd, h)},
        }
        return {
            # parallel block: ONE shared input layernorm (no mlp_norm)
            "attn_norm": {"scale": g(p + "input_layernorm.weight"),
                          "bias": g(p + "input_layernorm.bias")},
            "attn": attn,
            "mlp": {
                "w_up": {"kernel": g(p + "mlp.dense_h_to_4h.weight").T},
                "w_down": {"kernel": g(p + "mlp.dense_4h_to_h.weight").T},
            },
        }

    params: Dict[str, Any] = {
        "embed": {"embedding": g("word_embeddings.weight")},
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
        "layers": _stack(layer, cfg.num_layers),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.asarray(state["lm_head.weight"]).T}
    return params


def _convert_phi(state, cfg: TransformerConfig) -> Dict[str, Any]:
    h, hd, H = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads
    g = _getter(state, ("model.", ""))

    def layer(i):
        p = f"layers.{i}."
        return {
            # parallel block: ONE shared input layernorm
            "attn_norm": {"scale": g(p + "input_layernorm.weight"),
                          "bias": g(p + "input_layernorm.bias")},
            "attn": {
                "wq": {"kernel": g(p + "self_attn.q_proj.weight").T.reshape(h, H, hd),
                       "bias": g(p + "self_attn.q_proj.bias").reshape(H, hd)},
                "wk": {"kernel": g(p + "self_attn.k_proj.weight").T.reshape(h, H, hd),
                       "bias": g(p + "self_attn.k_proj.bias").reshape(H, hd)},
                "wv": {"kernel": g(p + "self_attn.v_proj.weight").T.reshape(h, H, hd),
                       "bias": g(p + "self_attn.v_proj.bias").reshape(H, hd)},
                "wo": {"kernel": g(p + "self_attn.dense.weight").T.reshape(H, hd, h),
                       "bias": g(p + "self_attn.dense.bias")},
            },
            "mlp": {
                "w_up": {"kernel": g(p + "mlp.fc1.weight").T, "bias": g(p + "mlp.fc1.bias")},
                "w_down": {"kernel": g(p + "mlp.fc2.weight").T, "bias": g(p + "mlp.fc2.bias")},
            },
        }

    params: Dict[str, Any] = {
        "embed": {"embedding": g("embed_tokens.weight")},
        "final_norm": {"scale": g("final_layernorm.weight"),
                       "bias": g("final_layernorm.bias")},
        "layers": _stack(layer, cfg.num_layers),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.asarray(state["lm_head.weight"]).T,
                             "bias": np.asarray(state["lm_head.bias"])}
    return params


def _split_fused_qkv_per_head(w, b, H, Hkv, hd, h):
    """Split a per-head-interleaved fused QKV (gpt-neox/bloom pattern —
    reference ``module_inject/fusedqkv_utils.py:29`` ``prepare_tp_fused_qkvw``
    'glmtype'/'bloomtype' orderings): rows are [head0: q,k,v | head1: ...].
    Returns the attn param subtree in flax orientation."""
    if H != Hkv:
        raise ValueError("per-head fused QKV with GQA is not a pattern these families use")
    wr = w.reshape(H, 3, hd, h)
    attn = {
        "wq": {"kernel": wr[:, 0].reshape(H * hd, h).T.reshape(h, H, hd)},
        "wk": {"kernel": wr[:, 1].reshape(H * hd, h).T.reshape(h, H, hd)},
        "wv": {"kernel": wr[:, 2].reshape(H * hd, h).T.reshape(h, H, hd)},
    }
    if b is not None:
        br = b.reshape(H, 3, hd)
        attn["wq"]["bias"] = br[:, 0]
        attn["wk"]["bias"] = br[:, 1]
        attn["wv"]["bias"] = br[:, 2]
    return attn


def _neox_style_layers(state, cfg: TransformerConfig, g, layer_prefix: str,
                       attn_prefix: str) -> Dict[str, Any]:
    """Shared layer conversion for the gpt-neox/bloom graph (per-head fused
    QKV, biased dense/MLP, two layernorms); only key prefixes differ."""
    h, hd, H = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads

    def layer(i):
        p = layer_prefix.format(i)
        a = p + attn_prefix
        attn = _split_fused_qkv_per_head(
            g(a + "query_key_value.weight"), g(a + "query_key_value.bias"),
            H, H, hd, h)
        attn["wo"] = {"kernel": g(a + "dense.weight").T.reshape(H, hd, h),
                      "bias": g(a + "dense.bias")}
        return {
            "attn_norm": {"scale": g(p + "input_layernorm.weight"),
                          "bias": g(p + "input_layernorm.bias")},
            "mlp_norm": {"scale": g(p + "post_attention_layernorm.weight"),
                         "bias": g(p + "post_attention_layernorm.bias")},
            "attn": attn,
            "mlp": {
                "w_up": {"kernel": g(p + "mlp.dense_h_to_4h.weight").T,
                         "bias": g(p + "mlp.dense_h_to_4h.bias")},
                "w_down": {"kernel": g(p + "mlp.dense_4h_to_h.weight").T,
                           "bias": g(p + "mlp.dense_4h_to_h.bias")},
            },
        }

    return _stack(layer, cfg.num_layers)


def _convert_gpt_neox(state, cfg: TransformerConfig) -> Dict[str, Any]:
    g = _getter(state, ("gpt_neox.", ""))
    params: Dict[str, Any] = {
        "embed": {"embedding": g("embed_in.weight")},
        "final_norm": {"scale": g("final_layer_norm.weight"),
                       "bias": g("final_layer_norm.bias")},
        "layers": _neox_style_layers(state, cfg, g, "layers.{}.", "attention."),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.asarray(state["embed_out.weight"]).T}
    return params


def _convert_bloom(state, cfg: TransformerConfig) -> Dict[str, Any]:
    g = _getter(state, ("transformer.", ""))
    return {
        "embed": {"embedding": g("word_embeddings.weight")},
        "embed_norm": {"scale": g("word_embeddings_layernorm.weight"),
                       "bias": g("word_embeddings_layernorm.bias")},
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
        "layers": _neox_style_layers(state, cfg, g, "h.{}.", "self_attention."),
    }


def _convert_gptj(state, cfg: TransformerConfig) -> Dict[str, Any]:
    h, hd, H = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads
    g = _getter(state, ("transformer.", ""))

    def layer(i):
        p = f"h.{i}."
        return {
            # parallel block: ONE shared ln_1 feeds attn and mlp
            "attn_norm": {"scale": g(p + "ln_1.weight"), "bias": g(p + "ln_1.bias")},
            "attn": {
                "wq": {"kernel": g(p + "attn.q_proj.weight").T.reshape(h, H, hd)},
                "wk": {"kernel": g(p + "attn.k_proj.weight").T.reshape(h, H, hd)},
                "wv": {"kernel": g(p + "attn.v_proj.weight").T.reshape(h, H, hd)},
                "wo": {"kernel": g(p + "attn.out_proj.weight").T.reshape(H, hd, h)},
            },
            "mlp": {
                "w_up": {"kernel": g(p + "mlp.fc_in.weight").T, "bias": g(p + "mlp.fc_in.bias")},
                "w_down": {"kernel": g(p + "mlp.fc_out.weight").T, "bias": g(p + "mlp.fc_out.bias")},
            },
        }

    params: Dict[str, Any] = {
        "embed": {"embedding": g("wte.weight")},
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
        "layers": _stack(layer, cfg.num_layers),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.asarray(state["lm_head.weight"]).T,
                             "bias": np.asarray(state["lm_head.bias"])}
    return params


def _convert_codegen(state, cfg: TransformerConfig) -> Dict[str, Any]:
    """CodeGen = the GPT-J graph with an mp_num-blocked fused QKV (reference
    ``module_inject/fusedqkv_utils.py:29`` 'codegentype'): qkv_proj rows are
    mp_num groups of [q_local | V_LOCAL | k_local] (query, value, key order
    inside each group, matching HF CodeGenAttention's split). The fused
    projection is de-fused into gpt-j-style q/k/v keys and the rest of the
    conversion delegates to :func:`_convert_gptj` — one layer mapping."""
    h = cfg.hidden_size
    g = _getter(state, ("transformer.", ""))
    mp_num = 4  # fixed in HF CodeGenAttention
    local = h // mp_num

    defused = {k: v for k, v in state.items() if "attn.qkv_proj" not in k}
    for i in range(cfg.num_layers):
        grouped = g(f"h.{i}.attn.qkv_proj.weight").reshape(mp_num, 3 * local, h)
        p = f"transformer.h.{i}.attn."
        defused[p + "q_proj.weight"] = grouped[:, :local].reshape(h, h)
        defused[p + "v_proj.weight"] = grouped[:, local: 2 * local].reshape(h, h)
        defused[p + "k_proj.weight"] = grouped[:, 2 * local:].reshape(h, h)
    return _convert_gptj(defused, cfg)


def _convert_gpt_bigcode(state, cfg: TransformerConfig) -> Dict[str, Any]:
    """GPT-BigCode / starcoder (reference ``module_inject`` bigcode
    containers): the gpt2 graph, but projections are nn.Linear ([out, in] —
    transposed vs gpt2's Conv1D) and with ``multi_query`` the fused c_attn
    packs [q(H*hd) | k(hd) | v(hd)] rows sharing ONE kv head."""
    h, hd, H, Hkv = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads, cfg.kv_heads
    g = _getter(state, ("", "transformer."))

    def layer(i):
        p = f"h.{i}."
        w, b = g(p + "attn.c_attn.weight"), g(p + "attn.c_attn.bias")
        if Hkv == 1:  # multi_query: [q(H*hd) | k(hd) | v(hd)] row blocks
            q_w, k_w, v_w = np.split(w, [H * hd, (H + 1) * hd], axis=0)
            q_b, k_b, v_b = np.split(b, [H * hd, (H + 1) * hd])
            q_w = q_w.T.reshape(h, H, hd)
            k_w, v_w = k_w.T.reshape(h, 1, hd), v_w.T.reshape(h, 1, hd)
        else:  # MHA: PER-HEAD [q_hd | k_hd | v_hd] blocks (HF comment: "the
            # memory layout is not the same as GPT2")
            per_head = w.reshape(H, 3 * hd, h)
            q_w, k_w, v_w = (per_head[:, s].transpose(2, 0, 1)
                             for s in (slice(0, hd), slice(hd, 2 * hd),
                                       slice(2 * hd, 3 * hd)))
            pb = b.reshape(H, 3 * hd)
            q_b, k_b, v_b = pb[:, :hd], pb[:, hd:2 * hd], pb[:, 2 * hd:]
        return {
            "attn_norm": {"scale": g(p + "ln_1.weight"), "bias": g(p + "ln_1.bias")},
            "mlp_norm": {"scale": g(p + "ln_2.weight"), "bias": g(p + "ln_2.bias")},
            "attn": {
                "wq": {"kernel": q_w, "bias": q_b.reshape(H, hd)},
                "wk": {"kernel": k_w, "bias": k_b.reshape(Hkv, hd)},
                "wv": {"kernel": v_w, "bias": v_b.reshape(Hkv, hd)},
                "wo": {"kernel": g(p + "attn.c_proj.weight").T.reshape(H, hd, h),
                       "bias": g(p + "attn.c_proj.bias")},
            },
            "mlp": {
                "w_up": {"kernel": g(p + "mlp.c_fc.weight").T, "bias": g(p + "mlp.c_fc.bias")},
                "w_down": {"kernel": g(p + "mlp.c_proj.weight").T, "bias": g(p + "mlp.c_proj.bias")},
            },
        }

    return {
        "embed": {"embedding": g("wte.weight")},
        "pos_embed": g("wpe.weight"),
        "final_norm": {"scale": g("ln_f.weight"), "bias": g("ln_f.bias")},
        "layers": _stack(layer, cfg.num_layers),
    }


_GLU_MATRICES = (("gate_proj", "w_gate"), ("up_proj", "w_up"), ("down_proj", "w_down"))  # HF, ours


def _latent_moe_layer_names(cfg: TransformerConfig, dense: bool):
    """One layer of a latent-attention routed decoder as ``(HF name under
    model.layers.<i>., path in the layer's tree, shape of the leaf)``: torch
    ``Linear`` stores ``[out, in]``, so a matrix is the leaf transposed and
    reshaped. ``kv_b_proj`` is kept WHOLE (``wkv_b`` [rank, H, nope + v]);
    serving slices its two parts where it absorbs them."""
    h, H = cfg.hidden_size, cfg.num_heads
    qk, nope, vd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim
    rank, rope_d = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    names = [
        ("input_layernorm.weight", ("attn_norm", "scale"), (h,)),
        ("post_attention_layernorm.weight", ("mlp_norm", "scale"), (h,)),
        ("self_attn.kv_a_proj_with_mqa.weight", ("attn", "wkv_a", "kernel"), (h, rank + rope_d)),
        ("self_attn.kv_a_layernorm.weight", ("attn", "kv_norm", "scale"), (rank,)),
        ("self_attn.kv_b_proj.weight", ("attn", "wkv_b", "kernel"), (rank, H, nope + vd)),
        # the one matrix whose INPUT is the split side: [hidden, H*v] stored
        ("self_attn.o_proj.weight", ("attn", "wo", "kernel"), (H * vd, h)),
        ("self_attn.q_a_proj.weight", ("attn", "wq_a", "kernel"), (h, cfg.q_lora_rank)),
        ("self_attn.q_a_layernorm.weight", ("attn", "q_norm", "scale"), (cfg.q_lora_rank,)),
        ("self_attn.q_b_proj.weight", ("attn", "wq_b", "kernel"), (cfg.q_lora_rank, H, qk)),
    ]
    if cfg.index_topk:  # the indexer's four leaves (``glm_moe_dsa``)
        Hi, Di = cfg.index_heads, cfg.index_head_dim
        names += [("self_attn.indexer.wq_b.weight", ("attn", "idx_wq", "kernel"), (cfg.q_lora_rank, Hi, Di)),
                  ("self_attn.indexer.wk.weight", ("attn", "idx_wk", "kernel"), (h, Di)),
                  ("self_attn.indexer.k_norm.weight", ("attn", "idx_k_norm", "scale"), (Di,)),
                  ("self_attn.indexer.k_norm.bias", ("attn", "idx_k_norm", "bias"), (Di,)),
                  ("self_attn.indexer.weights_proj.weight", ("attn", "idx_w", "kernel"), (h, Hi))]
    if dense:
        f = cfg.intermediate_size
        return names + [(f"mlp.{hf}.weight", ("mlp", ours, "kernel"), (f, h) if ours == "w_down" else (h, f))
                        for hf, ours in _GLU_MATRICES]
    f = cfg.expert_width
    fs = f * cfg.moe_shared_experts
    names += [("mlp.gate.weight", ("moe", "gate", "wg", "kernel"), (h, cfg.router_experts)),
              ("mlp.gate.e_score_correction_bias", ("moe", "gate", "e_bias"), (cfg.router_experts,))]
    if fs:
        names += [(f"mlp.shared_experts.{hf}.weight", ("moe", "shared", ours, "kernel"),
                   (fs, h) if ours == "w_down" else (h, fs)) for hf, ours in _GLU_MATRICES]
    return names


def _to_leaf(w, shape):
    """A torch tensor as a leaf: a matrix [out, in] transposed to ``shape``
    [in, out...], a vector as it is. (``wo`` is then split into heads by the
    caller's reshape: its leaf is [H, v, hidden].)"""
    w = np.asarray(w)
    return (w.T if w.ndim == 2 else w).reshape(shape)


def _from_leaf(a, shape):
    """The inverse of :func:`_to_leaf`: ``shape[0]`` is the matrix's input side."""
    a = np.asarray(a)
    return a.reshape(shape) if len(shape) == 1 else a.reshape(shape[0], -1).T


def _set(tree, path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _convert_glm4_moe_lite(state, cfg: TransformerConfig) -> Dict[str, Any]:
    """Leading dense layers as ``dense_<i>``, the routed stack stacked under
    ``layers``; the keys of layers past ``num_layers`` (the next-token-
    prediction layer) are not read."""
    g = _getter(state, ("",))

    def layer(i):
        p, dense = f"model.layers.{i}.", i < cfg.first_dense_layers
        blk: Dict[str, Any] = {}
        for hf, path, shape in _latent_moe_layer_names(cfg, dense):
            _set(blk, path, _to_leaf(g(p + hf), shape))
        wo = blk["attn"]["wo"]
        wo["kernel"] = wo["kernel"].reshape(cfg.num_heads, cfg.v_head_dim, cfg.hidden_size)
        if not dense:
            for hf, ours in _GLU_MATRICES:
                blk["moe"].setdefault("experts", {})[ours] = np.stack(  # (of a chip's share: the experts held here)
                    [g(f"{p}mlp.experts.{cfg.first_expert + e}.{hf}.weight").T for e in range(cfg.num_experts)])
        return blk

    D = cfg.first_dense_layers
    params: Dict[str, Any] = {
        "embed": {"embedding": g("model.embed_tokens.weight")},
        "final_norm": {"scale": g("model.norm.weight")},
        "layers": _stack(lambda i: layer(D + i), cfg.num_layers - D),
    }
    for i in range(D):
        params[f"dense_{i}"] = layer(i)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": g("lm_head.weight").T}
    return params


def latent_moe_hf_state(params, cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """The way back: a parameter tree of that family under its HF names."""
    D = cfg.first_dense_layers
    state = {"model.embed_tokens.weight": np.asarray(params["embed"]["embedding"]),
             "model.norm.weight": np.asarray(params["final_norm"]["scale"])}
    if not cfg.tie_embeddings:
        state["lm_head.weight"] = np.asarray(params["lm_head"]["kernel"]).T
    for i in range(cfg.num_layers):
        dense = i < D
        p = f"model.layers.{i}."
        for hf, path, shape in _latent_moe_layer_names(cfg, dense):
            leaf = params[f"dense_{i}"] if dense else params["layers"]
            for key in path:
                leaf = leaf[key]
            state[p + hf] = _from_leaf(leaf if dense else leaf[i - D], shape)
        if not dense:
            for hf, ours in _GLU_MATRICES:
                stacked = np.asarray(params["layers"]["moe"]["experts"][ours][i - D])
                for e in range(cfg.num_experts):
                    state[f"{p}mlp.experts.{cfg.first_expert + e}.{hf}.weight"] = stacked[e].T
    return state


# glm_moe_dsa under its published leaf names: glm4_moe_lite's, and the indexer's four leaves a layer, which
# ``_latent_moe_layer_names`` lists where the config has an indexer (``self_attn.indexer.{wq_b, wk, k_norm,
# weights_proj}``); a chip's share reads experts ``first_expert ..`` of the checkpoint's
_convert_glm_moe_dsa = _convert_glm4_moe_lite


def _evabyte_names(cfg: TransformerConfig):
    """EvaByte under its HF names as ``(HF name, path in our tree, shape of
    the leaf)``, ``{i}`` the layer: llama's names, plus the two pooling vectors
    a head and the head over ``num_pred_heads * vocab`` columns."""
    h, hd, H, Hkv, f = (cfg.hidden_size, cfg.dims_per_head, cfg.num_heads, cfg.kv_heads,
                        cfg.intermediate_size)
    attn = "model.layers.{i}.self_attn."
    layer = [
        ("model.layers.{i}.input_layernorm.weight", ("attn_norm", "scale"), (h,)),
        ("model.layers.{i}.post_attention_layernorm.weight", ("mlp_norm", "scale"), (h,)),
        (attn + "q_proj.weight", ("attn", "wq", "kernel"), (h, H, hd)),
        (attn + "k_proj.weight", ("attn", "wk", "kernel"), (h, Hkv, hd)),
        (attn + "v_proj.weight", ("attn", "wv", "kernel"), (h, Hkv, hd)),
        # the one matrix whose INPUT is the split side: [hidden, H*hd] stored
        (attn + "o_proj.weight", ("attn", "wo", "kernel"), (H * hd, h)),
        (attn + "adaptive_phi", ("attn", "phi"), (Hkv, hd)),
        (attn + "adaptive_mu_k", ("attn", "mu"), (Hkv, hd)),
    ] + [("model.layers.{i}.mlp.%s.weight" % hf, ("mlp", ours, "kernel"), (f, h) if ours == "w_down" else (h, f))
         for hf, ours in _GLU_MATRICES]
    top = [("model.embed_tokens.weight", ("embed", "embedding"), (cfg.vocab_size, h)),
           ("model.norm.weight", ("final_norm", "scale"), (h,)),
           ("lm_head.weight", ("lm_head", "kernel"), (h, cfg.num_pred_heads * cfg.vocab_size))]
    return top, layer


def _convert_evabyte(state, cfg: TransformerConfig) -> Dict[str, Any]:
    g = _getter(state, ("",))
    top, per_layer = _evabyte_names(cfg)

    def leaf(name, path, shape):
        w = np.asarray(g(name))
        if path[0] == "embed":
            return w  # a lookup table, stored [vocab, hidden] as we keep it
        return w.reshape(shape) if "adaptive" in name else _to_leaf(w, shape)

    def layer(i):
        blk: Dict[str, Any] = {}
        for hf, path, shape in per_layer:
            _set(blk, path, leaf(hf.format(i=i), path, shape))
        wo = blk["attn"]["wo"]
        wo["kernel"] = wo["kernel"].reshape(cfg.num_heads, cfg.dims_per_head, cfg.hidden_size)
        return blk

    params: Dict[str, Any] = {"layers": _stack(layer, cfg.num_layers)}
    for hf, path, shape in top:
        _set(params, path, leaf(hf, path, shape))
    return params


def evabyte_hf_state(params, cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """The way back: an EvaByte parameter tree under its HF names."""
    top, per_layer = _evabyte_names(cfg)

    def stored(name, path, a, shape):
        a = np.asarray(a)
        if path[0] == "embed" or "adaptive" in name:
            return a.reshape(shape)
        return _from_leaf(a, shape)

    state = {hf: stored(hf, path, _at(params, path), shape) for hf, path, shape in top}
    for i in range(cfg.num_layers):
        for hf, path, shape in per_layer:
            state[hf.format(i=i)] = stored(hf, path, _at(params["layers"], path)[i], shape)
    return state


# --- qwen3_next: a routed pattern of Gated DeltaNet and gated attention layers ---
#
# The checkpoint's ``linear_attn.in_proj_qkvz`` and ``in_proj_ba`` INTERLEAVE
# their output rows by key-head group (a group's q, k, then its value heads' v,
# then their z; a group's b, then a); the program and the plain reference keep
# them plain, ``[q | k | v | z]`` and ``[b | a]`` (``ops/gdn.py``). The two
# functions below give, for each row of the plain order, its row in the
# checkpoint. Layer ``i`` of the model is ``layers/layer_<i % P>`` at index
# ``i // P`` of its stacked leaves (``P`` the period). A chip's share
# (``expert_parallel``) takes experts ``first_expert ..`` of the checkpoint's;
# the multi-token-prediction layer (``mtp.*``) is not read.

def _qkvz_rows(g) -> np.ndarray:
    rep = g.n_v_heads // g.n_k_heads
    group = 2 * g.head_k_dim + 2 * rep * g.head_v_dim
    starts = np.arange(g.n_k_heads)[:, None] * group
    q = starts + np.arange(g.head_k_dim)[None, :]
    k = q + g.head_k_dim
    v = starts + 2 * g.head_k_dim + np.arange(rep * g.head_v_dim)[None, :]
    z = v + rep * g.head_v_dim
    return np.concatenate([a.reshape(-1) for a in (q, k, v, z)])


def _ba_rows(g) -> np.ndarray:
    rep = g.n_v_heads // g.n_k_heads
    b = np.arange(g.n_k_heads)[:, None] * 2 * rep + np.arange(rep)[None, :]
    return np.concatenate([b.reshape(-1), (b + rep).reshape(-1)])


def _interleaved_rows(hf: str, g) -> Optional[np.ndarray]:
    """For a checkpoint tensor whose rows are interleaved, the checkpoint's row of each row of the plain order."""
    if hf.endswith("in_proj_qkvz.weight"):
        return _qkvz_rows(g)
    return _ba_rows(g) if hf.endswith("in_proj_ba.weight") else None


def _qwen3_next_names(cfg: TransformerConfig, kind: str):
    """A layer of ``kind`` under its HF names as ``(HF name, path in our tree,
    shape of the leaf)``, the routed experts apart."""
    h, hd, H, Hkv, g = cfg.hidden_size, cfg.dims_per_head, cfg.num_heads, cfg.kv_heads, cfg.gdn
    fs = cfg.expert_width * cfg.moe_shared_experts
    names = [("post_attention_layernorm.weight", ("mlp_norm", "scale"), (h,)),
             ("mlp.gate.weight", ("moe", "gate", "wg", "kernel"), (h, cfg.router_experts)),
             ("mlp.shared_expert_gate.weight", ("moe", "shared_gate", "kernel"), (h, 1))]
    names += [(f"mlp.shared_expert.{hf}.weight", ("moe", "shared", ours, "kernel"),
               (fs, h) if ours == "w_down" else (h, fs)) for hf, ours in _GLU_MATRICES]
    if kind == "attention":
        return names + [
            ("input_layernorm.weight", ("attn_norm", "scale"), (h,)),
            ("self_attn.q_proj.weight", ("attn", "wq", "kernel"), (h, H, 2 * hd)),
            ("self_attn.k_proj.weight", ("attn", "wk", "kernel"), (h, Hkv, hd)),
            ("self_attn.v_proj.weight", ("attn", "wv", "kernel"), (h, Hkv, hd)),
            ("self_attn.o_proj.weight", ("attn", "wo", "kernel"), (H * hd, h)),  # (split into heads by the caller)
            ("self_attn.q_norm.weight", ("attn", "q_norm", "scale"), (hd,)),
            ("self_attn.k_norm.weight", ("attn", "k_norm", "scale"), (hd,))]
    return names + [
        ("input_layernorm.weight", ("gdn_pre_norm", "scale"), (h,)),
        ("linear_attn.in_proj_qkvz.weight", ("gdn", "gdn_in_proj", "kernel"), (h, g.proj_dim)),
        ("linear_attn.in_proj_ba.weight", ("gdn", "gdn_ba_proj", "kernel"), (h, 2 * g.n_v_heads)),
        ("linear_attn.conv1d.weight", ("gdn", "gdn_conv"), (g.d_conv, g.conv_dim)),
        ("linear_attn.A_log", ("gdn", "A_log"), (g.n_v_heads,)),
        ("linear_attn.dt_bias", ("gdn", "dt_bias"), (g.n_v_heads,)),
        ("linear_attn.norm.weight", ("gdn", "gdn_norm", "scale"), (g.head_v_dim,)),
        ("linear_attn.out_proj.weight", ("gdn", "gdn_out_proj", "kernel"), (g.value_dim, h))]


def _convert_qwen3_next(state, cfg: TransformerConfig) -> Dict[str, Any]:
    get = _getter(state, ("",))
    P, first = len(cfg.period), cfg.first_expert

    def layer(i):
        p, kind = f"model.layers.{i}.", cfg.layer_types[i]
        blk: Dict[str, Any] = {}
        for hf, path, shape in _qwen3_next_names(cfg, kind):
            w, rows = get(p + hf), _interleaved_rows(hf, cfg.gdn)
            if rows is not None:
                w = w[rows]
            elif hf.endswith("conv1d.weight"):
                w = w[:, 0, :]  # torch's depthwise [channels, 1, taps]
            _set(blk, path, _to_leaf(w, shape))
        if kind == "attention":
            wo = blk["attn"]["wo"]
            wo["kernel"] = wo["kernel"].reshape(cfg.num_heads, cfg.dims_per_head, cfg.hidden_size)
        for hf, ours in _GLU_MATRICES:
            blk["moe"].setdefault("experts", {})[ours] = np.stack(
                [get(f"{p}mlp.experts.{first + e}.{hf}.weight").T for e in range(cfg.num_experts)])
        return blk

    params: Dict[str, Any] = {
        "embed": {"embedding": get("model.embed_tokens.weight")},
        "final_norm": {"scale": get("model.norm.weight")},
        "layers": {f"layer_{j}": _stack(lambda n, j=j: layer(n * P + j), cfg.num_layers // P) for j in range(P)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": get("lm_head.weight").T}
    return params


def qwen3_next_hf_state(params, cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """The way back: a ``qwen3_next`` parameter tree under its HF names (a
    chip's share writes its experts under their numbers in the router)."""
    P, first = len(cfg.period), cfg.first_expert
    state = {"model.embed_tokens.weight": np.asarray(params["embed"]["embedding"]),
             "model.norm.weight": np.asarray(params["final_norm"]["scale"])}
    if not cfg.tie_embeddings:
        state["lm_head.weight"] = np.asarray(params["lm_head"]["kernel"]).T
    for i in range(cfg.num_layers):
        p, kind, blk, n = f"model.layers.{i}.", cfg.layer_types[i], params["layers"][f"layer_{i % P}"], i // P
        for hf, path, shape in _qwen3_next_names(cfg, kind):
            w, rows = _from_leaf(np.asarray(_at(blk, path))[n], shape), _interleaved_rows(hf, cfg.gdn)
            if rows is not None:
                w = w[np.argsort(rows)]
            elif hf.endswith("conv1d.weight"):
                w = w[:, None, :]
            state[p + hf] = w
        for hf, ours in _GLU_MATRICES:
            stacked = np.asarray(blk["moe"]["experts"][ours])[n]
            for e in range(cfg.num_experts):
                state[f"{p}mlp.experts.{first + e}.{hf}.weight"] = stacked[e].T
    return state


_CONVERTERS = {
    "qwen3_next": _convert_qwen3_next,
    "evabyte": _convert_evabyte,
    "glm4_moe_lite": _convert_glm4_moe_lite,
    "glm_moe_dsa": _convert_glm_moe_dsa,
    "llama": _convert_llama,
    "mistral": _convert_llama,
    "mixtral": _convert_llama,
    "qwen2": _convert_llama,  # llama graph + qkv biases (handled by presence)
    "gpt2": _convert_gpt2,
    "opt": _convert_opt,
    "falcon": _convert_falcon,
    "phi": _convert_phi,
    "gpt_neox": _convert_gpt_neox,
    "bloom": _convert_bloom,
    "gptj": _convert_gptj,
    "codegen": _convert_codegen,
    "gpt_bigcode": _convert_gpt_bigcode,
}


def convert_hf_state(
    state: Dict[str, np.ndarray],
    config: TransformerConfig,
    family: Optional[str] = None,
) -> Dict[str, Any]:
    """HF state dict -> CausalLM stacked-scan param pytree."""
    family = family or detect_family(state)
    if family not in _CONVERTERS:
        raise ValueError(f"unsupported family {family!r}; supported: {sorted(_CONVERTERS)}")
    return _CONVERTERS[family](state, config)


def load_hf_checkpoint(
    path: str,
    config: Optional[TransformerConfig] = None,
    family: Optional[str] = None,
) -> Tuple[TransformerConfig, Dict[str, Any]]:
    """One-call ingestion: checkpoint dir (config.json + safetensors) ->
    (TransformerConfig, params) ready for ``initialize(model_parameters=...)``
    or ``init_inference(params=...)``."""
    if config is None:
        cfg_path = os.path.join(path, "config.json") if os.path.isdir(path) else None
        if cfg_path is None or not os.path.exists(cfg_path):
            raise ValueError("pass config= or point at a dir containing config.json")
        with open(cfg_path) as f:
            config = config_from_hf(json.load(f))
    state = load_safetensors_state(path)
    params = convert_hf_state(state, config, family=family)
    return config, params
