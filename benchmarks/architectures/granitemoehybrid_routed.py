"""What the benchmark knows about how the PROGRAM lays the ROUTED
``granitemoehybrid`` out (``num_local_experts`` > 0: after every mixer a routed
layer beside one shared MLP) and how the architecture's work is counted: its
parameter tree under the names ``benchmarks/reference/
granitemoehybrid_routed.py`` reads, which keys of a published config are
widths, parameter counts and attention shapes from such a config, because the
architecture is ROUTED how the program's own expert picks come out of the
``put`` path and of the decode chain (PERF.md, section 7), and what its two
mechanisms cost by the mathematics alone: the state-space mixers of a decode
step (``ssm_decode_cost``) and a chunked layer-call of a prompt
(``ssd_scan_cost``), and the routed layers of a decode step as ONE CHIP'S
SHARE reads them (``routed_decode_cost``).

``num_local_experts`` in a configuration is the number of experts HELD by the
chip (``reduced``); with ``expert_parallel: {size, rank}`` the router scores
``size`` times as many, and the picks are numbered over all of them.
``intermediate_size`` is the width of ONE expert, ``shared_intermediate_size``
the shared MLP's.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Tuple

BF16 = 2


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_architecture_" + name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the dense member's file beside this one: the mixers, the pattern and the state are the same, counted once
dense = _beside("granitemoehybrid")
layers, ssm_layers, attention_layers = dense.layers, dense.ssm_layers, dense.attention_layers
heads, kv_heads, head_dim = dense.heads, dense.kv_heads, dense.head_dim
ssm_matmul_params, ssm_params, attention_params = dense.ssm_matmul_params, dense.ssm_params, dense.attention_params
# what one sequence keeps in one state-space layer; (FLOPs, bytes) of the mixers of decode steps (a live row's
# state and tail read and written once a layer, the mixers' weights once a step); of ONE chunked layer-call
state_bytes, ssm_decode_cost, ssd_scan_cost = dense.state_bytes, dense.ssm_decode_cost, dense.ssd_scan_cost

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = dense.WIDTH_KEYS + ("num_experts_per_tok", "expert_parallel")


def routed_layers(cfg: dict) -> int:
    """More than 0 says: decide ``correct`` at the program's own expert picks."""
    return cfg["num_hidden_layers"]


def held_experts(cfg: dict) -> int:
    return cfg["num_local_experts"]


def routed_experts(cfg: dict) -> int:
    """The router's width, the published numbering of the picks: the experts
    held here times the chips that share a layer."""
    return cfg["num_local_experts"] * int((cfg.get("expert_parallel") or {"size": 1})["size"])


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: dict) -> int:
    """The shared MLP (no gate)."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * routed_experts(cfg)


def _params(cfg: dict, experts: float) -> float:
    """Every mixer and, a layer, the router, the shared MLP and ``experts`` experts."""
    return (ssm_layers(cfg) * ssm_params(cfg) + attention_layers(cfg) * attention_params(cfg)
            + layers(cfg) * (router_params(cfg) + shared_params(cfg) + experts * expert_params(cfg)))


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product HERE: every mixer's
    projections, the router, the shared MLP, its own experts' share of this
    chip (``experts_per_token`` over the chips that share a layer) and the
    (tied) head."""
    here = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    return int(ssm_layers(cfg) * ssm_matmul_params(cfg) + attention_layers(cfg) * attention_params(cfg)
               + layers(cfg) * (router_params(cfg) + shared_params(cfg) + here * expert_params(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    embed = (1 if cfg.get("tie_word_embeddings") else 2) * cfg["vocab_size"] * h
    return int(_params(cfg, held_experts(cfg))) + layers(cfg) * 2 * h + embed + h


def reference_weights(params) -> dict:
    """The program's parameter tree (``layers/layer_<j>``: the ``j``-th layer
    of a period, its leaves stacked over the periods) under the names the plain
    reference reads. Relabelling only: the arrays are the program's own,
    whatever their dtype and placement."""

    def one(layer):
        moe = layer["moe"]
        out = {"norm2": layer["mlp_norm"]["scale"], "router": moe["gate"]["wg"]["kernel"],
               "w_gate": moe["experts"]["w_gate"], "w_up": moe["experts"]["w_up"],
               "w_down": moe["experts"]["w_down"], "shared_gate": moe["shared"]["w_gate"]["kernel"],
               "shared_up": moe["shared"]["w_up"]["kernel"], "shared_down": moe["shared"]["w_down"]["kernel"]}
        if "ssm" in layer:
            m = layer["ssm"]
            return dict(out, norm1=layer["ssm_pre_norm"]["scale"], w_in=m["ssm_in_proj"]["kernel"],
                        conv_w=m["ssm_conv"]["kernel"], conv_b=m["ssm_conv"]["bias"], A_log=m["A_log"],
                        dt_bias=m["dt_bias"], D=m["D"], norm_w=m["ssm_norm"]["scale"],
                        w_out=m["ssm_out_proj"]["kernel"])
        a = layer["attn"]
        return dict(out, norm1=layer["attn_norm"]["scale"], wq=a["wq"]["kernel"], wk=a["wk"]["kernel"],
                    wv=a["wv"]["kernel"], wo=a["wo"]["kernel"])

    stack = params["layers"]
    period = [one(stack[k]) for k in sorted(stack, key=lambda k: int(k.rpartition("_")[2]))]
    return {"embed": params["embed"]["embedding"], "final_norm": params["final_norm"]["scale"],
            "period": period}


def put_with_picks(engine, uids, fed):
    """``engine.put`` itself, and the picks its compiled step wrote beside the
    logits: ``picks[i]`` int32 ``[len(fed[i]), routed_layers, k]``, in the
    router's numbering (every chip's experts)."""
    return engine.put_with_picks(uids, fed)


def generate_with_picks(engine, prompts, max_new_tokens):
    """``engine.generate`` itself, and the picks its fused prefill and decode
    chains wrote beside the tokens, fetched after it has returned."""
    return engine.generate_with_picks(prompts, max_new_tokens=max_new_tokens)


# --- what a share's routed layers cost, by the mathematics alone -------------------------------

def routed_decode_cost(cfg: dict, experts_read: float, token_steps: float,
                       layer_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed feed-forward layers of decode steps, as
    THIS CHIP's share of them: ``experts_read`` the sum over steps and routed
    layers of the DISTINCT HELD experts the live rows picked (each read once a
    step, whoever shares it; the program's ``experts_touched`` counts held
    experts), ``token_steps`` the live rows summed over steps and routed
    layers, ``layer_steps`` the (step, routed layer) pairs. Beside the experts
    each pair reads the shared MLP and the router (all its columns) once; a
    token does the router's and the shared MLP's products and those of its
    visits to HELD experts, ``experts_per_token`` over the chips that share a
    layer on average."""
    expert = expert_params(cfg)
    always = shared_params(cfg) + router_params(cfg)
    visits = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    flops = 2.0 * token_steps * (visits * expert + always)
    bytes_ = (experts_read * expert + layer_steps * always) * BF16
    return flops, bytes_
