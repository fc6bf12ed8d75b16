"""The seam to the system under test: its model config from a published
``config.json``, through the mapping it applies to checkpoints of every
architecture it loads. How an architecture's parameter tree is relabelled for
its plain reference, and how its work is counted, is in that architecture's
own file, ``benchmarks/architectures/<architecture>.py``; everything else the
benchmark knows about the program is in the runners."""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

# what a configuration file holds beside the published config: the
# benchmark's own notes, which neither the program nor the reference sees
NOTE_KEYS = ("name", "source", "architecture", "reference", "deployment", "reduced", "assumed",
             "check")


def published(config: dict) -> dict:
    """The configuration file without the benchmark's own notes."""
    return {k: v for k, v in config.items() if k not in NOTE_KEYS}


def model_config(config: dict, dtype):
    """The program's TransformerConfig for a published config, through the
    mapping it applies to HuggingFace checkpoints; no preset of its own."""
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    return dataclasses.replace(config_from_hf(published(config)), dtype=dtype)


def tolerance(config: dict, key: str) -> float:
    """A tolerance of the configuration's own ``check`` block. There is no
    default: a configuration that never measured one does not inherit one."""
    try:
        return float(config["check"][key])
    except KeyError:
        raise KeyError(f"configuration {config.get('name')!r} states no check.{key}: measure it "
                       "(PERF.md, section 7) and write it with its readings into the file") from None


def relative_error(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class Routing(NamedTuple):
    """What a routed architecture's file says of its router and hands out of
    the program (PERF.md, section 7): the picks are the experts the timed
    path itself sent each token to."""

    layers: int  # routed layers, in the order the picks' layer axis has
    experts: int  # routed experts of a layer, by the published numbering
    k: int  # experts per token
    put: Callable  # put_with_picks(engine, uids, fed) -> (logits, picks)
    generate: Callable  # generate_with_picks(engine, prompts, max_new_tokens) -> (outs, picks)


ROUTED_MEMBERS = ("routed_layers", "routed_experts", "experts_per_token", "put_with_picks",
                  "generate_with_picks")


def routing(architecture, config: dict) -> Optional[Routing]:
    """None for an architecture file that does not say it is routed (no
    ``routed_layers``, or 0 of them in this configuration): such a model is
    checked as it always was. A file that says it is has to hand out the
    picks, because a routed model's ``correct`` is decided at them."""
    if not hasattr(architecture, "routed_layers"):
        return None
    cfg = published(config)
    layers = int(architecture.routed_layers(cfg))
    if layers == 0:
        return None
    missing = [m for m in ROUTED_MEMBERS if not hasattr(architecture, m)]
    if missing:
        raise AttributeError(
            f"the architecture file of {config.get('architecture')!r} says {layers} routed layers and "
            f"lacks {', '.join(missing)}: a routed model is checked at the program's own expert picks. "
            "Write put_with_picks(engine, uids, fed) -> (logits, picks), the same call and compiled "
            "programs as engine.put, picks[i] int32 [len(fed[i]), routed_layers, experts_per_token], and "
            "generate_with_picks(engine, prompts, max_new_tokens) -> (outs, picks) out of the fused "
            "prefill and the decode chain likewise (PERF.md, section 7)")
    return Routing(layers, int(architecture.routed_experts(cfg)), int(architecture.experts_per_token(cfg)),
                   architecture.put_with_picks, architecture.generate_with_picks)


def checked_picks(picks, tokens: int, routing: Routing):
    """One row's picks as the program reported them, refused unless they are
    ``[tokens, routed_layers, k]`` distinct published expert numbers: inside
    jit an index out of range would be clamped in silence."""
    import numpy as np

    picks = np.asarray(picks)
    want = (tokens, routing.layers, routing.k)
    if picks.shape != want or not np.issubdtype(picks.dtype, np.integer):
        raise ValueError(f"picks of shape {picks.shape} and type {picks.dtype}, wanted int32 {want}: "
                         "[tokens fed, routed layers, experts per token]")
    ordered = np.sort(picks, axis=-1)
    if picks.min() < 0 or picks.max() >= routing.experts or (ordered[..., 1:] == ordered[..., :-1]).any():
        raise ValueError(f"picks are not {routing.k} distinct experts of 0..{routing.experts - 1} "
                         f"at every token and layer (they range {picks.min()}..{picks.max()})")
    return picks.astype(np.int32)
