"""The seam to the system under test: its model config from a published
``config.json``, through the mapping it applies to checkpoints of every
architecture it loads. How an architecture's parameter tree is relabelled for
its plain reference, and how its work is counted, is in that architecture's
own file, ``benchmarks/architectures/<architecture>.py``; everything else the
benchmark knows about the program is in the runners."""

from __future__ import annotations

import dataclasses

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
