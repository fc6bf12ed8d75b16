"""One general traffic generator, driven by the ``traffic`` block of a
workload file. A new traffic mix is a new data file, never new code here.

Every seed gets the SAME work in another order: the multiset of prompt
lengths and the multiset of gaps between arrivals are drawn once from a fixed
generator (never from ``--seed``); ``--seed`` shuffles both and fills in the
token ids. A bound measured on one set of seeds then holds for the driver's.

``traffic`` keys:

  kind           "token_batches" (training), "open_loop" or "closed_waves"
  token_batches  sequences, seq_len; steps_in_flight (how many steps the train
                 runner keeps dispatched before it waits for the oldest)
  open_loop      rate_per_s (Poisson arrivals); prompt_len; output_tokens
  closed_waves   wave (requests submitted at once; the next wave when the last
                 request of this one is done); prompt_len; output_tokens
  prompt_len     {"dist": "uniform", "min", "max"} or
                 {"dist": "lognormal", "median", "sigma", "min", "max"}
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    # --seed may exceed 32 signed bits; SeedSequence takes any non-negative int
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def length_set(spec: dict, n: int) -> np.ndarray:
    """The fixed multiset of ``n`` lengths of a ``prompt_len`` spec."""
    rng = _rng(0, 1)
    dist = spec["dist"]
    if dist == "uniform":
        out = rng.integers(spec["min"], spec["max"] + 1, n)
    elif dist == "lognormal":
        out = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n)).round().astype(np.int64)
    else:
        raise ValueError(f"unknown prompt_len dist {dist!r}")
    return np.sort(np.clip(out, spec["min"], spec["max"]))


def gap_set(rate_per_s: float, n: int) -> np.ndarray:
    """The fixed multiset of ``n`` exponential gaps between arrivals, its own
    mean pinned to 1/rate, so that n requests span n/rate seconds."""
    mean = 1.0 / rate_per_s
    gaps = _rng(0, 2).exponential(mean, n)
    return np.sort(gaps * (mean / gaps.mean()))


@dataclasses.dataclass
class Requests:
    prompts: List[np.ndarray]
    output_tokens: int
    arrival_s: Optional[np.ndarray]  # None: all at once (one closed wave)


def _prompts(lengths: np.ndarray, vocab: int, rng: np.random.Generator) -> List[np.ndarray]:
    return [rng.integers(0, vocab, int(n), dtype=np.int32) for n in lengths]


def open_loop(traffic: dict, vocab: int, seed: int, seconds: float) -> Requests:
    """``round(rate * seconds)`` requests with their due times."""
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    rng = _rng(seed, 3)
    lengths = rng.permutation(length_set(traffic["prompt_len"], n))
    gaps = rng.permutation(gap_set(traffic["rate_per_s"], n))
    return Requests(_prompts(lengths, vocab, rng), int(traffic["output_tokens"]),
                    np.cumsum(gaps) - gaps[0])


def closed_waves(traffic: dict, vocab: int, seed: int) -> Iterator[Requests]:
    """Waves without end; each holds the same set of lengths, reshuffled."""
    lengths = length_set(traffic["prompt_len"], int(traffic["wave"]))
    rng = _rng(seed, 5)
    while True:
        yield Requests(_prompts(rng.permutation(lengths), vocab, rng),
                       int(traffic["output_tokens"]), None)


def token_batches(traffic: dict, vocab: int, seed: int) -> Iterator[np.ndarray]:
    """Training batches without end: ``[sequences, seq_len]`` int32 token ids."""
    rng = _rng(seed, 6)
    shape = (int(traffic["sequences"]), int(traffic["seq_len"]))
    while True:
        yield rng.integers(0, vocab, shape, dtype=np.int32)
