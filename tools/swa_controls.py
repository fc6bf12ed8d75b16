#!/usr/bin/env python3
"""The planted faults of a cell with a sliding kind, run through
``benchmarks/run.py`` itself on the chip (the readings behind
``check.readings.*.control_min`` of ``benchmarks/configs/command-a-plus-05-2026.json``),
one control that must NOT move ``correct`` but must move time, and the decode
kernel's roofline share, which the benchmark does not list.

    python3 tools/swa_controls.py --control <name> \\
        --workload command-a-plus-05-2026.serve.long-prompt-wave8 --seed <n> --seconds 5 --trace 0

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.
The reference is always the published model.

- ``window_wider`` / ``window_narrower``: the program's window is one key wider
  (4,097) or narrower (4,095) than the published one: its band, its ring and
  its first live slot all follow (``checkpoint/hf.py::config_from_hf`` wrapped).
- ``dead_slots``: the oldest ring page's dead slots unmasked: a decode query
  sees every slot of the page that holds its oldest live key
  (``ops/attention.py::first_live`` rounded down to its page's first slot).
- ``rope_on_full``: rotary on the full layers too (``models/transformer.py::
  sliding_kind`` says every kind rotates).
- ``rope_halves``: rotary in halves instead of adjacent pairs.
- ``shared_summed``: the shared experts summed, not averaged.
- ``e4m3_ring``: what a sliding layer's decode step reads of its ring, keys and
  values, through an e4m3-wide float (4 exponent bits, 3 of mantissa: the
  nearest precision below the bf16 the pages are kept in), by
  ``lax.reduce_precision``, which XLA does not fold away as it does a cast there
  and back.
- ``e4m3_swa_prefill``: what a sliding layer's PREFILL computes its attention
  from, the chunk's own queries, keys and values, through the same e4m3-wide
  float (``ops/attention.py::causal_attention`` wrapped where it is handed a
  window); the pages are written from the unrounded keys and values, so the
  decode steps read what they always read: a lower precision on the prefill
  path alone.
- ``e4m3_shared``: the averaged shared experts' input and output through the
  same float, in every layer and program (``inference/model.py::_mlp`` wrapped).
- ``ranks_2_to_k1``: the router takes ranks 2..k+1 of its scores for 1..k (the
  reading behind ``route_shortfall_tol``; ``tools/routed_controls.py``'s control
  of that name adds a correction bias, which this router has not).
- ``band_as_mask``: the band as a mask alone: the banded forward runs every
  cell of the causal triangle and masks the ones under the band whole.
  ``correct`` must stay true and a prefill must take longer by the cells that
  are not skipped (26.4 against 11.5 TFLOP in the three sliding layers).
- ``wave_parts``: no fault and no check (``correct`` reads true for nothing:
  the comparison is skipped to save its 165 s): after every wave of the timed
  window one line ``wave_parts=1 wave_s=... touched=... real_pairs=...
  pad_pairs=...`` from the picks the serving programs hand out anyway:
  ``touched``, the held experts that some row of a decode step picked, the
  mean over the wave's steps and layers (what a decode step reads of the
  experts); ``real_pairs`` / ``pad_pairs``, the (token, pick) pairs of the
  wave's eight prefills that land on a held expert, of the prompts' own tokens
  and of the pads that fill a prompt to its bucket (the rows of the grouped
  products). What makes a seed fast or slow: ``PERF.md``, section 6.
- ``decode_roofline``: no fault: the cell's traced run with ``swa_decode_roofline``
  printed beside its listed metrics (pass ``--trace 1``), for the decode kernel's
  share where the window held a whole chain.
- ``flash_cells``: no fault: the cell's traced run (pass ``--trace 1``) with
  ``flash_cells_live_share.batch`` beside its listed metrics: of the cells of
  the flash forwards' grids in the window's prefills (a head, summed over rows
  and layers: ``flash_cells_grid`` on a prefill's ``serve:dispatch`` span), the
  share that holds a live query and so runs since PR 58 (``flash_cells_live``);
  100 says every prompt fills its bucket, and nothing is printed where no
  span says them (a parent of PR 58, or a model with no such call).

The last line is ``run.py``'s: for every control but ``band_as_mask``,
``wave_parts``, ``decode_roofline`` and ``flash_cells``, ``correct`` has to
read false.
"""

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def plant_config(change):
    """The program's model config goes through ``change``; the reference reads the published dict."""
    from deepspeed_tpu.checkpoint import hf

    mapped = hf.config_from_hf
    hf.config_from_hf = lambda published: change(mapped(published))


def plant_window(by: int):
    plant_config(lambda cfg: dataclasses.replace(
        cfg, sliding=dataclasses.replace(cfg.sliding, window=cfg.sliding.window + by)))


def plant_dead_slots():
    from benchmarks.lib import harness
    from deepspeed_tpu.ops import attention

    block = None
    load, live = harness.load_workload, attention.first_live

    def load_workload(name, *args):
        nonlocal block
        workload = load(name, *args)
        block = int(workload["engine"]["kv_block_size"])
        return workload

    harness.load_workload = load_workload
    attention.first_live = lambda positions, window: live(positions, window) // block * block


def plant_rope_on_full():
    from deepspeed_tpu.models import transformer

    kind = transformer.sliding_kind
    transformer.sliding_kind = lambda cfg, name: dict(kind(cfg, name), rotates=True)


def plant_e4m3_ring():
    import jax

    from deepspeed_tpu.inference import paged

    attend = paged.paged_attention

    def paged_attention(q, pool_k, pool_v, *args, **kwargs):
        if kwargs.get("first_live") is not None:  # a sliding layer's read of its ring
            pool_k, pool_v = (jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3) for a in (pool_k, pool_v))
        return attend(q, pool_k, pool_v, *args, **kwargs)

    paged.paged_attention = paged_attention


def _e4m3(a):
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)


def plant_e4m3_swa_prefill():
    from deepspeed_tpu.ops import attention

    attend = attention.causal_attention

    def causal_attention(q, k, v, *args, window=None, **kwargs):
        if window is not None:  # a sliding layer's attention inside a fresh prompt's chunk
            q, k, v = _e4m3(q), _e4m3(k), _e4m3(v)
        return attend(q, k, v, *args, window=window, **kwargs)

    attention.causal_attention = causal_attention


def plant_e4m3_shared():
    from deepspeed_tpu.inference import model

    mlp = model._mlp
    model._mlp = lambda lp, cfg, x: _e4m3(mlp(lp, cfg, _e4m3(x)))


def plant_ranks_2_to_k1():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.parallel import moe

    def route(logits, top_k, *, kind="softmax", bias=None, renormalize=True, scale=1.0):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        picks = jax.lax.top_k(scores, top_k + 1)[1][:, 1:]
        weights = jnp.take_along_axis(scores, picks, axis=-1)
        return weights / (weights.sum(-1, keepdims=True) + 1e-20) * scale, picks.astype(jnp.int32)

    moe.route = route


def plant_band_as_mask():
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import flash_attention as fa

    fa._band_maps = lambda n, block, window: fa._tri_maps(n)
    fa._band_first = lambda qi, block, window: jnp.zeros_like(qi)


def plant_wave_parts():
    import numpy as np

    from benchmarks.lib import harness
    from benchmarks.runners import serve
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2 as Engine

    serve.check = lambda *args: (True, {})
    generate, log_picks = Engine.generate, Engine._log_picks
    prefills = []  # (a prefill's picks on the device, tokens fed a row) of the wave in flight

    def _log_picks(self, picks, uids, rids, token_lists=None, **kwargs):
        if picks and token_lists is not None and self.picks_log is not None:
            prefills.append((picks[-1], [len(t) for t in token_lists]))
        return log_picks(self, picks, uids, rids, token_lists, **kwargs)

    def wave(self, prompts, max_new_tokens=32, **kwargs):
        cfg = self.model_config
        if len(prompts) != self.config.max_seqs or max_new_tokens <= self.config.decode_chain + 1:
            return generate(self, prompts, max_new_tokens=max_new_tokens, **kwargs)  # (warm-up's calls)
        del prefills[:]
        took = []

        def timed():
            t0 = time.perf_counter()
            outs = generate(self, prompts, max_new_tokens=max_new_tokens, **kwargs)
            took.append(time.perf_counter() - t0)
            return outs

        outs, picks = self._with_picks(timed, len(prompts))

        def held(a):
            return (a >= cfg.first_expert) & (a < cfg.first_expert + cfg.num_experts)

        # picks[i]: [prompt i + its outputs less one, layers, k]: the tokens after the prompt are the decode steps'
        steps = np.stack([p[len(q):len(q) + max_new_tokens - 1] for p, q in zip(picks, prompts)])  # [rows, steps, L, k]
        touched = np.mean([[len(np.unique(steps[:, s, layer][held(steps[:, s, layer])]))
                            for layer in range(steps.shape[2])] for s in range(steps.shape[1])])
        real = pads = 0
        for on_device, counts in prefills:
            a = np.asarray(on_device)  # [rows, C, layers, k]
            fed = np.arange(a.shape[1])[None, :] < np.asarray(counts)[:, None]
            fed = np.concatenate([fed, np.zeros((a.shape[0] - len(counts), a.shape[1]), bool)])
            real += int(held(a[fed]).sum())
            pads += int(held(a[~fed]).sum())
        harness.say(wave_parts=1, wave_s=took[0], touched=float(touched), real_pairs=real, pad_pairs=pads,
                    prefill_calls=len(prefills))
        return outs

    Engine._log_picks, Engine.generate = _log_picks, wave


def plant_unlisted(name: str, unit: str, reader):
    """``reader(run)`` as one more per-layer metric of the cell's traced line.
    (No file under ``benchmarks/metrics/``: a reader there has to be listed, ``tests/benchmarks/test_contract.py``.)"""
    from benchmarks.lib import harness

    wanted, readers = harness.cell_metrics, harness.load_reader

    def cell_metrics(bench, group, workload_name):
        out = wanted(bench, group, workload_name)
        return out + [{"name": name, "unit": unit}] if group == "per_layer" else out

    harness.cell_metrics = cell_metrics
    harness.load_reader = lambda metric, *args: (
        (lambda run, trace: reader(run)) if metric == name else readers(metric, *args))


def plant_decode_roofline():
    from benchmarks.lib import swa

    plant_unlisted("swa_decode_roofline.batch", "%", swa.decode_roofline)


def flash_cells_live_share(run):
    from benchmarks.lib import harness, spans

    said = [(float(s.args["flash_cells_live"]), float(s.args["flash_cells_grid"]))
            for s in spans.named(spans.of_run(run), "serve:dispatch") if "flash_cells_grid" in s.args]
    live, grid = (sum(a[i] for a in said) for i in (0, 1))
    if not grid:
        return None
    harness.say(flash_cells_calls=len(said), flash_cells_live=live, flash_cells_grid=grid)
    return 100.0 * live / grid


PLANTS = {
    "window_wider": lambda: plant_window(1), "window_narrower": lambda: plant_window(-1),
    "dead_slots": plant_dead_slots, "rope_on_full": plant_rope_on_full,
    "rope_halves": lambda: plant_config(lambda cfg: dataclasses.replace(cfg, rope_interleaved=False)),
    "shared_summed": lambda: plant_config(lambda cfg: dataclasses.replace(cfg, moe_shared_average=False)),
    "e4m3_ring": plant_e4m3_ring, "e4m3_swa_prefill": plant_e4m3_swa_prefill, "e4m3_shared": plant_e4m3_shared,
    "wave_parts": plant_wave_parts, "ranks_2_to_k1": plant_ranks_2_to_k1, "band_as_mask": plant_band_as_mask,
    "decode_roofline": plant_decode_roofline,
    "flash_cells": lambda: plant_unlisted("flash_cells_live_share.batch", "%", flash_cells_live_share),
}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=tuple(PLANTS))
    args, rest = ap.parse_known_args()
    PLANTS[args.control]()
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
