#!/usr/bin/env python3
"""The planted faults of the cell whose two attention kinds differ in more than
the band (``mimo-v2.5.serve.long-output-wave128``), run through
``benchmarks/run.py`` itself on the chip (the readings behind
``check.readings.*.control_min`` of ``benchmarks/configs/mimo-v2.5.json``), and
the prefill kernels' roofline shares at these shapes, which the benchmark does
not list.

    python3 tools/mimo_controls.py --control <name> \\
        --workload mimo-v2.5.serve.long-output-wave128 --seed <n> --seconds 5 --trace 0

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs (``tools/swa_controls.py``'s
manner, whose helpers it takes); nothing here is read by either. The reference
is always the published model.

- ``no_sink``: the sliding layers' softmax without its sink (``models/
  transformer.py::sliding_kind`` tells the paged path that no kind has one).
- ``no_value_scale``: the values not times ``attention_value_scale``.
- ``rope_all``: rotary over all 192 columns of a head, not the first 64.
- ``bases_swapped``: the two kinds' rotary bases swapped (10,000 in the global
  layers, 10,000,000 in the sliding ones).
- ``kv_groups``: the sliding layers' query heads in groups of the OTHER kind's
  size: query head ``i`` reads key-value head ``i // 16`` (the global kind's
  group) for ``i // 8``, so key-value heads 4-7 are never read (planted on the
  projected keys and values: head ``g`` is given head ``g // 2``'s).
- ``e4m3_ring`` / ``e4m3_global``: what a decode step reads of the ring's pages,
  or of the global pages, keys and values, through an e4m3-wide float (4
  exponent bits, 3 of mantissa: the nearest precision below the bf16 the pages
  are kept in), by ``lax.reduce_precision``.
- ``dead_slot``: the oldest ring page's dead slots unmasked: after a wrap a
  decode query sees every slot of the page that holds its oldest live key,
  the ones an older block left there too (``ops/attention.py::first_live``
  rounded down to its page's first slot).
- ``bias_weighs``: the correction bias weighing as well as choosing: ``w_e = (s_e
  + b_e) / sum`` over the picks.
- ``ranks_2_to_k1``: the router takes ranks 2..k+1 of ``s + b`` for 1..k (the
  reading behind ``route_shortfall_tol``).
- ``prefill_rooflines``: no fault: the cell's traced run (pass ``--trace 1``) with
  ``swa_prefill_roofline.batch`` and ``full_prefill_roofline.batch`` beside its
  listed metrics (``lib/swa.py``'s readers over this architecture's two-width
  costs), where the window holds whole prefills.

The last line is ``run.py``'s: for every control but ``prefill_rooflines``,
``correct`` has to read false, and its timed window is one wave of 8 requests of
16 tokens (a control is decided by the check; the numbers of its window are no
reading of the cell).
"""

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from swa_controls import _e4m3, plant_config, plant_dead_slots, plant_unlisted  # noqa: E402


def plant_no_sink():
    """(While the paged path's builder binds ``sliding_kind`` alone: the flax module, which makes the weights the
    reference is given, keeps its sinks.)"""
    from deepspeed_tpu.inference import paged
    from deepspeed_tpu.models import transformer

    kind, build = transformer.sliding_kind, paged._ATTENTION["windowed"]

    def builder(cfg, call):
        transformer.sliding_kind = lambda cfg, name: dict(kind(cfg, name), sink=False)
        try:
            return build(cfg, call)
        finally:
            transformer.sliding_kind = kind

    paged._ATTENTION["windowed"] = builder


def plant_bases_swapped():
    plant_config(lambda cfg: dataclasses.replace(
        cfg, rope_theta=cfg.sliding.rope_theta, sliding=dataclasses.replace(cfg.sliding, rope_theta=cfg.rope_theta)))


def plant_kv_groups():
    import jax.numpy as jnp

    from deepspeed_tpu.inference import paged

    qkv = paged._qkv

    def _qkv(lp, cfg, x):
        q, k, v = qkv(lp, cfg, x)
        heads = cfg.sliding.num_kv_heads
        if k.shape[-2] == heads != cfg.kv_heads:  # a sliding layer: head g is given head g // 2's keys and values
            other = jnp.arange(heads) * (cfg.num_heads // heads) // (cfg.num_heads // cfg.kv_heads)
            k, v = k[..., other, :], v[..., other, :]
        return q, k, v

    paged._qkv = _qkv


def plant_e4m3_pages(ring: bool):
    from deepspeed_tpu.inference import paged

    attend = paged.paged_attention

    def paged_attention(q, pool_k, pool_v, *args, **kwargs):
        if (kwargs.get("first_live") is not None) == ring:  # (a sliding layer's read of its ring says first_live)
            pool_k, pool_v = _e4m3(pool_k), _e4m3(pool_v)
        return attend(q, pool_k, pool_v, *args, **kwargs)

    paged.paged_attention = paged_attention


def _plant_route(weigh_with_bias: bool, skip: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.parallel import moe

    def route(logits, top_k, *, kind="softmax", bias=None, renormalize=True, scale=1.0):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        select = scores + bias.astype(jnp.float32)
        picks = jax.lax.top_k(select, top_k + skip)[1][:, skip:]
        weights = jnp.take_along_axis(select if weigh_with_bias else scores, picks, axis=-1)
        return weights / (weights.sum(-1, keepdims=True) + 1e-20) * scale, picks.astype(jnp.int32)

    moe.route = route


def plant_prefill_rooflines():
    from benchmarks.lib import swa

    plant_unlisted("swa_prefill_roofline.batch", "%", lambda run: swa.prefill_roofline(
        run, "swa_prefill_cost", swa.SWA_PREFILL_KERNEL, "sliding_layers"))
    plant_unlisted("full_prefill_roofline.batch", "%", lambda run: swa.prefill_roofline(
        run, "full_prefill_cost", swa.FULL_PREFILL_KERNEL, "full_layers"))  # (over the first: each wraps what is there)


PLANTS = {
    "no_sink": plant_no_sink,
    "no_value_scale": lambda: plant_config(lambda cfg: dataclasses.replace(cfg, value_multiplier=1.0)),
    "rope_all": lambda: plant_config(lambda cfg: dataclasses.replace(cfg, rotary_dim=None)),
    "bases_swapped": plant_bases_swapped, "kv_groups": plant_kv_groups,
    "e4m3_ring": lambda: plant_e4m3_pages(True), "e4m3_global": lambda: plant_e4m3_pages(False),
    "dead_slot": plant_dead_slots,
    "bias_weighs": lambda: _plant_route(True, 0), "ranks_2_to_k1": lambda: _plant_route(False, 1),
    "prefill_rooflines": plant_prefill_rooflines,
}


def plant_small_window():
    """A control is decided by the check: the timed window after it is cut to one wave of 8 requests of 16 tokens
    (the programs the check has compiled already), and nothing is warmed."""
    from benchmarks.lib import harness

    load = harness.load_workload

    def load_workload(name, *args):
        workload = load(name, *args)
        return dict(workload, traffic=dict(workload["traffic"], wave=8, output_tokens=16),
                    warm=dict(workload["warm"], prefill=[], chain_rows=[]))

    harness.load_workload = load_workload


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=tuple(PLANTS))
    args, rest = ap.parse_known_args()
    if args.control != "prefill_rooflines":
        plant_small_window()
    PLANTS[args.control]()
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
