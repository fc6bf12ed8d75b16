#!/usr/bin/env python3
"""The planted faults that the ROUTED ``granitemoehybrid`` cell's ``check`` has
to refuse, run through ``benchmarks/run.py`` itself on the chip (the readings
behind ``check.readings.*.control_min`` of ``benchmarks/configs/
granite-4.0-h-small.json``), and the decode over the traffic's own length that
the runner's check, which decodes 12 tokens, does not reach. A sibling of
``tools/granite_controls.py`` (the dense member's), whose plants of the mixer it
takes, with ``tools/qwen3_next_controls.py``'s of a share's routed layer.

    python3 tools/granite_routed_controls.py --control <one of CONTROLS> \\
        --workload granite-4.0-h-small.serve.long-output-wave64 --seed <n> --seconds 5 --trace 0
    python3 tools/granite_routed_controls.py --control drift --workload <the same> --seed <n>

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.
The reference always runs the configuration as it is written.

- ``e4m3_out_proj``, ``e4m3_w_down`` (the nearest precision below the bf16 the
  WEIGHTS are kept in): every mixer's ``ssm_out_proj``, or every routed
  expert's ``w_down``, goes into the engine through float8_e4m3fn, planted on
  the host (``tools/routed_controls.py::plant_e4m3``); the reference is given
  the weights as they were (2.26 GB of ``w_down`` twice on the chip).
- ``ranks_2_to_k1``: the router takes ranks 2..11 of its logits for 1..10: picks
  that this router could not have made (the audit's control: ``route_shortfall``).
- ``renorm_held_only``: the picks' weights are renormalised over the picks HELD
  on this chip (as if the chip were the whole layer), not over all ten.
- ``no_shared``: the shared MLP is left out of every layer.
- ``pad_moves_state``: the tokens a prompt is padded with are left to move the
  state (``ssd_chunked`` is not told which tokens are live).
- ``residual_1``: the program adds every sublayer's output whole
  (``residual_multiplier`` 1 for 0.22).

Of these the last line is ``run.py``'s: ``correct`` has to read false.

- ``drift``: 8 prompts of the traffic's lengths through the fused prefill and
  then 511 tokens of decode chains (the timed path's own greedy tokens, the
  chain ahead); the last token is fed through ``put`` and its logits, which
  rest on every state update before them, are compared with the reference's
  FULL forward of the same continuation at the program's own picks, as is
  every token generated (its gap under the reference's best logit), and every
  pick is audited (``tools/qwen3_next_controls.py::drift``, which takes any
  routed cell with recurrent state). One JSON line; ``ok`` by the
  configuration's own tolerances.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = ("e4m3_out_proj", "e4m3_w_down", "ranks_2_to_k1", "renorm_held_only", "no_shared", "pad_moves_state",
            "residual_1")


def plant_no_shared():
    from deepspeed_tpu.inference import paged

    honest = paged._moe_with_picks
    paged._moe_with_picks = lambda lp, cfg, x: honest({k: v for k, v in lp.items() if k != "shared"}, cfg, x)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=CONTROLS + ("drift",))
    args, rest = ap.parse_known_args()
    import qwen3_next_controls

    if args.control == "drift":
        run = argparse.ArgumentParser()
        run.add_argument("--workload", required=True)
        run.add_argument("--seed", type=int, default=0)
        asked, _ = run.parse_known_args(rest)
        return qwen3_next_controls.drift(asked.workload, asked.seed)  # (it reads its architecture from the cell)
    import granite_controls
    import routed_controls

    if args.control == "e4m3_out_proj":
        routed_controls.plant_e4m3(lambda path: "'ssm_out_proj'" in path)
    elif args.control == "e4m3_w_down":
        routed_controls.plant_e4m3(lambda path: "'experts'" in path and "'w_down'" in path)
    elif args.control == "ranks_2_to_k1":
        qwen3_next_controls.plant_router()
    elif args.control == "renorm_held_only":
        qwen3_next_controls.plant_renorm_held_only()
    elif args.control == "no_shared":
        plant_no_shared()
    elif args.control == "pad_moves_state":
        granite_controls.plant_pad_moves_state()
    else:
        granite_controls.plant_config(residual_multiplier=1.0)
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
