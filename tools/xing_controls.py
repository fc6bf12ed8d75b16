#!/usr/bin/env python3
"""The planted faults that the ``xing4_0`` cell's ``check`` has to refuse, run
through ``benchmarks/run.py`` itself on the chip: the readings behind
``check.readings.*.control_min`` of ``benchmarks/configs/xing4.0-29b-a4b.json``.

    python3 tools/xing_controls.py --control sinkhorn_2|post_without_2|plain_rotary|e4m3_latent \\
        --workload xing4.0-29b-a4b.serve.long-prompt-batch --seed <n> --seconds 5 --trace 0

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.
The reference always runs the configuration as it is written.

- ``sinkhorn_2``: the program runs 2 Sinkhorn rounds where the configuration
  says 20 (``hc_sinkhorn_iters`` replaced in the TransformerConfig it is given).
- ``post_without_2``: ``H_post = sigmoid(.)`` without its 2 (``ops/mhc.py::mix``
  wrapped, and ``mix_read`` for the mix the kernel ``mhc_mix_read`` makes: what
  the sublayers write back is halved).
- ``plain_rotary``: the program drops ``rope_scaling``: plain frequencies and
  the plain softmax scale where YaRN's are configured.
- ``e4m3_latent`` (the nearest precision below bf16 for what the latent pool
  holds): ``tools/routed_controls.py``'s, every layer's ``wkv_a`` through
  float8_e4m3fn on the host.

The routers' controls (``no_bias``, ``ranks_2_to_k1``: the readings behind
``route_shortfall_tol``) are ``tools/routed_controls.py``'s own, given this
cell's ``--workload``. The last line is ``run.py``'s: ``correct`` has to read false.
"""

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def plant_config(**changed):
    """The program is built from the configuration with ``changed`` replaced."""
    from deepspeed_tpu.checkpoint import hf

    honest = hf.config_from_hf
    hf.config_from_hf = lambda hf_config: dataclasses.replace(honest(hf_config), **changed)


def plant_post_without_2():
    from deepspeed_tpu.ops import mhc

    honest = mhc.mix

    def mix(*args, **kw):
        mixed = honest(*args, **kw)
        return mixed._replace(post=0.5 * mixed.post)

    mhc.mix = mix
    read = mhc.mix_read

    def mix_read(streams, *args, **kw):  # where the kernels make the mix (a prefill on the chip), rows n .. 2n of it
        mixed, u = read(streams, *args, **kw)
        n = streams.shape[0]
        return (mixed if isinstance(mixed, mhc.Mix) else mixed.at[n:2 * n].multiply(0.5)), u

    mhc.mix_read = mix_read


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True,
                    choices=("sinkhorn_2", "post_without_2", "plain_rotary", "e4m3_latent"))
    args, rest = ap.parse_known_args()
    if args.control == "sinkhorn_2":
        plant_config(hc_sinkhorn_iters=2)
    elif args.control == "plain_rotary":
        plant_config(rope_scaling=None)
    elif args.control == "post_without_2":
        plant_post_without_2()
    else:
        import routed_controls

        routed_controls.plant_e4m3(routed_controls.E4M3_LEAVES["e4m3_latent"])
    from benchmarks import run

    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
