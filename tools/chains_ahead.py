#!/usr/bin/env python3
"""How many of a benchmark cell's decode chains the serving loop dispatched
ahead (before the chain before them was fetched), a ``generate`` call at a
time, read from the engine's own counters while ``benchmarks/run.py`` runs the
cell as it always does:

    python3 tools/chains_ahead.py --workload pythia-1.4b.serve.batch --seed <n> --seconds 5 --trace 0

The benchmark keeps the span tracer off (its records come from the flight
recorder), so the registry's ``serving/chains_ahead`` and ``serving/chains``
are not there to read; ``InferenceEngineV2.chains_ahead`` and ``.chain_steps``
are the same counts as plain numbers. ``generate`` is wrapped OUTSIDE the
program and the benchmark, before ``run.main`` imports the engine's user;
nothing here is read by either. One line a call, the check's and the warm-up's
first, then one a wave:

    generate requests=64 max_new_tokens=192 chains=24 chains_ahead=23 share=0.9583

and last ``run.py``'s own line.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmarks import run
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    generate = InferenceEngineV2.generate

    def counted(self, prompts, max_new_tokens=32, **kwargs):
        chains, ahead = self.chain_steps, self.chains_ahead
        try:
            return generate(self, prompts, max_new_tokens=max_new_tokens, **kwargs)
        finally:
            chains, ahead = self.chain_steps - chains, self.chains_ahead - ahead
            print(f"generate requests={len(prompts)} max_new_tokens={max_new_tokens} chains={chains} "
                  f"chains_ahead={ahead} share={ahead / max(chains, 1):.4f}", flush=True)

    InferenceEngineV2.generate = counted
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
