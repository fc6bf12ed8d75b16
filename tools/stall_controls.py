#!/usr/bin/env python3
"""The four causes of a slow serving call, each planted in a real cell through
``benchmarks/run.py``'s own runner, to be read back as its class from the
engine's call log (``engine.stalls``; docs/diagnostics.md, "A slow call"):

    python3 tools/stall_controls.py --control sleep|stop|device|collect \\
        --workload <a serve cell> --seed <n> --seconds 20 --trace 0|1

Each control is made OUTSIDE the program and the benchmark, by wrapping what
this process imports before ``run.main`` runs; nothing here is read by either.
The plant goes off once, at the first decode chain that starts ``--at-share``
of ``--seconds`` into the window (0.33: inside the window ``--trace 1``
traces), and lasts about ``--stall-seconds``.

- ``sleep``: the host sleeps between two calls, a chain in flight ahead
  (expected ``host_not_running``: the chain is ready at once afterwards).
- ``stop``: the process is stopped and continued from outside (``SIGSTOP``,
  ``SIGCONT``) while a chain is in flight, so inside a ``serve:fetch``
  (expected ``host_not_running``: the NEXT chain is ready at once).
  With ``--trace 1`` it has cost the calling shell a SIGHUP: run it last and in
  a session of its own (``setsid python3 tools/stall_controls.py ...``).
- ``device``: a long device program (a loop of 4096-wide products, compiled
  and timed before the window) is queued before a chain (expected
  ``device_late``: the chain after it then takes its usual time).
- ``collect``: a full collection of a large heap (made before the window)
  between two calls (expected ``collector``).

The program's own ``[serving] stall:`` lines are in the output as they happen;
the end prints each record again as a ``stall=`` line, a ``planted=`` line with
what was expected and found, and exits 1 where the expected class is missing.
The last line but those is ``run.py``'s.
"""

import argparse
import gc
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EXPECTED = {"sleep": "host_not_running", "stop": "host_not_running", "device": "device_late",
            "collect": "collector"}
HEAP_OBJECTS_PER_S = 20_000_000  # containers a full collection walks in a second (the v5e's host: 18M in 0.87 s, PR 53)


class Plant:
    """Goes off once, in front of the first decode chain that starts
    ``at_s`` into the window; the window starts where the runner marks its
    compile counter."""

    def __init__(self, control: str, at_s: float, stall_s: float):
        self.control, self.at_s, self.stall_s = control, at_s, stall_s
        self.window_t0 = self.planted_at = self.engine = None
        self._burn = self._heap = self._burning = None

    def install(self):
        from benchmarks.lib import harness
        from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

        honest_mark, honest_chain = harness.CompileCounter.mark, InferenceEngineV2.decode_chain
        plant = self

        def mark(counter):
            plant.prepare()
            honest_mark(counter)
            plant.window_t0 = time.perf_counter()

        def decode_chain(engine, *args, **kwargs):
            plant.engine = engine
            if (plant.planted_at is None and plant.window_t0 is not None
                    and time.perf_counter() - plant.window_t0 >= plant.at_s):
                plant.planted_at = time.perf_counter() - plant.window_t0
                plant.go()
            return honest_chain(engine, *args, **kwargs)

        harness.CompileCounter.mark, InferenceEngineV2.decode_chain = mark, decode_chain

    def prepare(self):
        """Set-up's share of a plant: nothing of it compiles or allocates inside the window."""
        from benchmarks.lib import harness

        if self.control == "device":
            import jax
            import jax.numpy as jnp

            @jax.jit
            def burn(x, n):
                return jax.lax.fori_loop(0, n, lambda _, y: (y @ y) * jnp.bfloat16(1 / 64), x)

            x = jnp.full((4096, 4096), 1 / 64, jnp.bfloat16)
            jax.block_until_ready(burn(x, 8))
            t0 = time.perf_counter()
            jax.block_until_ready(burn(x, 256))
            per_product = (time.perf_counter() - t0) / 256
            self._burn = (burn, x, max(int(self.stall_s / per_product), 1))
            harness.say(control="device", product_s=per_product, products=self._burn[2])
        elif self.control == "collect":
            t0 = time.perf_counter()
            gc.disable()  # (or the collector walks the growing heap again and again while it is made)
            try:
                self._heap = [[i] for i in range(int(self.stall_s * HEAP_OBJECTS_PER_S))]
            finally:
                gc.enable()
            harness.say(control="collect", heap_objects=len(self._heap), made_in_s=time.perf_counter() - t0)

    def go(self):
        if self.control == "sleep":
            time.sleep(self.stall_s)
        elif self.control == "stop":
            pid = os.getpid()
            subprocess.Popen(["/bin/sh", "-c", f"sleep 0.03; kill -STOP {pid}; sleep {self.stall_s}; kill -CONT {pid}"])
        elif self.control == "device":
            burn, x, n = self._burn
            self._burning = burn(x, n)  # dispatched, never fetched: the chain queues behind it
        else:
            t0 = time.perf_counter()
            gc.collect()
            print(f"control=collect full_collection_s={time.perf_counter() - t0}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True, choices=tuple(EXPECTED))
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--at-share", type=float, default=0.33)
    ap.add_argument("--stall-seconds", type=float, default=1.5)
    args, rest = ap.parse_known_args(argv)
    if not 0 < args.at_share < 1 or args.stall_seconds <= 0:
        ap.error("--at-share lies in (0, 1) and --stall-seconds above 0")
    from benchmarks import run
    from benchmarks.lib import harness
    from deepspeed_tpu.telemetry import tracer

    plant = Plant(args.control, args.at_share * args.seconds, args.stall_seconds)
    plant.install()
    code = run.main(rest + ["--seconds", str(args.seconds)])
    stalls = list(plant.engine.stalls) if plant.engine is not None else []
    for i, stall in enumerate(stalls):
        harness.say(stall=i, **stall._asdict())
    found = [s.cause for s in stalls]
    harness.say(planted=args.control, at_s=plant.planted_at, expected=EXPECTED[args.control],
                found=",".join(found) or "-", calls=len(plant.engine.calls) if plant.engine is not None else 0,
                gc_seconds=tracer.gc_seconds())
    return code or int(EXPECTED[args.control] not in found)


if __name__ == "__main__":
    sys.exit(main())
