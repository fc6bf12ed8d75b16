"""Milliseconds of the traced window inside the garbage collector: the
``dstpu:gc`` spans (one a collection, whatever thread tripped it, clipped to
the window), which the program's one ``gc.callbacks`` entry opens
(``deepspeed_tpu/telemetry/tracer.py``). A window may hold no collection at
all, so the spans cannot say by themselves whether the program has the hook:
the marker is the hook's own counter, ``telemetry.tracer.gc_seconds``, in the
program this process runs. With it and no span 0.0; without it (the parent of
the hook) the metric is left out. Serves ``gc_pause_ms.batch`` and
``gc_pause_ms.train``."""

from benchmarks.lib import spans


def hooked() -> bool:
    from deepspeed_tpu.telemetry import tracer

    return hasattr(tracer, "gc_seconds")


def read(run, trace):
    pauses = spans.named(spans.of_run(run), "gc")
    if pauses:
        return 1e3 * sum(s.seconds for s in pauses)
    return 0.0 if hooked() else None
