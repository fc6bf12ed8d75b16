"""What every runner needs: finding a cell's files by name, the device check,
the compile cache and counter, which metrics a cell reports, the last line."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_workload(name: str, bench_dir: str = BENCH_DIR) -> dict:
    path = os.path.join(bench_dir, "workloads", name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no workload file {path}")
    return load_json(path)


def load_config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "configs", name + ".json"))


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_runner(kind: str, bench_dir: str = BENCH_DIR):
    return _load_module(os.path.join(bench_dir, "runners", kind + ".py"), f"bench_runner_{kind}")


def load_reference(architecture: str, bench_dir: str = BENCH_DIR):
    return _load_module(os.path.join(bench_dir, "reference", architecture + ".py"),
                        f"bench_reference_{architecture}")


def load_architecture(architecture: str, bench_dir: str = BENCH_DIR):
    """What the benchmark knows of how the program lays an architecture out
    and how its work is counted: ``architectures/<architecture>.py``."""
    return _load_module(os.path.join(bench_dir, "architectures", architecture + ".py"),
                        f"bench_architecture_{architecture}")


def load_benchmark(bench_dir: str = BENCH_DIR) -> dict:
    """``BENCHMARK.json`` beside ``benchmarks/``: the one place that says which
    metrics a cell reports, in which unit."""
    return load_json(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))


def cell_metrics(bench: dict, group: str, workload_name: str) -> List[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that the cell reports."""
    if not any(w["name"] == workload_name for w in bench["workloads"]):
        raise KeyError(f"{workload_name} is not a cell of BENCHMARK.json")
    return [m for m in bench[group] if workload_name in m.get("workloads", [workload_name])]


def load_reader(metric_name: str, bench_dir: str = BENCH_DIR):
    """A per-layer metric's reader, ``read(run, trace) -> number or None``:
    ``metrics/<name>.py``, or ``metrics/<name without its last suffix>.py``
    where one reader serves the metric under several suffixes
    (``idle_share.train``, ``idle_share.batch``)."""
    for stem in (metric_name, metric_name.rpartition(".")[0]):
        path = os.path.join(bench_dir, "metrics", stem + ".py")
        if stem and os.path.isfile(path):
            return _load_module(path, "bench_metric_" + stem).read
    raise FileNotFoundError(f"no reader under {bench_dir}/metrics for {metric_name}")


def read_metrics(entries: List[dict], run: dict, trace, bench_dir: str = BENCH_DIR) -> Dict[str, dict]:
    """The per-layer part of the last line. A reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], bench_dir)(run, trace)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def require_devices(chips: int) -> list:
    """The first ``chips`` TPU devices, or NoDevice. Tests steer this."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX reports platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s), JAX reports {len(devices)}")
    return devices[:chips]


def memory_held(devices) -> Dict[str, int]:
    """What the fullest chip holds NOW, from one ``memory_stats()`` call:
    ``held_bytes`` = live buffers (``bytes_in_use``) + what the runtime has
    set aside for the loaded programs' temporaries (``bytes_reserved``; the
    v5e keeps that reservation between runs of a program: it reads the same
    with no program running), and the two peak counters beside it. A runner
    calls this at the end of its window, before it lets go of anything."""
    fullest = {"held_bytes": 0, "peak_bytes_in_use": 0, "peak_bytes_reserved": 0}
    for dev in devices:
        stats = dev.memory_stats() or {}
        say(memory_stats=dev.id, **dict(sorted(stats.items())))
        held = int(stats.get("bytes_in_use", 0)) + int(stats.get("bytes_reserved", 0))
        if held >= fullest["held_bytes"]:
            fullest = {"held_bytes": held,
                       "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                       "peak_bytes_reserved": int(stats.get("peak_bytes_reserved", 0))}
    return fullest


def device_report(devices, memory: Dict[str, int]) -> dict:
    import jax

    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
            "memory_peak_bytes": memory["held_bytes"]}


def enable_compile_cache() -> str:
    """The program's own rule (JAX_COMPILATION_CACHE_DIR wins, else the fixed
    ``<checkout>/.jax_cache``), and every program cached however fast it
    compiled, so that a cell's second run compiles nothing."""
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts XLA backend compilations (cache hits included: a program that
    has to be looked up inside the window was not warmed) between marks."""

    def __init__(self):
        import jax.monitoring

        self.total = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, *_, **__):
        if name == BACKEND_COMPILE_EVENT:
            self.total += 1

    def mark(self) -> None:
        self._mark = self.total

    def since_mark(self) -> int:
        return self.total - self._mark


def say(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class Phases:
    """Prints how long each part of set-up took, on a line of its own."""

    def __init__(self, t_process_start: float):
        import time

        self._clock = time.perf_counter
        self._last = self._clock()
        say(setup_phase="start_up_and_imports", seconds=self._last - t_process_start)

    def done(self, name: str) -> None:
        now = self._clock()
        say(setup_phase=name, seconds=now - self._last)
        self._last = now


class TraceWindow:
    """The profiler over part of a run, with the ``bench:window`` span that
    tells the reducer where the traced window starts and ends."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.active = False

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.trace_dir)
        self._span = jax.profiler.TraceAnnotation("bench:window")
        self._span.__enter__()
        self.active = True

    def stop(self) -> None:
        import jax

        if self.active:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False


def last_line(correct: bool, attempted: int, failed: int, metrics: Dict[str, dict],
              device: dict, compared: Dict[str, list], breakdown: Optional[dict] = None) -> str:
    """``compared``: each number that decided ``correct`` as ``[found, limit]``,
    under a key that comes last, where a record cut to its end still has it."""
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)
