"""Anomaly-triggered ``jax.profiler`` capture.

The step-time anomaly detector (diagnostics/anomaly.py) can tell you a step
was slow; it cannot tell you *why*. This module closes that gap: when the
detector flags a straggler or sustained regression — or an operator sends
SIGUSR2, or code calls :meth:`ProfilerCapture.arm` — the next N steps run
under ``jax.profiler.start_trace`` and the resulting trace directory is
dropped next to the flight record, referenced from the dump context and a
telemetry instant, so the post-mortem of a slow step holds the device
timeline that explains it.

Discipline:
  - **armed ≠ active**: arming is a flag flip (any thread, signal-safe);
    the trace starts only at the next step boundary on the training thread —
    ``jax.profiler`` must bracket whole dispatches, not fire mid-step.
  - **bounded**: each window traces ``steps`` steps then stops;
    ``cooldown_steps`` gates how soon another anomaly can trigger again, so
    a straggler storm cannot turn the run into one long profile.
  - **never breaks the step**: start/stop failures (profiler already active
    in-process, unsupported backend) log and disarm.

SIGUSR2 wiring mirrors the flight recorder's process hooks: one handler per
process, dispatching to live captures through a WeakSet, chaining to any
previous handler.
"""

from __future__ import annotations

import os
import signal
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger

_CAPTURES: "weakref.WeakSet[ProfilerCapture]" = weakref.WeakSet()
_HOOK_LOCK = threading.Lock()
_HOOK_INSTALLED = False
_PREV_HANDLER = None


def _sigusr2_handler(signum, frame):
    for cap in list(_CAPTURES):
        cap.arm(reason="signal:SIGUSR2")
    prev = _PREV_HANDLER
    if callable(prev):
        prev(signum, frame)


def install_sigusr2() -> None:
    """Install the SIGUSR2 → arm-capture hook (process-wide, once, main
    thread only — signal.signal raises elsewhere)."""
    global _HOOK_INSTALLED, _PREV_HANDLER
    with _HOOK_LOCK:
        if _HOOK_INSTALLED:
            return
        if threading.current_thread() is not threading.main_thread():
            return
        try:
            _PREV_HANDLER = signal.signal(signal.SIGUSR2, _sigusr2_handler)
            _HOOK_INSTALLED = True
        except (ValueError, OSError):  # pragma: no cover - exotic embedders
            pass


class ProfilerCapture:
    """Arms on trigger, traces the next N steps, records where the trace went.

    The engine brackets every step with :meth:`on_step_start` /
    :meth:`on_step_end` (one attribute check each when idle). ``captures``
    keeps one record per completed window so tests and the flight recorder
    can reference the trace without scraping logs.
    """

    def __init__(self, steps: int = 3, out_dir: Optional[str] = None,
                 cooldown_steps: int = 200, tracer=None, recorder=None):
        self.steps = max(int(steps), 1)
        self.cooldown_steps = max(int(cooldown_steps), 0)
        if out_dir is None:
            from deepspeed_tpu.telemetry.exporters import default_output_dir
            from deepspeed_tpu.telemetry.fleet import get_identity

            # per-process capture dir (proc 0 keeps the historical layout):
            # two replicas' device traces must land in joinable, distinct
            # directories, same policy as the flight-recorder dumps
            idx = get_identity().process_index
            sub = "profiler" if idx == 0 else f"profiler.p{idx}"
            out_dir = os.path.join(default_output_dir(), sub)
        self.out_dir = out_dir
        self.captures: List[Dict[str, Any]] = []
        self._armed_reason: Optional[str] = None
        self._active: Optional[Dict[str, Any]] = None
        self._last_window_step: Optional[int] = None
        if tracer is None:
            from deepspeed_tpu.telemetry import get_tracer

            tracer = get_tracer()
        self._tracer = tracer
        self._recorder = recorder  # FlightRecorder: trace path lands in dumps
        _CAPTURES.add(self)

    # ------------------------------------------------------------- triggers
    def arm(self, reason: str = "manual") -> None:
        """Request a capture window starting at the next step boundary.
        Idempotent while armed or active; any thread (signal handlers call
        this)."""
        if self._active is None and self._armed_reason is None:
            self._armed_reason = reason

    @property
    def active(self) -> bool:
        return self._active is not None

    # --------------------------------------------------------- step brackets
    def on_step_start(self, step: int) -> None:
        """Start the trace if armed (training thread, before dispatch)."""
        if self._armed_reason is None or self._active is not None:
            return
        if (self._last_window_step is not None
                and step - self._last_window_step < self.cooldown_steps):
            # inside the cooldown: drop the request, keep the run quiet
            self._armed_reason = None
            return
        reason = self._armed_reason
        self._armed_reason = None
        # a FAILED start consumes the cooldown too: a wedged in-process
        # profiler must not turn every subsequent anomaly into a retry storm
        self._last_window_step = step
        path = os.path.join(self.out_dir, f"step{step:06d}")
        try:
            import jax

            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except Exception as e:  # noqa: BLE001 — never break the step
            logger.warning(f"profiler capture failed to start ({reason}): {e}")
            try:  # best-effort: don't leave an empty stepNNNNNN dir behind
                os.rmdir(path)
            except OSError:
                pass
            return
        self._active = {"reason": reason, "path": path, "first_step": step,
                        "remaining": self.steps, "t0": time.perf_counter()}
        logger.warning(
            f"profiler capture armed by {reason}: tracing {self.steps} "
            f"step(s) from step {step} into {path}")

    def on_step_end(self, step: int) -> None:
        """Count the step; stop and record the window when it is full."""
        act = self._active
        if act is None:
            return
        act["remaining"] -= 1
        if act["remaining"] > 0:
            return
        self._active = None
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            logger.warning(f"profiler capture failed to stop: {e}")
            return
        from deepspeed_tpu.telemetry.fleet import get_identity

        ident = get_identity()
        record = {
            "reason": act["reason"],
            "trace_dir": act["path"],
            "first_step": act["first_step"],
            "last_step": step,
            "steps": self.steps,
            "wall_s": round(time.perf_counter() - act["t0"], 3),
            "run_id": ident.run_id,
            "process_index": ident.process_index,
        }
        self.captures.append(record)
        if self._tracer.enabled:
            self._tracer.count("anomaly/profiler_captures")
            self._tracer.instant("profiler_capture", cat="diagnostics", **record)
        if self._recorder is not None:
            # the crash dump's header names the freshest device trace
            self._recorder.set_context(profiler_trace=act["path"],
                                       profiler_trace_reason=act["reason"])
        logger.warning(
            f"profiler capture complete ({act['reason']}): steps "
            f"{act['first_step']}..{step} -> {act['path']}")
