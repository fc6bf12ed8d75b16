"""Deterministic fault injection: the tool that proves recovery works.

The resilience stack (async snapshots in ``checkpoint/snapshot.py``, the
rewind supervisor in ``elasticity/resilience.py``) is only as real as the
faults it has survived. This module injects the three failure classes the
stack claims to handle, each deterministically (a given seed/step always
produces the same fault — flaky fault tests are worse than none):

  - **NaN gradients at step K** — a NaN planted in the batch poisons the
    whole backward (the same propagation path a bad data shard takes in
    production; the idiom the diagnostics test suite established). The
    in-step health probes then fire ``nonfinite`` with whatever policy is
    configured.
  - **writer killed mid-save** — the snapshot writer thread raises between
    two shard writes (or before the manifest / the commit rename), leaving a
    ``*.tmp-*`` directory and an untouched ``latest`` pointer: the
    crash-mid-save atomicity claim, made testable.
  - **shard truncated on disk** — post-commit corruption (bit rot, a
    truncated copy): the manifest checksum must catch it BEFORE any device
    state is touched and the loader must fall back to the previous tag.

Used by ``tests/unit/checkpoint/test_snapshot.py``,
``tests/unit/aux/test_resilience.py`` and ``tools/fault_smoke.py``.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Iterable, Optional

import numpy as np

from deepspeed_tpu.utils.logging import logger


class InjectedWriterCrash(RuntimeError):
    """Raised inside the snapshot writer thread by :meth:`FaultInjector.kill_writer`."""


def poison_batch(batch: Any, value: float = float("nan")) -> Any:
    """Copy of ``batch`` with ``value`` planted in the first element of every
    float leaf — one poisoned element is enough to NaN the whole backward."""
    out = {}
    poisoned = False
    for k, v in batch.items():
        arr = np.array(v, copy=True)
        if np.issubdtype(arr.dtype, np.floating) and arr.size:
            arr.flat[0] = value
            poisoned = True
        out[k] = arr
    if not poisoned:
        raise ValueError(
            "poison_batch: no float leaf to poison (integer-only batches "
            "need a model-level injection point)")
    return out


class FaultInjector:
    """One injector instance per experiment; every injection is logged and
    counted so a test can assert the fault actually fired."""

    def __init__(self):
        self.nan_steps_fired: list = []
        self.writer_kills_fired: int = 0
        self.daemon_kills_fired: int = 0

    # ------------------------------------------------------------- NaN grads
    def nan_batch_fn(
        self,
        batch_fn: Callable[[int], Any],
        at_steps: Iterable[int],
        repeat: bool = False,
    ) -> Callable[[int], Any]:
        """Wrap a deterministic ``batch_fn(step)`` so the batch for each step
        in ``at_steps`` comes back NaN-poisoned. ``repeat=False`` (default)
        injects each step's fault ONCE — a rewind that replays the step gets
        the clean batch, modeling a transient fault; ``repeat=True`` keeps
        poisoning on every replay, modeling a deterministic fault (the
        give-up path)."""
        pending = set(int(s) for s in at_steps)
        always = frozenset(pending) if repeat else None

        def wrapped(step: int) -> Any:
            fire = (step in always) if repeat else (step in pending)
            if not fire:
                return batch_fn(step)
            if not repeat:
                pending.discard(step)
            self.nan_steps_fired.append(step)
            logger.warning(f"faultinject: NaN planted in the batch for step {step}")
            return poison_batch(batch_fn(step))

        return wrapped

    def poison_engine_params(self, engine, value: float = float("nan")) -> int:
        """Plant ``value`` in the first element of EVERY float param leaf ON
        DEVICE — the model-level injection point for integer-batch models (a
        causal LM's ``input_ids`` carries no float to poison). Every-leaf
        coverage is deliberate: a single poisoned element can sit outside the
        compute path (an embedding row no token id gathers propagates NOTHING
        — its grad is a zero scatter, not NaN), but a NaN in every dense
        kernel/norm reaches the loss on any input. A snapshot restore
        replaces params wholesale, so the fault is transient across a rewind
        by construction. Returns the number of leaves poisoned."""
        import jax

        from deepspeed_tpu.utils.compat import device_put_unaliased

        leaves, treedef = jax.tree_util.tree_flatten_with_path(engine.state.params)
        new_leaves, n = [], 0
        for _path, leaf in leaves:
            arr = np.asarray(jax.device_get(leaf))
            if np.issubdtype(arr.dtype, np.floating) and arr.size:
                arr = np.array(arr, copy=True)
                arr.flat[0] = value
                leaf = device_put_unaliased(arr, leaf.sharding)
                n += 1
            new_leaves.append(leaf)
        if not n:
            raise ValueError("poison_engine_params: no float param leaf to poison")
        engine.state = engine.state._replace(
            params=jax.tree_util.tree_unflatten(treedef, new_leaves))
        logger.warning(f"faultinject: NaN planted in {n} param leaves")
        return n

    def flip_param_bit(self, engine, replica_index: int = -1, bit: int = 20,
                       element: int = 0) -> str:
        """Flip ONE mantissa bit of ONE element on ONE replica's copy of the
        first replicated float param leaf — the single-replica silent-
        corruption fault (an SDC/cosmic-ray flip, or a diverged lossy
        collective) the numerics divergence sentinel exists to catch.

        Unlike :meth:`poison_engine_params` (which poisons every replica
        identically and is therefore INVISIBLE to a cross-replica digest),
        this edits exactly one addressable shard's buffer, so replicas
        physically disagree afterwards. Deterministic: the same
        (replica_index, bit, element) always flips the same bit. Returns
        the path-string of the leaf flipped."""
        import jax

        leaves = jax.tree_util.tree_flatten_with_path(engine.state.params)[0]
        for path, leaf in leaves:
            arr_dtype = np.asarray(jax.device_get(
                leaf.addressable_shards[0].data)).dtype if leaf.addressable_shards else None
            if arr_dtype is None or not np.issubdtype(arr_dtype, np.floating):
                continue
            shards = [np.array(np.asarray(s.data), copy=True)
                      for s in leaf.addressable_shards]
            # only a leaf with >1 replica copy can disagree: find two shards
            # holding identical data (a fully-sharded leaf has none)
            if len(shards) < 2 or not any(
                    np.array_equal(shards[0], s) for s in shards[1:]):
                continue
            target = shards[replica_index % len(shards)]
            if target.size <= element or target.dtype != np.float32:
                # the master params are fp32; a sub-fp32 leaf would round
                # the flip away on the astype round trip — skip it
                continue
            flat = np.ascontiguousarray(target)
            flat.view(np.uint32).flat[element] ^= np.uint32(1 << bit)
            shards[replica_index % len(shards)] = flat
            bufs = [jax.device_put(s, sh.device)
                    for s, sh in zip(shards, leaf.addressable_shards)]
            new_leaf = jax.make_array_from_single_device_arrays(
                leaf.shape, leaf.sharding, bufs)
            key = jax.tree_util.keystr(path)
            params = jax.tree_util.tree_map_with_path(
                lambda p, l: new_leaf if p == path else l,
                engine.state.params)
            engine.state = engine.state._replace(params=params)
            logger.warning(
                f"faultinject: flipped bit {bit} of element {element} on "
                f"replica shard {replica_index % len(shards)} of param "
                f"{key} — replicas now physically disagree")
            return key
        raise ValueError(
            "flip_param_bit: no replicated float param leaf to corrupt "
            "(every leaf is fully sharded or non-float)")

    def nan_params_fn(
        self,
        engine,
        batch_fn: Callable[[int], Any],
        at_steps: Iterable[int],
    ) -> Callable[[int], Any]:
        """Wrap a deterministic ``batch_fn(step)`` so the ENGINE PARAMS are
        NaN-poisoned just before each step in ``at_steps`` — the injection
        path for models whose batches carry no float leaf. Each step fires
        once; the rewind's restore replaces the poisoned params, so replays
        run clean (transient-fault semantics, like ``nan_batch_fn``'s
        default)."""
        pending = set(int(s) for s in at_steps)

        def wrapped(step: int) -> Any:
            if step in pending:
                pending.discard(step)
                self.nan_steps_fired.append(step)
                self.poison_engine_params(engine)
            return batch_fn(step)

        return wrapped

    # ------------------------------------------------------- writer crashes
    def kill_writer(self, manager, after_shards: int = 1, times: int = 1,
                    at: str = "shard") -> None:
        """Arm ``manager`` (a SnapshotManager) so its writer thread crashes
        mid-save: at the ``after_shards``-th shard write (``at='shard'``),
        before the manifest (``at='manifest'``) or just before the commit
        rename (``at='commit'``). Fires ``times`` saves, then disarms —
        subsequent snapshots succeed (transient disk fault semantics)."""
        if at not in ("shard", "manifest", "commit"):
            raise ValueError(f"kill_writer at={at!r}: shard|manifest|commit")
        state = {"remaining": int(times)}

        def hook(event: str, index: int) -> None:
            if state["remaining"] <= 0:
                return
            if event == at and (event != "shard" or index >= after_shards):
                state["remaining"] -= 1
                self.writer_kills_fired += 1
                logger.warning(
                    f"faultinject: killing snapshot writer at {event}[{index}]")
                raise InjectedWriterCrash(
                    f"injected writer crash at {event}[{index}]")

        manager.fault_hook = hook

    # ----------------------------------------------------- fabric / process
    def kill_replica_daemon(self, proc_or_pid) -> int:
        """SIGKILL a serving-fabric replica daemon (ISSUE 18): the hard-death
        case — no drain, no flush, the HTTP socket just goes away. The
        router must detect it via heartbeat/dispatch failure and re-admit
        the replica's admitted-but-unfinished requests elsewhere. Accepts a
        ``subprocess.Popen`` or a raw pid; returns the pid killed."""
        import signal

        pid = int(getattr(proc_or_pid, "pid", proc_or_pid))
        os.kill(pid, signal.SIGKILL)
        wait = getattr(proc_or_pid, "wait", None)
        if wait is not None:
            try:
                wait(timeout=10.0)  # reap so the test sees returncode set
            except Exception:
                pass
        self.daemon_kills_fired += 1
        logger.warning(f"faultinject: SIGKILLed replica daemon pid={pid}")
        return pid

    # --------------------------------------------------- on-disk corruption
    @staticmethod
    def truncate_shard(base_dir: str, tag: Optional[str] = None,
                       shard_index: int = 0, keep_bytes: int = 16) -> str:
        """Truncate one committed shard file to ``keep_bytes`` — the checksum
        in the manifest no longer matches. Returns the file truncated."""
        from deepspeed_tpu.checkpoint import snapshot as snap

        tag = tag or snap.latest_tag(base_dir)
        if tag is None:
            raise FileNotFoundError(f"no snapshots under {base_dir}")
        manifest = snap.read_manifest(base_dir, tag)
        shard = manifest["shards"][shard_index]
        path = os.path.join(snap.snapshot_root(base_dir), tag, shard["file"])
        with open(path, "r+b") as f:
            f.truncate(keep_bytes)
        logger.warning(f"faultinject: truncated {path} to {keep_bytes} bytes")
        return path

    @staticmethod
    def corrupt_manifest(base_dir: str, tag: Optional[str] = None) -> str:
        """Overwrite a committed manifest with junk (an interrupted rewrite /
        filesystem fault). Returns the path corrupted."""
        from deepspeed_tpu.checkpoint import snapshot as snap

        tag = tag or snap.latest_tag(base_dir)
        if tag is None:
            raise FileNotFoundError(f"no snapshots under {base_dir}")
        path = os.path.join(snap.snapshot_root(base_dir), tag, snap.MANIFEST_FILE)
        with open(path, "w") as f:
            f.write("{not json")
        logger.warning(f"faultinject: corrupted {path}")
        return path

    # ------------------------------------------------------------- reporting
    def summary(self) -> Dict[str, Any]:
        return {
            "nan_steps_fired": list(self.nan_steps_fired),
            "writer_kills_fired": self.writer_kills_fired,
            "daemon_kills_fired": self.daemon_kills_fired,
        }
