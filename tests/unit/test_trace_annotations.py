"""The program's spans in a ``jax.profiler`` trace: under a profiler session on
the CPU backend a tiny ``train_batch`` and a tiny ``generate`` leave their
``dstpu:`` events in the host plane with their args, properly nested, with the
tracer disabled and enabled; with no session and the tracer disabled nothing is
recorded anywhere, while the serving loop's call log fills all the same. A
stall planted through ``generate`` leaves its ``serve:stall`` span, the
collector its ``gc`` spans, every ``serve:fetch`` its ``cadence_ms``."""

import gc
import glob
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.diagnostics.anomaly import beyond
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM, TransformerConfig, causal_lm_spec
from deepspeed_tpu.telemetry import get_tracer

CFG = TransformerConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=2, max_seq_len=64)
TRAIN_SPANS = {"train_batch", "data", "step", "post_step"}
SERVE_SPANS = {"serve:generate", "serve:setup", "serve:admit", "serve:schedule", "serve:assemble",
               "serve:dispatch", "serve:fetch", "serve:accept", "serve:finish"}


@pytest.fixture(params=[False, True], ids=["tracer_off", "tracer_on"])
def tracer(request):
    tr = get_tracer()
    tr.configure(enabled=request.param)
    tr.reset()
    yield tr
    tr.configure(enabled=False)
    tr.reset()


@pytest.fixture(scope="module")
def trainer():
    eng, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(CFG, example_seq_len=16),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1}, "steps_per_print": 10_000})
    eng.train_batch(_batch(eng))  # compile outside every traced block
    return eng


@pytest.fixture(scope="module")
def server():
    params = CausalLM(CFG).init({"params": jax.random.PRNGKey(0)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    eng = InferenceEngineV2(CFG, params, {"max_seqs": 4, "decode_chain": 4, "kv_block_size": 8,
                                          "num_kv_blocks": 32, "row_bucket": 4, "chunk_bucket": 16})
    eng.generate(_prompts(), max_new_tokens=6)
    return eng


@pytest.fixture(scope="module")
def busy_server():
    """A model wide enough that a chain's device time passes the host's share
    of a call, as in every cell on the chip: a chain ahead is then waited for,
    and one that is ready at once says that the host was away."""
    cfg = TransformerConfig(vocab_size=256, hidden_size=256, intermediate_size=1024,
                            num_layers=2, num_heads=4, max_seq_len=128)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(0)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    eng = InferenceEngineV2(cfg, params, {"max_seqs": 8, "decode_chain": 8, "kv_block_size": 8,
                                          "num_kv_blocks": 160, "row_bucket": 8, "chunk_bucket": 16})
    eng.generate(_prompts(8), max_new_tokens=97)  # compiled, and the chains' class has its cadences
    return eng


def _batch(eng):
    return {"input_ids": np.random.default_rng(0).integers(
        0, 64, (eng.train_batch_size, 16), dtype=np.int32)}


def _prompts(n=3):
    return [np.arange(5, dtype=np.int32) + i for i in range(n)]


def _dstpu_events(trace_dir, collector=False):
    """``(name, start_ns, end_ns, args)`` of every dstpu: event, per host line;
    the collector's ``gc`` spans, which fall where they fall and on any
    thread, only where asked for."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name[len("dstpu:"):], e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("dstpu:") and (collector or e.name != "dstpu:gc")]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return lines


def _assert_nested(events):
    """Spans of one thread never overlap in part: each lies inside or after
    every span that started before it."""
    open_ends = []
    for _, start, end, _ in events:
        while open_ends and open_ends[-1] <= start:
            open_ends.pop()
        assert not open_ends or end <= open_ends[-1]
        open_ends.append(end)


def test_train_batch_spans_are_in_the_profiler_trace(tracer, trainer, tmp_path):
    first = trainer._batch_count
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            jax.block_until_ready(trainer.train_batch(_batch(trainer))["loss"])
    (events,) = _dstpu_events(tmp_path)
    assert {e[0] for e in events} == TRAIN_SPANS
    _assert_nested(events)
    batches = [e for e in events if e[0] == "train_batch"]
    assert [e[3]["step"] for e in batches] == [first, first + 1]
    for _, start, end, _ in batches:  # data, step and post_step inside each train_batch
        inner = [e[0] for e in events if start <= e[1] and e[2] <= end][1:]
        assert inner == ["data", "step", "post_step"]
    # the tracer's own buffer follows its switch, not the profiler's
    assert {e["name"] for e in tracer.events()} == (TRAIN_SPANS if tracer.enabled else set())


def test_generate_spans_are_in_the_profiler_trace(tracer, server, tmp_path):
    first_chain = server.chain_steps
    with jax.profiler.trace(str(tmp_path)):
        outs = server.generate(_prompts(), max_new_tokens=6)
    assert [len(o) for o in outs] == [6, 6, 6]
    (events,) = _dstpu_events(tmp_path)
    assert {e[0] for e in events} == SERVE_SPANS
    _assert_nested(events)
    assert all(e[3]["cadence_ms"] > 0 for e in events if e[0] == "serve:fetch")
    assert events[0][0] == "serve:generate" and events[0][3]["requests"] == 3
    admit = next(e[3] for e in events if e[0] == "serve:admit")
    assert (admit["requests"], admit["tokens"], admit["queue_len"], admit["rids"]) == (3, 15, 3, "0 1 2")
    prefill = next(e[3] for e in events if e[0] == "serve:dispatch" and e[3]["kind"] == "prefill")
    assert (prefill["rows"], prefill["live"], prefill["rids"]) == (4, 3, "0 1 2")
    # 1 token from the prefill, then 5 more: two chains of k=4, each with its own id on
    # every one of its spans
    for chain in (first_chain, first_chain + 1):
        mine = {e[0]: e[3] for e in events if e[3].get("chain") == chain}
        assert set(mine) == {"serve:schedule", "serve:assemble", "serve:dispatch",
                             "serve:fetch", "serve:accept"}
        assert (mine["serve:dispatch"]["rows"], mine["serve:dispatch"]["live"],
                mine["serve:dispatch"]["k"]) == (4, 3, 4)
        assert (mine["serve:schedule"]["active"], mine["serve:schedule"]["preempted"]) == (3, 0)
    emitted = [e[3]["emitted"] for e in events if e[0] == "serve:accept"]
    assert emitted == [3, 12, 3]  # prefill, a full chain, the tail
    assert bool(tracer.events()) == tracer.enabled


def test_idle_wait_span_under_open_loop_arrivals(server, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        server.generate(_prompts()[:1], max_new_tokens=2, arrival_times=[0.03])
    (events,) = _dstpu_events(tmp_path)
    waits = [e for e in events if e[0] == "serve:idle_wait"]
    assert waits and waits[0][3]["queue_len"] == 1
    assert sum(e[2] - e[1] for e in waits) >= 0.02e9


def test_nothing_is_recorded_without_a_session_and_with_the_tracer_off(trainer, server):
    tr = get_tracer()
    assert not tr.enabled and not tr.recording()
    trainer.train_batch(_batch(trainer))
    server.generate(_prompts(), max_new_tokens=6)
    assert tr.events() == [] and tr.registry.snapshot() == {}
    assert server._span_rids([0, 1]) == ""  # not even formatted


# ------------------------------------------------------------ the call log
def test_the_call_log_fills_with_the_tracer_off_and_no_session_on(server):
    """One record a device call, stamped at the four edges, its cadence
    known; and still nothing anywhere in the tracer."""
    tr = get_tracer()
    assert not tr.enabled and not tr.recording()
    before, first_chain = len(server.calls), server.chain_steps
    server.generate(_prompts(), max_new_tokens=6)
    new = list(server.calls)[before:]
    assert [(c.kind, c.chain, c.rows, c.k) for c in new] == [
        ("prefill", -1, 4, 16), ("chain", first_chain, 4, 4), ("chain", first_chain + 1, 4, 4)]
    for c in new:
        walls = [c.dispatch_open.wall, c.dispatch_close.wall, c.fetch_open.wall, c.fetch_close.wall]
        assert walls == sorted(walls) and c.cadence_s > 0 and c.fetch_close.cpu >= c.dispatch_open.cpu
    ahead = new[-1]  # dispatched before the chain before it was fetched: its cadence runs from that fetch's end
    assert ahead.dispatch_open.wall < new[-2].fetch_close.wall
    assert ahead.cadence_s == ahead.fetch_close.wall - new[-2].fetch_close.wall
    assert new[1].cadence_s == new[1].fetch_close.wall - new[1].dispatch_open.wall  # nothing was in flight
    server.put([901], [np.arange(5, dtype=np.int32)])
    server.flush(901)
    assert (server.calls[-1].kind, server.calls[-1].rows, server.calls[-1].k) == ("put", 4, 16)
    assert tr.events() == [] and tr.registry.snapshot() == {}


def _heap_whose_collection_takes(seconds, most):
    """A heap of small lists grown until a full collection of it takes
    ``seconds`` on this host, whatever its speed (or it holds ``most``), with
    what the last one took. It is left in the oldest generation, where no
    young collection walks it."""
    heap = []
    while True:
        t0 = time.perf_counter()
        gc.collect()
        took = time.perf_counter() - t0
        if took >= seconds or len(heap) >= most:
            return heap, took
        gc.disable()
        heap.extend([] for _ in range(max(1_000_000, len(heap) // 2)))
        gc.enable()


def _plant(server, monkeypatch, plants):
    """``plants[n]()`` runs before the n-th ``decode_chain`` of the next generate."""
    honest, n = server.decode_chain, [0]

    def decode_chain(*args, **kwargs):
        n[0] += 1
        plants.get(n[0], lambda: None)()
        return honest(*args, **kwargs)

    monkeypatch.setattr(server, "decode_chain", decode_chain)


def test_planted_stalls_leave_their_spans_in_the_profiler_trace(tracer, busy_server, tmp_path, monkeypatch):
    """A sleep between two calls (a chain in flight ahead: it is ready at once
    afterwards) and a full collection of a large heap, planted through
    ``generate``; in the trace a ``serve:stall`` span with its args right
    after the fetch that decided it, the ``gc`` spans, ``cadence_ms`` on
    every ``serve:fetch``."""
    server = busy_server
    # how long the host has to be held for the rule to call it a stall, on this host under its load of the moment:
    # a quarter of a second or six MADs over the chains' median, and a chain runs on under it
    _, chain_s, mad = beyond([c.cadence_s for c in server.calls if c.kind == "chain"][-64:], 0.0, 6.0)
    needed = max(0.25, 6 * mad) + 2 * chain_s + 0.35
    heap, _ = _heap_whose_collection_takes(0.1, 8_000_000)
    took = {"s": 0.0, "longest": 0.0}

    def collect():  # full collections of the large heap, as many as it takes
        while took["s"] < needed:
            t0 = time.perf_counter()
            gc.collect()
            took["longest"] = max(took["longest"], time.perf_counter() - t0)
            took["s"] += time.perf_counter() - t0

    first, seen = server.chain_steps, len(server.stalls)
    _plant(server, monkeypatch, {5: lambda: time.sleep(needed), 10: collect})
    with jax.profiler.trace(str(tmp_path)):
        server.generate(_prompts(8), max_new_tokens=97)
    del heap
    stalls = {s.chain: s for s in list(server.stalls)[seen:]}
    slept, collected = stalls[first + 4], stalls[first + 9]
    # (the chain in flight ran on under the sleep: the excess is the sleep less a chain's usual time)
    assert slept.cause == "host_not_running" and needed - 2 * chain_s - 0.1 < slept.excess_s < needed + 1.0
    assert slept.cpu_s < 0.1 and slept.next_wait_s == slept.in_fetch_s < 0.25 * slept.usual_fetch_s
    assert collected.cause == "collector" and collected.gc_s >= 0.9 * took["s"] and collected.excess_s >= 0.25
    lines = _dstpu_events(tmp_path, collector=True)
    events = max(lines, key=len)
    _assert_nested(events)
    spans = {e[3]["chain"]: e for e in events if e[0] == "serve:stall"}
    _, start, end, args = spans[first + 4]
    assert set(args) == {"chain", "kind", "rows", "seconds", "excess_s", "in_fetch_s", "cpu_s", "gc_s",
                         "next_wait_s", "cause"}
    assert (args["cause"], args["kind"], args["rows"]) == ("host_not_running", "chain", 8)
    assert args["excess_s"] == pytest.approx(slept.excess_s) and args["seconds"] == pytest.approx(slept.seconds)
    # decided when the NEXT call was fetched: the span stands right after that fetch, inside serve:generate
    next_fetch = next(e for e in events if e[0] == "serve:fetch" and e[3].get("chain") == first + 5)
    assert next_fetch[2] <= start and start - next_fetch[2] < 0.05e9
    fetches = [e for e in events if e[0] == "serve:fetch"]
    assert fetches and all(e[3]["cadence_ms"] > 0 for e in fetches)
    slow = next(e for e in fetches if e[3].get("chain") == first + 4)
    assert slow[3]["cadence_ms"] == pytest.approx(1e3 * slept.seconds, abs=0.01)
    pauses = [e for line in lines for e in line if e[0] == "gc"]
    full = max(pauses, key=lambda e: e[2] - e[1])
    assert full[3]["generation"] == 2 and full[3]["collected"] >= 0
    assert (full[2] - full[1]) * 1e-9 == pytest.approx(took["longest"], rel=0.2)
    # counters on an enabled tracer only; the registry of a disabled one stays empty
    counters = tracer.registry.counters()
    if tracer.enabled:
        assert counters["serving/stalls"] >= 1 and counters["serving/stall_s"] >= slept.excess_s
        assert any(e["name"] == "serve:stall" for e in tracer.events())
        assert not any(e["name"] == "gc" for e in tracer.events())  # the collector's span is the bare annotation
    else:
        assert tracer.registry.snapshot() == {} and tracer.events() == []


def test_a_collection_inside_a_section_that_holds_the_tracer_s_lock_does_not_take_it(tracer):
    """A collection runs on whichever thread trips it, at any allocation: also
    where that thread holds ``Tracer._lock`` (``reset``, ``events``,
    ``append_events`` allocate under it), which is not reentrant. The hook
    called as the collector calls it, start and stop, with the lock held."""
    from deepspeed_tpu.telemetry import tracer as tracer_mod

    class _Lock:
        """``Tracer._lock``, but a second taking by its holder fails instead of hanging the test."""

        def __init__(self):
            self._lock = threading.Lock()

        def __enter__(self):
            assert self._lock.acquire(timeout=0.5), "taken while held: the process would hang here"

        def __exit__(self, *exc):
            self._lock.release()

    assert tracer_mod._collector in gc.callbacks and gc.callbacks.count(tracer_mod._collector) == 1
    honest, before = tracer._lock, tracer_mod.gc_seconds()
    tracer._lock = _Lock()
    gc.disable()  # (a collection of its own would call the hook between the two calls below)
    try:
        with tracer._lock:
            tracer_mod._collector("start", {"generation": 2, "collected": 0, "uncollectable": 0})
            time.sleep(0.01)
            tracer_mod._collector("stop", {"generation": 2, "collected": 5, "uncollectable": 0})
        with tracer.span("after"):  # the lock is free again, and the tracer's own spans still take it
            pass
    finally:
        gc.enable()
        tracer._lock = honest
    assert tracer_mod.gc_seconds() - before >= 0.01
    assert [e["name"] for e in tracer.events()] == (["after"] if tracer.enabled else [])


def test_a_stall_goes_into_the_flight_recorder_s_ring(tmp_path, monkeypatch):
    params = CausalLM(CFG).init({"params": jax.random.PRNGKey(0)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    eng = InferenceEngineV2(CFG, params, {"max_seqs": 4, "decode_chain": 4, "kv_block_size": 8, "num_kv_blocks": 32,
                                          "row_bucket": 4, "chunk_bucket": 16, "flight_recorder": True})
    eng.generate(_prompts(), max_new_tokens=30)
    _plant(eng, monkeypatch, {5: lambda: time.sleep(0.5)})
    eng.generate(_prompts(), max_new_tokens=40)
    (stall,) = [s for s in eng.stalls if s.excess_s > 0.45]
    import json

    rows = [json.loads(line) for line in open(eng._recorder.dump(path=str(tmp_path / "flight.jsonl")))]
    (row,) = [r for r in rows if r.get("stall") and r["metrics"]["excess_s"] > 0.45]
    assert row["kind"] == "step_record" and row["stall"] == stall.cause == row["metrics"]["cause"]
    assert row["step"] == stall.chain and row["metrics"]["seconds"] == pytest.approx(stall.seconds)


def test_an_arrival_that_falls_due_after_the_admission_pass_is_admitted_on_the_next_round(server):
    """An injected clock that moves a second a reading: the admission pass
    reads 1 s (the request is due at 1.5: not yet), the idle check reads 2 s
    (no wait left, nothing admitted, nothing active). That used to raise "KV
    pool too small for a single sequence"; the loop goes round again."""
    ticks = iter(range(1000))
    server._clock = lambda: float(next(ticks))
    try:
        (out,) = server.generate(_prompts()[:1], max_new_tokens=2, arrival_times=[1.5])
    finally:
        server._clock = time.perf_counter
    assert len(out) == 2


# ------------------------------------------------------------ device scopes
# (the Pallas kernels' names are in
# tests/unit/ops/test_kernel_names.py: on the CPU the engines take XLA attention)
TRAIN_SCOPES = ("embed", "layers", "lm_head_ce", "optimizer")
CHAIN_SCOPES = ("embed", "pool_scan", "layer", "kv_write", "lm_head", "sample")


def _lowered_programs(trainer, server):
    placed = trainer._shard_global_batch(_batch(trainer))
    step = trainer._train_step.lower(trainer.state, placed)
    rows, k = 4, 4
    chain = server._chain_fn(rows, k, None, (("do_sample", False),)).lower(
        server.params, server.pools, jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows, server.max_pages), jnp.int32), jnp.ones((rows,), bool),
        jnp.full((rows,), k, jnp.int32), jax.random.PRNGKey(0))
    return step, chain


def test_scopes_and_kernel_names_are_hlo_metadata_only(trainer, server, monkeypatch):
    """The train step and the decode chain carry the program's scopes in their
    op_names, and with every name-stack entry switched off they lower to the
    same StableHLO: the scopes change what a trace calls an operation, not
    what runs."""
    import re

    from jax._src import source_info_util

    with_scopes = _lowered_programs(trainer, server)
    for lowered, wanted in zip(with_scopes, (TRAIN_SCOPES, CHAIN_SCOPES)):
        text = lowered.as_text(debug_info=True)
        for scope in wanted:
            # a path component of an op_name (a scan body's are relative to the body)
            assert re.search(r'[/("]%s[/)"]' % scope, text), f"no op_name under scope {scope!r}"
    monkeypatch.setattr(source_info_util.ExtendNameStackContextManager, "__enter__",
                        lambda self: None)
    monkeypatch.setattr(source_info_util.ExtendNameStackContextManager, "__exit__",
                        lambda self, *exc: None)
    jax.clear_caches()  # or the cached traces, scopes and all, are handed back
    without = _lowered_programs(trainer, server)
    for a, b, scope in zip(with_scopes, without, ("optimizer", "pool_scan")):
        assert scope in a.as_text(debug_info=True) and scope not in b.as_text(debug_info=True)
        assert a.as_text() == b.as_text()
