"""The program's spans in a ``jax.profiler`` trace: under a profiler session on
the CPU backend a tiny ``train_batch`` and a tiny ``generate`` leave their
``dstpu:`` events in the host plane with their args, properly nested, with the
tracer disabled and enabled; with no session and the tracer disabled nothing is
recorded anywhere."""

import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
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


def _batch(eng):
    return {"input_ids": np.random.default_rng(0).integers(
        0, 64, (eng.train_batch_size, 16), dtype=np.int32)}


def _prompts():
    return [np.arange(5, dtype=np.int32) + i for i in range(3)]


def _dstpu_events(trace_dir):
    """``(name, start_ns, end_ns, args)`` of every dstpu: event, per host line."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name[len("dstpu:"):], e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("dstpu:")]
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
        server.params, server.pool, jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
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
