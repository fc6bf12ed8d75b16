"""Serving router over N engine replicas (ISSUE 12 tentpole leg a).

Contract under test:
  - routed output is token-identical to a single engine serving the same
    prompts (greedy; each replica runs the unchanged fast path)
  - SLO admission gate: shed/defer/admit decisions pinned against a fake
    clock; a loop-level run with an unmeetable TTFT budget sheds everything
    BEFORE dispatching (admitted requests are never dropped)
  - preemption re-queues replica-affine: the request re-enters through the
    SAME replica (where its prefix-cache blocks live) and still finishes
    with the correct tokens
  - telemetry: router/* counters + per-replica gauges, per-replica
    serving/* SLO metrics (labelled replica=i), one Perfetto track per
    replica with a slice per dispatched program
"""

import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, ServingRouter
from deepspeed_tpu.inference.config import ServingSLOConfig
from deepspeed_tpu.inference.router import REPLICA_TRACK_BASE
from deepspeed_tpu.telemetry import chrome_trace_events, get_tracer

from .test_inference_v2 import make_model


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    tr = get_tracer()
    tr.configure(enabled=False)
    tr.reset()
    yield
    tr.configure(enabled=False)
    tr.reset()


BASE = {"dtype": "fp32", "kv_block_size": 4, "num_kv_blocks": 64,
        "chunk_bucket": 8, "decode_chain": 4, "hbm_check": "off"}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------------------------- parity
def test_router_greedy_parity_with_single_engine():
    cfg, _, params = make_model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (7, 3, 5, 6, 4, 8)]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=8)
    router = ServingRouter.build(cfg, params, BASE, replicas=2)
    outs = router.serve(prompts, max_new_tokens=8)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    # the load balancer actually spread the work
    assert all(d > 0 for d in router.stats()["dispatches"])


def test_router_parity_with_prefix_cache_and_spec():
    """The whole serving tier composed: 2 replicas, content-hash prefix
    cache, speculative chains — still token-identical to the plain single
    engine."""
    cfg, _, params = make_model(seed=1)
    rng = np.random.RandomState(1)
    shared = rng.randint(0, cfg.vocab_size, (8,))
    prompts = [np.concatenate([shared, rng.randint(0, cfg.vocab_size, (n,))])
               for n in (3, 5, 2, 4)]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=8)
    router = ServingRouter.build(
        cfg, params, dict(BASE, prefix_cache=True, spec_decode=3), replicas=2)
    # two waves: the first populates each replica's prefix cache, the
    # second's admissions hit it (requests admitted in one batched prefill
    # can't reuse blocks that very prefill is writing)
    outs = router.serve(prompts[:2], max_new_tokens=8)
    outs += router.serve(prompts[2:], max_new_tokens=8)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    cached = sum(r.engine.prefill_tokens_cached for r in router.replicas)
    assert cached >= 8  # wave-2 prompts reused the shared prefix


# -------------------------------------------------------- admission decisions
def _router_with_emas(slo, prefill_ema=0.0, chain_ema=0.0, replicas=2):
    cfg, _, params = make_model()
    r = ServingRouter.build(cfg, params, BASE, replicas=replicas,
                            slo=slo, clock=FakeClock())
    for rep in r.replicas:
        rep.prefill_ema = prefill_ema
        rep.chain_ema = chain_ema
    return r


def test_admission_decision_shed_fake_clock():
    """Projected TTFT = waited + replica prefill estimate, judged against
    ttft_ms * factor — exact decisions, no wall clock involved."""
    slo = ServingSLOConfig(ttft_ms=100.0, admission="shed",
                           admission_ttft_factor=1.0)
    r = _router_with_emas(slo, prefill_ema=0.040)
    rep = r.replicas[0]
    assert r._admission_decision(0.050, rep) == "admit"   # 90 <= 100 ms
    assert r._admission_decision(0.070, rep) == "shed"    # 110 > 100 ms
    # the factor loosens the gate
    r.slo = ServingSLOConfig(ttft_ms=100.0, admission="shed",
                             admission_ttft_factor=1.5)
    assert r._admission_decision(0.070, rep) == "admit"   # 110 <= 150 ms
    # a FULL replica (no admission capacity) adds one chain boundary to
    # the projection — its earliest admission slot
    r.slo = slo
    rep.chain_ema = 0.050
    for i in range(rep.engine.config.max_seqs):
        rep.active[i] = i
    assert r._admission_decision(0.020, rep) == "shed"    # 20+40+50 > 100
    rep.active.clear()
    assert r._admission_decision(0.020, rep) == "admit"   # 20+40 <= 100


def test_admission_decision_defer_vs_shed():
    """defer holds a request while ANY replica could make the budget; it
    sheds only when the wait alone has blown the budget everywhere."""
    slo = ServingSLOConfig(ttft_ms=100.0, admission="defer")
    r = _router_with_emas(slo, prefill_ema=0.200)  # every replica slow
    rep = r.replicas[0]
    r.replicas[1].prefill_ema = 0.010  # ...except replica 1
    assert r._admission_decision(0.050, rep) == "defer"  # rep 1 could admit
    r.replicas[1].prefill_ema = 0.200
    assert r._admission_decision(0.050, rep) == "defer"  # wait itself OK
    assert r._admission_decision(0.150, rep) == "shed"   # wait alone > budget


def test_admission_none_admits_everything():
    slo = ServingSLOConfig(ttft_ms=0.001, admission="none")
    r = _router_with_emas(slo, prefill_ema=10.0)
    assert r._admission_decision(99.0, r.replicas[0]) == "admit"


# ------------------------------------------------------------ loop-level SLO
def test_router_sheds_unmeetable_budget_before_dispatch():
    """ttft budget no real machine can meet: every request sheds (output
    None), nothing is dispatched, and — the invariant —
    nothing was dropped AFTER admission."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (5,)) for _ in range(4)]
    slo = ServingSLOConfig(ttft_ms=1e-4, admission="shed")
    router = ServingRouter.build(cfg, params, BASE, replicas=2, slo=slo)
    outs = router.serve(prompts, max_new_tokens=4)
    assert all(o is None for o in outs)
    assert router.shed_count == 4
    assert router.stats()["dispatches"] == [0, 0]


def test_router_generous_budget_sheds_nothing():
    cfg, _, params = make_model()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (5,)) for _ in range(4)]
    slo = ServingSLOConfig(ttft_ms=60_000.0, tpot_ms=60_000.0, admission="shed")
    router = ServingRouter.build(cfg, params, BASE, replicas=2, slo=slo)
    outs = router.serve(prompts, max_new_tokens=4)
    assert router.shed_count == 0
    assert all(o is not None and len(o) == 4 for o in outs)
    met, missed = router.goodput()
    assert (met, missed) == (0, 0)  # tracker off without telemetry


# -------------------------------------------------- preemption + affinity
def test_preemption_readmits_replica_affine():
    """Pools sized to force preemption mid-generation: the victim re-enters
    through its original replica (prefix-cache blocks live there), the
    affinity counter sees it, and outputs still match the dense path."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (8,)) for _ in range(4)]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=8)
    router = ServingRouter.build(
        cfg, params, dict(BASE, num_kv_blocks=6, max_seqs=4,
                          prefix_cache=True),
        replicas=2)
    outs = router.serve(prompts, max_new_tokens=8)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    assert router.preemptions >= 1
    assert router.affine_readmits >= 1
    # (no cache-hit assertion here: under exactly the pressure that causes
    # preemption, _can_schedule_evicting drains the cache FIRST by design —
    # live traffic always outranks cached prefixes)
    # everything released (modulo live cache references)
    for rep in router.replicas:
        held = len(rep.engine.prefix_cache)
        assert rep.engine.state.free_blocks == rep.engine.num_kv_blocks - held


# ------------------------------------------------------------------ telemetry
def test_router_metrics_and_replica_tracks():
    cfg, _, params = make_model()
    tr = get_tracer()
    tr.configure(enabled=True)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (7, 3, 5, 6)]
    slo = ServingSLOConfig(ttft_ms=60_000.0, tpot_ms=60_000.0, admission="shed")
    router = ServingRouter.build(cfg, params, BASE, replicas=2, slo=slo)
    outs = router.serve(prompts, max_new_tokens=6)
    assert all(len(o) == 6 for o in outs)

    reg = tr.registry
    counters = reg.counters()
    assert counters["router/requests"] == 4
    assert counters.get("router/shed_requests", 0) == 0
    disp = [v for k, v in counters.items() if k.startswith("router/dispatches")]
    assert len(disp) == 2 and sum(disp) >= 4  # >= 1 prefill + 1 chain each
    gauges = reg.gauges()
    for i in (0, 1):
        assert f'router/replica_queue_depth{{replica="{i}"}}' in gauges
        assert f'router/replica_active{{replica="{i}"}}' in gauges
    # per-replica serving SLO metrics: every request finished under the
    # generous targets, counted on its replica's labelled family
    met = sum(v for k, v in counters.items() if k.startswith("serving/slo_met"))
    assert met == 4
    met2, missed2 = router.goodput()
    assert (met2, missed2) == (4, 0)

    # per-replica Perfetto tracks with one slice per dispatched program
    doc = chrome_trace_events(tr)
    evs = doc["traceEvents"]
    track_names = {e["tid"]: e["args"]["name"] for e in evs
                   if e.get("ph") == "M" and e["name"] == "thread_name"}
    for rep in router.replicas:
        tid = REPLICA_TRACK_BASE + rep.index
        assert track_names.get(tid) == f"replica {rep.index}"
        slices = [e for e in evs if e.get("cat") == "router"
                  and e.get("tid") == tid]
        assert len(slices) == rep.dispatches
        assert {e["name"] for e in slices} <= {"prefill", "chain"}


def test_defer_migrates_to_budget_capable_replica():
    """admission='defer' must MOVE the request to the replica that can still
    make the budget (a not-yet-prefilled request has no KV to lose), not
    hold it on an over-budget replica until the clock sheds it."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(6)
    slo = ServingSLOConfig(ttft_ms=500.0, admission="defer")
    router = ServingRouter.build(cfg, params, BASE, replicas=2, slo=slo)
    router.replicas[0].prefill_ema = 10.0  # replica 0 projects way over
    router.replicas[1].prefill_ema = 0.001
    outs = router.serve([rng.randint(0, cfg.vocab_size, (5,))], max_new_tokens=4)
    assert outs[0] is not None and len(outs[0]) == 4
    assert router.deferred_count >= 1
    assert router.shed_count == 0
    d = router.stats()["dispatches"]
    assert d[0] == 0 and d[1] >= 2  # served entirely by the viable replica


def test_router_validates_infeasible_prompts_upfront():
    """A prompt no replica can ever serve raises immediately (the engine's
    generate() guards, applied at serve()) instead of stalling the loop."""
    cfg, _, params = make_model()
    router = ServingRouter.build(
        cfg, params, dict(BASE, num_kv_blocks=2), replicas=2)
    with pytest.raises(ValueError, match="KV pool"):
        router.serve([np.arange(12) % cfg.vocab_size], max_new_tokens=8)
    router2 = ServingRouter.build(cfg, params, dict(BASE, max_seq_len=16),
                                  replicas=2)
    with pytest.raises(ValueError, match="max_seq_len"):
        router2.serve([np.arange(12) % cfg.vocab_size], max_new_tokens=8)


def test_router_rejects_spec_with_sampling():
    cfg, _, params = make_model()
    router = ServingRouter.build(cfg, params, dict(BASE, spec_decode=2),
                                 replicas=2)
    with pytest.raises(ValueError, match="greedy-only"):
        router.serve([np.arange(5) % cfg.vocab_size], max_new_tokens=4,
                     do_sample=True)


def test_preempted_request_bypasses_admission_gate():
    """The SLO gate applies to FIRST admissions only: once a request has
    dispatched a prefill (and may hold generated tokens), a later
    re-admission after preemption must NOT shed it — even if the gate would
    now reject it. Pinned by a gate stub that sheds everything after the
    first wave: the preempted requests still finish, tokens intact."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, (8,)) for _ in range(4)]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=8)
    slo = ServingSLOConfig(ttft_ms=60_000.0, admission="shed")
    router = ServingRouter.build(
        cfg, params, dict(BASE, num_kv_blocks=6, max_seqs=4), replicas=2,
        slo=slo)
    calls = {"n": 0}

    def hostile_gate(waited, rep):
        calls["n"] += 1
        return "admit" if calls["n"] <= 4 else "shed"

    router._admission_decision = hostile_gate
    outs = router.serve(prompts, max_new_tokens=8)
    assert router.preemptions >= 1  # pressure really preempted
    assert router.shed_count == 0  # ...and nothing admitted was dropped
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)


def test_router_requires_engines():
    with pytest.raises(ValueError):
        ServingRouter([])
