"""A chain ahead (ISSUE 36): ``generate`` lets ``decode_chain`` dispatch chain
N+1 from chain N's carry on the device before it fetches chain N's tokens.

What is held here, on the CPU: the tokens are a serial driver's (``put`` and
``decode_chain`` one chain at a time, as the router drives the engine), the
spans and counters say which boundaries went ahead, one compiled program
serves both ways of starting a chain, the staging of a chain in flight is its
own, and a call that is not the chain dispatched ahead is refused. The routed
toy's picks and the EVA toy's windows are beside their models
(``test_latent_routed.py``, ``test_eva.py``) and use ``serial_driver`` too.
"""

import jax
import numpy as np
import pytest

from deepspeed_tpu.telemetry import get_tracer

from .test_inference_v2 import make_model
from .test_serving_fastpath import _engine

K = 4


def serial_driver(eng, prompts, n_new, eos=None, do_sample=False, seed=0, ahead=False, flush=True):
    """``prompts`` admitted at once and decoded to the end, chain after chain,
    by the engine's public pieces; ``n_new`` a number or one a prompt. With
    ``ahead`` each call keeps ``decode_chain``'s promise about the next (the
    rows left, in order, their budgets less k, the rng returned), which is all
    the engine needs to dispatch it early. Returns the tokens a prompt."""
    k = eng.config.decode_chain
    n_new = [n_new] * len(prompts) if isinstance(n_new, int) else list(n_new)
    sample_kw = (("do_sample", do_sample), ("temperature", 1.0), ("top_k", 0), ("top_p", 1.0))
    rng = jax.device_put(jax.random.PRNGKey(seed), eng._replicated)
    rids = list(range(len(prompts)))
    uids = [100 + i for i in rids]
    first, rng = eng._put_sample(uids, [np.asarray(p, np.int32) for p in prompts], rng, sample_kw, rids=rids)
    gen = {u: [int(t)] for u, t in zip(uids, first)}
    want = dict(zip(uids, n_new))

    def done(u):
        return len(gen[u]) >= want[u] or (eos is not None and gen[u][-1] == eos)

    live = list(uids)
    while True:
        for u in live:
            if done(u) and flush:
                eng.flush(u)
        live = [u for u in live if not done(u)]
        if not live and eng._ahead is None:
            break
        budgets = [want[u] - len(gen[u]) for u in live]
        if eng._ahead is None:
            assert eng.can_schedule(live, eng.chain_window(budgets, k))
        out, emitted, rng = eng.decode_chain(
            live, [gen[u][-1] for u in live], budgets, k, rng, eos_id=eos, sample_kw=sample_kw,
            rids=[u - 100 for u in live], **({"ahead": True} if ahead else {}))
        for i, u in enumerate(live):
            gen[u] += out[i, :emitted[i]].tolist()
    return [np.asarray(gen[u], np.int32) for u in uids]


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (n,)) for n in lens]


def _first_met(outs, lo, hi, alone=True):
    """(row, token): a token some row emits first at an index in [lo, hi), and
    (``alone``) no row before ``lo``: an EOS that ends that row there."""
    for r, out in enumerate(outs):
        for j in range(lo, hi):
            tok = int(out[j])
            if tok not in out[:j] and not (alone and any(tok in o[:lo] for o in outs)):
                return r, tok
    raise AssertionError("no such token: take other prompts")


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.fixture(scope="module")
def model():
    cfg, _, params = make_model()
    return cfg, params


# ------------------------------------------- (a) generate is the serial driver
@pytest.mark.parametrize("n_new", [1 + 3 * K, 3 * K - 1], ids=["whole_chains", "tail_of_3_steps"])
def test_greedy_wave_is_the_serial_drivers(model, n_new):
    """A closed wave: every boundary but the first goes ahead, also where
    ``max_new_tokens`` is no multiple of k (the last chain's budget is short
    and the carry ends the rows inside it)."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 3, 5))
    eng = _engine(cfg, params, K)
    _same(eng.generate(prompts, max_new_tokens=n_new), serial_driver(_engine(cfg, params, K), prompts, n_new))
    chains = -(-(n_new - 1) // K)
    assert (eng.chain_steps, eng.chains_ahead) == (chains, chains - 1)
    assert eng.host_sync_count == eng.dispatch_count == 1 + chains  # (e) a fetch a program, still
    assert eng.state.free_blocks == 64 and eng._ahead is None


def test_sampled_wave_at_a_fixed_seed_is_the_serial_drivers(model):
    """Sampling draws a row's token from its row of the chain's one key: the
    rows of a wave stay where they are, and so do the draws."""
    cfg, params = model
    prompts = _prompts(cfg, (6, 6, 4, 9), seed=1)
    eng = _engine(cfg, params, K)
    got = eng.generate(prompts, max_new_tokens=1 + 3 * K, do_sample=True, seed=11)
    _same(got, serial_driver(_engine(cfg, params, K), prompts, 1 + 3 * K, do_sample=True, seed=11))
    assert eng.chains_ahead == 2
    other = _engine(cfg, params, K).generate(prompts, max_new_tokens=1 + 3 * K, do_sample=True, seed=12)
    assert any(not np.array_equal(a, b) for a, b in zip(got, other))  # the seed matters


def test_eos_in_a_chain_whose_successor_is_in_flight(model):
    """One row meets its EOS in the middle of chain 2 while chain 3 is already
    dispatched: the carry knows, the row rides chain 3 dead, and the host
    hears of it at the fetch."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 3, 5))
    n_new = 1 + 4 * K
    free = _engine(cfg, params, K).generate(prompts, max_new_tokens=n_new)
    row, eos = _first_met(free, 1 + K + 1, 1 + 2 * K - 1)  # inside chain 2, nobody before it
    eng = _engine(cfg, params, K)
    got = eng.generate(prompts, max_new_tokens=n_new, eos_token_id=eos)
    _same(got, serial_driver(_engine(cfg, params, K), prompts, n_new, eos=eos))
    assert got[row][-1] == eos and 1 + K + 1 < len(got[row]) < 1 + 2 * K
    assert max(len(o) for o in got) == n_new  # and somebody went on to the end
    assert eng.chains_ahead == eng.chain_steps - 1 and eng.host_sync_count == eng.dispatch_count
    assert eng.state.free_blocks == 64


def test_every_row_ends_at_an_eos_with_a_chain_in_flight(model):
    """The one request ends in chain 1 while chain 2 is in flight: the loop
    fetches a chain that did nothing, and leaves nothing behind."""
    cfg, params = model
    prompt = _prompts(cfg, (6,), seed=2)
    free = _engine(cfg, params, K).generate(prompt, max_new_tokens=8)[0]
    _, eos = _first_met([free], 1, K)  # inside chain 1, not its last step
    eng = _engine(cfg, params, K)
    got = eng.generate(prompt, max_new_tokens=1 + 3 * K, eos_token_id=eos)
    _same(got, serial_driver(_engine(cfg, params, K), prompt, 1 + 3 * K, eos=eos))
    assert got[0][-1] == eos and len(got[0]) <= K
    assert (eng.chain_steps, eng.chains_ahead) == (2, 1) and eng.host_sync_count == eng.dispatch_count
    assert eng._ahead is None and eng.state.free_blocks == 64 and eng.state.n_active == 0


def test_rows_of_unequal_budgets_ride_dead_once_spent(model):
    """``decode_chain(ahead=True)`` over rows whose budgets end in different
    chains: a row whose budget is spent keeps its row of the program, dead,
    and the rows behind it keep theirs."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 3, 5, 4), seed=3)
    budgets = [1 + 3 * K, 3, 1 + 2 * K - 2, 1 + 3 * K + 1]
    eng = _engine(cfg, params, K)
    got = serial_driver(eng, prompts, budgets, ahead=True)
    _same(got, serial_driver(_engine(cfg, params, K), prompts, budgets))
    assert [len(o) for o in got] == budgets
    # 4 chains (the longest row needs 13 tokens after its first), each but the first ahead
    assert eng.dispatch_count == 1 + 4 and eng.chains_ahead == 3 and eng.host_sync_count == eng.dispatch_count
    assert eng.state.free_blocks == 64


def test_a_pool_too_tight_for_the_next_chain_goes_serial_then_preempts(model):
    """6 pages of 4 for two requests that grow to 16 tokens each: the pool
    does not cover the next window as it stands, the boundary is serial, and
    the loop shrinks and preempts there as it always did."""
    cfg, params = model
    prompts = _prompts(cfg, (8, 8), seed=3)
    eng = _engine(cfg, params, K, num_kv_blocks=6, max_seqs=4)
    got = eng.generate(prompts, max_new_tokens=8)
    roomy = _engine(cfg, params, K)
    _same(got, [serial_driver(roomy, [p], 8)[0] for p in prompts])
    assert eng.chains_ahead < eng.chain_steps - 1  # some boundary after the first was serial
    assert eng.state.free_blocks == 6 and eng.host_sync_count == eng.dispatch_count


def test_more_prompts_than_seats_with_an_eos_among_them(model):
    """Two seats, four prompts: a boundary at which a budget ends is serial
    (a seat comes free there), one at which none does goes ahead though
    prompts wait, and the seat an EOS frees meanwhile is filled one chain
    later. Greedy tokens are each request's own, whenever it was admitted."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 3, 5, 6), seed=4)
    n_new = 1 + 3 * K
    free = _engine(cfg, params, K).generate(prompts, max_new_tokens=n_new)
    _, eos = _first_met(free[:1], 1 + K, 1 + 2 * K, alone=False)  # request 0 ends in its second chain
    eng = _engine(cfg, params, K, max_seqs=2)
    got = eng.generate(prompts, max_new_tokens=n_new, eos_token_id=eos)
    roomy = _engine(cfg, params, K)
    _same(got, [serial_driver(roomy, [p], n_new, eos=eos)[0] for p in prompts])
    assert got[0][-1] == eos and len(got[0]) < n_new
    assert 0 < eng.chains_ahead < eng.chain_steps - 1
    assert eng.host_sync_count == eng.dispatch_count and eng.state.free_blocks == 64


# ------------------------------------------------- (b) the spans and the counter
def test_the_next_dispatch_lies_before_the_fetch(model):
    cfg, params = model
    eng = _engine(cfg, params, K)
    prompts = _prompts(cfg, (7, 3, 5))
    eng.generate(prompts, max_new_tokens=1 + K)  # compile outside the recorded run
    tr = get_tracer()
    tr.configure(enabled=True)
    tr.reset()
    try:
        first = eng.chain_steps
        eng.generate(prompts, max_new_tokens=1 + 4 * K)
        spans = [e for e in tr.events() if e["kind"] == "span" and e.get("args", {}).get("kind") == "chain"]
        counters = {k: v for k, v in tr.registry.snapshot().items() if k.startswith("serving/chains")}
    finally:
        tr.configure(enabled=False)
        tr.reset()
    at = {(e["name"], e["args"]["chain"]): e for e in spans}
    chains = list(range(first, first + 4))
    assert sorted(c for name, c in at if name == "serve:dispatch") == chains
    for c in chains:  # one id a chain, from its assemble to its accept
        assert {name for name, cc in at if cc == c} == {"serve:assemble", "serve:dispatch", "serve:fetch", "serve:accept"}
    assert [at["serve:dispatch", c]["args"]["ahead"] for c in chains] == [0, 1, 1, 1]
    assert [at["serve:dispatch", c]["args"]["live"] for c in chains] == [3] * 4
    for c in chains[:-1]:
        nxt, fetch = at["serve:dispatch", c + 1], at["serve:fetch", c]
        assert at["serve:assemble", c + 1]["ts"] < nxt["ts"] < nxt["ts"] + nxt["dur"] <= fetch["ts"]
        assert fetch["ts"] + fetch["dur"] <= at["serve:accept", c]["ts"]
    (ahead,) = [v for k, v in counters.items() if k.startswith("serving/chains_ahead")]
    (total,) = [v for k, v in counters.items() if k.split("{")[0] == "serving/chains"]
    assert (total, ahead) == (4, 3)  # chains - 1 a closed wave


# ------------------------------------- (c) one program, both ways of starting it
def test_a_chain_ahead_runs_the_warmed_program(model):
    """What the benchmark's warm-up does, then a wave: a generation of 1 + k
    tokens (one chain, started from the host's values), then one of 1 + 3k
    (two chains started from a carry). No jit cache entry and no compilation
    is added: shapes, dtypes AND placement of the operands are the same."""
    import jax.monitoring

    cfg, params = model
    eng = _engine(cfg, params, K)
    prompts = _prompts(cfg, (7, 3, 5))
    eng.generate(prompts, max_new_tokens=1 + K)
    (key,) = [k for k in eng._step_cache if k[0] == "chain"]
    programs, traces = eng.jit_cache_size(), eng._step_cache[key]._cache_size()
    compiled = []

    def listener(name, *_, **__):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        eng.generate(_prompts(cfg, (7, 3, 5), seed=9), max_new_tokens=1 + 3 * K)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert eng.chains_ahead == 2
    assert eng.jit_cache_size("chain") == 1 and eng.jit_cache_size() == programs
    assert eng._step_cache[key]._cache_size() == traces == 1
    assert compiled == []  # nor a slice of an output: the fetch takes them whole


# ------------------------------------------------------------------ (d) staging
def test_garbage_in_the_staging_after_a_dispatch_changes_no_token(model):
    """The staging arrays are refilled for chain N+1 while chain N is in
    flight, and the CPU backend may place a host array without copying it:
    what is placed is a copy."""
    cfg, params = model
    prompts = _prompts(cfg, (7, 3, 5))
    want = _engine(cfg, params, K).generate(prompts, max_new_tokens=1 + 3 * K)
    eng = _engine(cfg, params, K)
    dispatch = eng._dispatch_chain

    def then_garbage(*args, **kwargs):
        flight = dispatch(*args, **kwargs)
        for buf in eng._chain_buf.values():
            for a in buf.values():
                a[...] = np.array(0x5A5A5A5A).astype(a.dtype) if a.dtype != bool else True
        return flight

    eng._dispatch_chain = then_garbage
    _same(eng.generate(prompts, max_new_tokens=1 + 3 * K), want)
    assert eng.chains_ahead == 2


# -------------------------------------------------- the call that is not the chain
def test_a_call_that_is_not_the_chain_dispatched_ahead_is_refused(model):
    cfg, params = model
    eng = _engine(cfg, params, K)
    prompts = _prompts(cfg, (7, 3))
    rng = jax.device_put(jax.random.PRNGKey(0), eng._replicated)
    logits = eng.put([1, 2], prompts)
    last = logits.argmax(-1)
    out, emitted, rng2 = eng.decode_chain([1, 2], last, [9, 9], K, rng, ahead=True)
    assert eng._ahead is not None and list(emitted) == [K, K]
    with pytest.raises(RuntimeError, match="not that chain: budgets"):
        eng.decode_chain([1, 2], out[:, -1], [9 - K, 3], K, rng2)
    eng._ahead = None
    eng.flush(1), eng.flush(2)

    # and without the argument nothing is dispatched ahead: the router's way
    eng.put([3], prompts[:1])
    eng.decode_chain([3], last[:1], [9], K, rng)
    assert eng._ahead is None and eng.chains_ahead == 1
