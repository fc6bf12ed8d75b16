"""The generator: same seed, same inputs; every seed, the same work."""

import numpy as np
import pytest

from benchmarks.lib import harness, traffic

# the chat file waits for its cell and carries no rate yet (PERF.md, section 7)
CHAT = dict(harness.load_workload("pythia-1.4b.serve.chat")["traffic"], rate_per_s=4.0)
BATCH = harness.load_workload("pythia-1.4b.serve.batch")["traffic"]
BIG_SEED = 2**31 + 12345  # more than 32 signed bits hold


def test_open_loop_repeats_from_the_seed():
    a = traffic.open_loop(CHAT, 50304, BIG_SEED, 30.0)
    b = traffic.open_loop(CHAT, 50304, BIG_SEED, 30.0)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert np.array_equal(a.arrival_s, b.arrival_s)


def test_every_seed_gets_the_same_lengths_and_gaps_in_another_order():
    a = traffic.open_loop(CHAT, 50304, 1, 30.0)
    b = traffic.open_loop(CHAT, 50304, BIG_SEED, 30.0)
    la, lb = [len(p) for p in a.prompts], [len(p) for p in b.prompts]
    assert sorted(la) == sorted(lb) and la != lb
    ga, gb = np.diff(a.arrival_s), np.diff(b.arrival_s)
    assert not np.array_equal(ga, gb)
    # one set of gaps; each order leaves its own first gap out of the differences
    assert len(set(np.round(ga, 9)) ^ set(np.round(gb, 9))) <= 2
    assert not np.array_equal(a.prompts[0][:8], b.prompts[0][:8])


def test_open_loop_matches_its_stated_distributions():
    r = traffic.open_loop(dict(CHAT, rate_per_s=50.0), 50304, 7, 40.0)
    lens = np.array([len(p) for p in r.prompts])
    assert len(lens) == 2000 and r.output_tokens == 128
    assert lens.min() >= 32 and lens.max() <= 1536
    assert 350 <= np.median(lens) <= 420  # lognormal, median 384
    assert 0.02 <= (lens == 1536).mean() <= 0.07  # the clipped tail: P(z > 1.73) = 0.042
    assert r.arrival_s[0] == 0.0 and r.arrival_s[-1] == pytest.approx(40.0, rel=0.02)
    gaps = np.diff(r.arrival_s)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)  # Poisson: CV 1
    assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 50304 for p in r.prompts[:20])


def test_closed_waves_hold_one_set_of_lengths():
    waves = traffic.closed_waves(BATCH, 50304, BIG_SEED)
    first, second = next(waves), next(waves)
    l1, l2 = [len(p) for p in first.prompts], [len(p) for p in second.prompts]
    assert len(l1) == 64 and sorted(l1) == sorted(l2) and l1 != l2
    assert min(l1) >= 64 and max(l1) <= 256 and first.arrival_s is None
    assert first.output_tokens == 192
    again = next(traffic.closed_waves(BATCH, 50304, BIG_SEED))
    assert all(np.array_equal(x, y) for x, y in zip(first.prompts, again.prompts))


def test_token_batches():
    tr = harness.load_workload("pythia-410m.train.seq2048")["traffic"]
    a, b = traffic.token_batches(tr, 50304, BIG_SEED), traffic.token_batches(tr, 50304, BIG_SEED)
    x, y = next(a), next(b)
    assert x.shape == (16, 2048) and x.dtype == np.int32 and np.array_equal(x, y)
    assert not np.array_equal(x, next(a))


def test_unknown_distributions_are_refused():
    with pytest.raises(ValueError):
        traffic.length_set({"dist": "zipf"}, 4)
