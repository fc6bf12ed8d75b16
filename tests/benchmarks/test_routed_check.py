"""``runners/serve.py::check`` for an architecture that says it is ROUTED:
``correct`` is decided at the program's own expert picks, and the picks are
audited against the reference's own scores. Proved on the CPU against the
stand-in engine under ``data/routed_architecture/`` (hidden 256, one dense and
four routed layers of 32 experts at top-4, sigmoid router with a correction
bias, renormalised and scaled weights, a shared expert, bf16, its own KV
cache), which reports its picks honestly unless a fault is planted. Nothing
here is a device number."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from tests.benchmarks.conftest import ROUTED, ROUTED_TOY, routed_rules, routed_stand_in

SEEDS = [2**31 + 5 + 1000 * i for i in range(16)]
LOGIT_TOL = ROUTED_TOY["check"]["logit_rel_tol"]
SHORTFALL_TOL = ROUTED_TOY["check"]["route_shortfall_tol"]


class Toy:
    """The files of the routed toy as the harness would load them, and every
    reading of ``check`` made so far: a seed's honest runs are made once."""

    def __init__(self):
        self.reference = harness._load_module(os.path.join(ROUTED, "reference.py"), "routed_reference")
        self.architecture = harness._load_module(os.path.join(ROUTED, "architecture.py"),
                                                 "routed_architecture")
        self.serve = harness.load_runner("serve")
        self.read = {}

    def without(self, *members):
        """The architecture file as it would be without some of its members."""
        kept = {k: v for k, v in vars(self.architecture).items()
                if not k.startswith("_") and k not in members}
        return types.SimpleNamespace(**kept)

    def check(self, seed, faults=(), architecture=None, config=ROUTED_TOY):
        engine = routed_stand_in(seed, faults, config)
        return self.serve.check(engine, self.reference, architecture or self.architecture, config, seed)

    def honest(self, seed):
        """``check`` of the honest bf16 engine: at its own picks, and the plain
        way, as an architecture file that says nothing of routing would get it."""
        if seed not in self.read:
            loose = dict(ROUTED_TOY, check=dict(ROUTED_TOY["check"], logit_rel_tol=0.09))
            self.read[seed] = (self.check(seed),
                               self.check(seed, architecture=self.without("routed_layers"), config=loose))
        return self.read[seed]


@pytest.fixture(scope="module")
def toy():
    return Toy()


@pytest.mark.parametrize("seed", SEEDS)
def test_honest_bf16_is_correct_at_its_own_picks(toy, seed):
    (ok, compared), _ = toy.honest(seed)
    assert ok and set(compared) == {"logit_rel_err", "token_gap", "route_shortfall"}
    assert all(found <= limit for found, limit in compared.values())
    assert compared["logit_rel_err"][1] == LOGIT_TOL and compared["route_shortfall"][1] == SHORTFALL_TOL
    # flips happen (the audit reads above zero) and stay within what bf16 can do to a score
    assert 0 < compared["route_shortfall"][0] < 0.05


def test_the_pinned_readings_keep_to_one_band_and_the_plain_ones_do_not(toy):
    """The finding that the pinning rests on: over the same 16 runs the error
    at the program's own picks is bf16's (0.0108-0.0123 here), the plain one
    is whatever the flips made it (0.026-0.213). Whoever "simplifies" the
    pinning away meets the second assertion."""
    pinned = [toy.honest(s)[0][1]["logit_rel_err"][0] for s in SEEDS]
    plain = [toy.honest(s)[1][1]["logit_rel_err"][0] for s in SEEDS]
    assert max(pinned) <= 1.3 * min(pinned) and max(pinned) <= LOGIT_TOL <= 1.25 * max(pinned)
    assert max(plain) > 3 * min(plain) and min(plain) > 2 * max(pinned)
    # no tolerance holds the plain number: most seeds read many times what bf16's error is
    assert sum(p > 5 * LOGIT_TOL for p in plain) >= len(SEEDS) // 2


# fault -> the number that has to say so; the others may pass
FAULTS = {
    "down_proj_x1.25": "logit_rel_err",       # (c) one expert's down-projection a quarter too large
    "weights_unnormalised": "logit_rel_err",  # (d) top-k weights left unnormalised
    "scaling_left_out": "logit_rel_err",      # (e) routed_scaling_factor left out
    "cache_e4m3": "logit_rel_err",            # (f) the lower-precision control: an fp8 cache
    "ranks_2_to_k_plus_1": "route_shortfall",  # (g) a router that skips its best expert
    "picks_misreported": "logit_rel_err",     # (h) the picks reported are not the picks used
    "bias_ignored": "route_shortfall",        # (i) the correction bias left out of the choice
}


# (c) as ISSUE 29 states it: one expert of 32 at 1.05 adds, in quadrature, about half of bf16's own
# error (0.0128-0.0141 against 0.0108-0.0123 sound), under a tolerance 1.2 x the sound runs' largest.
# What the check cannot see stays pinned here, and fails the suite on the day it can.
UNRESOLVED = {"down_proj_x1.05": "a fault half of bf16's own error reads inside logit_rel_tol "
                                 "(PERF.md, section 2)"}
FAULTS.update(dict.fromkeys(UNRESOLVED, "logit_rel_err"))


@pytest.mark.parametrize("fault", [
    pytest.param(f, marks=pytest.mark.xfail(strict=True, reason=UNRESOLVED[f])) if f in UNRESOLVED else f
    for f in FAULTS])
def test_a_planted_fault_is_not_correct_and_says_by_which_number(toy, fault, capsys):
    for seed in SEEDS[:3]:
        ok, compared = toy.check(seed, faults=(fault,))
        found, limit = compared[FAULTS[fault]]
        assert not ok and found > 1.3 * limit, (fault, seed, compared)
        assert "ok=False" in capsys.readouterr().out
    if FAULTS[fault] == "route_shortfall":
        # the router's own fault: the logits at its picks are still bf16's, only the audit can tell
        assert compared["logit_rel_err"][0] <= LOGIT_TOL and found > 1.0


def test_put_with_picks_runs_the_programs_put_has_compiled(toy):
    """What is checked is what is timed: the picks come out of ``put``'s own
    compiled programs, the prefill's and the decode step's."""
    engine = routed_stand_in(SEEDS[0])
    tokens = np.arange(20, dtype=np.int32)
    engine.put([1], [tokens])
    engine.put([1], [tokens[:1]])
    compiles = harness.CompileCounter()
    compiles.mark()
    for fed in (tokens[:17], tokens[:1]):
        logits, picks = toy.architecture.put_with_picks(engine, [2], [fed])
        assert logits.shape == (1, 512) and picks[0].shape == (len(fed), 4, 4)
    assert compiles.since_mark() == 0


def drop(config, key):
    return dict(config, check={k: v for k, v in config["check"].items() if k != key})


@pytest.mark.parametrize("why,missing,config,error,says", [
    ("no picks out of the put path", ("put_with_picks",), ROUTED_TOY, AttributeError,
     "lacks put_with_picks: a routed model is checked at the program's own expert picks. Write "
     "put_with_picks"),
    ("no tolerance for the audit", (), drop(ROUTED_TOY, "route_shortfall_tol"), KeyError,
     "states no check.route_shortfall_tol: measure it"),
    ("no picks out of the fused prefill and the chain", ("generate_with_picks",), ROUTED_TOY, AttributeError,
     "lacks generate_with_picks: a routed model is checked at the program's own expert picks"),
])
def test_a_routed_configuration_that_cannot_be_pinned_is_refused(toy, why, missing, config, error, says):
    """By the runner and by the contract's rule alike, each saying what to write."""
    architecture = toy.without(*missing)
    with pytest.raises(error, match=says):
        toy.check(SEEDS[0], architecture=architecture, config=config)
    with pytest.raises(error, match=says):
        routed_rules(config, architecture, toy.reference)


def test_a_reference_that_takes_no_picks_is_refused_by_the_contract(toy):
    plain = types.SimpleNamespace(forward=lambda weights, cfg, tokens: None)
    with pytest.raises(AssertionError, match="picks=None"):
        routed_rules(ROUTED_TOY, toy.architecture, plain)
    pins = types.SimpleNamespace(forward=toy.reference.forward)
    with pytest.raises(AssertionError, match="lacks route_shortfall"):
        routed_rules(ROUTED_TOY, toy.architecture, pins)
    routed_rules(ROUTED_TOY, toy.architecture, toy.reference)


@pytest.mark.parametrize("why,picks,says", [
    ("a layer short", np.zeros((5, 3, 4), np.int32), "wanted int32"),
    ("not integers", np.zeros((5, 4, 4), np.float32), "wanted int32"),
    ("an expert the layer does not have", np.full((5, 4, 4), 32) - np.arange(4), "distinct experts of 0..31"),
    ("one expert twice", np.zeros((5, 4, 4), np.int32), "distinct experts of 0..31"),
])
def test_picks_that_cannot_be_picks_are_refused(toy, why, picks, says):
    routing = program.routing(toy.architecture, ROUTED_TOY)
    assert (routing.layers, routing.experts, routing.k) == (4, 32, 4)
    with pytest.raises(ValueError, match=says):
        program.checked_picks(picks, 5, routing)
    good = np.broadcast_to(np.arange(4), (5, 4, 4))
    assert program.checked_picks(good, 5, routing).dtype == np.int32


def test_the_reference_pinned_to_its_own_top_k_is_the_published_forward(toy):
    """The contract's two ends: picks=None is the published router, and the
    shortfall is zero or less exactly where the picks are that router's."""
    cfg = program.published(ROUTED_TOY)
    engine = routed_stand_in(SEEDS[1])
    weights = toy.architecture.reference_weights(engine.params)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 512, (2, 24), dtype=np.int32))
    own = toy.reference.forward(weights, cfg, tokens)
    # the stand-in's picks: its own router's, wherever bf16 flipped none
    _, picks = toy.architecture.put_with_picks(engine, [7, 8], list(np.asarray(tokens)))
    picks = jnp.asarray(np.stack(picks))
    shortfall = np.asarray(toy.reference.route_shortfall(weights, cfg, tokens, picks))
    assert shortfall.shape == (2, 24, 4) and 0 < (shortfall > 0).mean() < 0.1
    settled = (shortfall <= 0).all(-1).cumprod(-1).astype(bool)  # no flip at or before the position
    pinned = np.asarray(toy.reference.forward(weights, cfg, tokens, picks))
    assert settled[:, 0].all() and not settled.all()
    np.testing.assert_allclose(pinned[settled], np.asarray(own)[settled], rtol=0, atol=1e-4)
    assert np.abs(pinned[~settled] - np.asarray(own)[~settled]).max() > 1e-2
    # a pick swapped for the expert ranked last: the audit reads by how far
    worst = np.asarray(picks).copy()
    worst[0, 5, 2, 0] = next(e for e in range(32) if e not in worst[0, 5, 2])
    moved = np.asarray(toy.reference.route_shortfall(weights, cfg, tokens, jnp.asarray(worst)))
    assert moved[0, 5, 2] > shortfall[0, 5, 2] and (moved[0, :5] == shortfall[0, :5]).all()


def test_the_architecture_file_counts_the_stand_ins_parameters(toy):
    engine = routed_stand_in(SEEDS[0])
    cfg = program.published(ROUTED_TOY)
    assert toy.architecture.total_params(cfg) == sum(a.size for a in jax.tree_util.tree_leaves(engine.params))
    idle = 4 * (32 - 4) * 3 * 256 * 64  # a token meets 4 of a routed layer's 32 experts
    rest = 512 * 256 + 5 * 2 * 256 + 256 + 4 * 32  # the embedding, the norms, the correction bias
    assert toy.architecture.matmul_params(cfg) == toy.architecture.total_params(cfg) - idle - rest
