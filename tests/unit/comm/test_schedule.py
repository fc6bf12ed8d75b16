"""Schedule compiler (ISSUE 19): synthesized hop programs vs jax.lax.

Acceptance pins:

- a compiled program round-trips facade -> compiler -> hop scope and is
  BIT-identical to the ``jax.lax`` baseline on exact wires (integer-valued
  payloads make every summation order exact), on a 1D ring, a (4,2)
  two-axis mesh, and a (2,2,2) mesh — including non-divisible payloads;
- the search is deterministic across cache invalidation;
- the cost model the compiler consumes IS the selector's refit-calibrated
  object (``selector.cost_model()``), and a recalibration visibly flips
  the pick: alpha-dominant -> compiled wins at world 30 (non-pow2, where
  the [2,3,5] factorization's 14 hops beat ring2d's 18 and bidir's 58),
  beta-dominant -> the SAME query flips to ``bidir``, alpha-huge with no
  forced codec -> the 0-hop ``lax`` floor;
- the decision cache keys on the mesh-axis factorization, not just world
  size;
- hierarchical constants (``set_tier_beta_scale``) surface the ZeRO++
  mixed placement (exact inner level, quantized outer) from search.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.collectives import algorithms, schedule, selector
from deepspeed_tpu.comm import benchmark
from deepspeed_tpu.utils.compat import shard_map


@pytest.fixture(autouse=True)
def _reset_selector():
    selector.configure()
    yield
    selector.configure()


def _mesh(shape, names):
    return Mesh(np.array(jax.devices()[:8]).reshape(shape), names)


def _run(mesh, f, x, out_specs):
    spec = P(mesh.axis_names if len(mesh.axis_names) > 1
             else mesh.axis_names[0])
    return jax.jit(shard_map(f, mesh=mesh, in_specs=spec,
                             out_specs=out_specs, check_vma=False))(x)


def _ints(rng, n):
    return jnp.asarray(rng.integers(-8, 8, size=(n,)).astype(np.float32))


# ------------------------------------------------------------ bit identity
@pytest.mark.parametrize("alg", [
    "compiled", "compiled:dp*2.none/dp*4.none",
    "compiled:dp*2.none/dp*2.none/dp*2.none"])
def test_compiled_all_reduce_1d_bit_identical(alg):
    mesh = _mesh((8,), ("dp",))
    x = _ints(np.random.default_rng(0), 8 * 96)
    got = _run(mesh, lambda v: algorithms.all_reduce(v, "dp", algorithm=alg),
               x, P("dp"))
    want = _run(mesh, lambda v: jax.lax.psum(v, "dp"), x, P("dp"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compiled_all_reduce_nondivisible_payload():
    # L=333 per shard is not divisible by the sub-ring sizes -> pad path
    mesh = _mesh((8,), ("dp",))
    x = _ints(np.random.default_rng(1), 8 * 333)
    for alg in ("compiled", "compiled:dp*4.none/dp*2.none"):
        got = _run(mesh, lambda v, a=alg: algorithms.all_reduce(
            v, "dp", algorithm=a), x, P("dp"))
        want = _run(mesh, lambda v: jax.lax.psum(v, "dp"), x, P("dp"))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compiled_two_axis_mesh_bit_identical():
    mesh = _mesh((4, 2), ("a", "b"))
    x = _ints(np.random.default_rng(2), 8 * 96)
    got = _run(mesh, lambda v: algorithms.all_reduce(
        v, ("a", "b"), algorithm="compiled"), x, P(("a", "b")))
    want = _run(mesh, lambda v: jax.lax.psum(v, ("a", "b")), x, P(("a", "b")))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # forced cross-axis program: minor axis first, then the 4-ring
    got = _run(mesh, lambda v: algorithms.all_gather(
        v, ("a", "b"), algorithm="compiled:b*2.none/a*4.none"), x, P())
    want = _run(mesh, lambda v: jax.lax.all_gather(
        v, ("a", "b"), tiled=True), x, P())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = _run(mesh, lambda v: algorithms.reduce_scatter(
        v, ("a", "b"), algorithm="compiled:b*2.none/a*4.none"),
        x, P(("a", "b")))
    want = _run(mesh, lambda v: jax.lax.psum_scatter(
        v, ("a", "b"), tiled=True), x, P(("a", "b")))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compiled_three_axis_mesh_bit_identical():
    mesh = _mesh((2, 2, 2), ("a", "b", "c"))
    axes = ("a", "b", "c")
    x = _ints(np.random.default_rng(3), 8 * 96)
    for op, lax_f, outs in (
            (algorithms.all_reduce,
             lambda v: jax.lax.psum(v, axes), P(axes)),
            (algorithms.reduce_scatter,
             lambda v: jax.lax.psum_scatter(v, axes, tiled=True), P(axes))):
        got = _run(mesh, lambda v, f=op: f(v, axes, algorithm="compiled"),
                   x, outs)
        want = _run(mesh, lax_f, x, outs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = _run(mesh, lambda v: algorithms.all_gather(
        v, axes, algorithm="compiled:c*2.none/b*2.none/a*2.none"), x, P())
    want = _run(mesh, lambda v: jax.lax.all_gather(v, axes, tiled=True),
                x, P())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compiled_mixed_codec_placement_bounded():
    # ZeRO++ shape by hand: exact 2-ring on b, int8 4-ring on a
    mesh = _mesh((4, 2), ("a", "b"))
    x = _ints(np.random.default_rng(4), 8 * 96)
    got = _run(mesh, lambda v: algorithms.all_reduce(
        v, ("a", "b"), algorithm="compiled:b*2.none/a*4.int8",
        block_size=32), x, P(("a", "b")))
    want = _run(mesh, lambda v: jax.lax.psum(v, ("a", "b")), x, P(("a", "b")))
    rel = (np.abs(np.asarray(got) - np.asarray(want)).max()
           / (np.abs(np.asarray(want)).max() + 1e-9))
    assert rel < 0.1, rel


# ------------------------------------------------------- search + selector
def test_search_deterministic_across_cache_invalidation():
    s1 = schedule.compile_schedule("all_reduce", (("dp", 8),), 1 << 20, "int8")
    s2 = schedule.compile_schedule("all_reduce", (("dp", 8),), 1 << 20, "int8")
    schedule.invalidate_cache()
    s3 = schedule.compile_schedule("all_reduce", (("dp", 8),), 1 << 20, "int8")
    assert s1.signature == s2.signature == s3.signature
    assert s1.est_us == s3.est_us
    # round-trip through the signature grammar
    levels = schedule.parse_signature(s1.signature)
    assert schedule.format_signature(levels) == s1.signature


def test_cost_model_is_selectors_calibrated_object_and_refit_flips():
    op, nbytes, world = "all_reduce", 1 << 20, 30
    axes_sig = (("dp", world),)
    selector.configure(compiled_search=True, codecs=("int8",))
    cm = selector.cost_model()
    # alpha-dominant: hop count decides -> compiled [2,3,5] wins at the
    # non-pow2 world (rhd out; 14 hops vs ring2d 18 / bidir 58)
    selector.calibrate("ppermute", 10.0, 0.1)
    d = selector.select(op, nbytes, world, codec="int8", axes_sig=axes_sig)
    assert d.algorithm.startswith("compiled:"), d
    # the compiler consumed THE selector model, not a frozen copy
    assert cm is selector.cost_model()
    sched = schedule.compile_schedule(op, axes_sig, nbytes, "int8", cm=cm)
    assert f"compiled:{sched.signature}" == d.algorithm
    # beta-dominant refit of the SAME model: bidir's half per-link wire
    # beats single-direction sub-rings -> the SAME query flips
    selector.calibrate("ppermute", 0.01, 100.0)
    d2 = selector.select(op, nbytes, world, codec="int8", axes_sig=axes_sig)
    assert d2.algorithm == "bidir", d2
    # alpha huge + no forced codec: the 0-hop lax floor wins
    selector.calibrate("ppermute", 1e6, 1e-6)
    d3 = selector.select(op, nbytes, 8, axes_sig=(("dp", 8),))
    assert d3.algorithm == "lax", d3


def test_decision_cache_keys_on_axis_factorization():
    # same (op, bytes, world, codec) but different mesh factorizations
    # must NOT collapse to one cached decision
    selector.configure(compiled_search=True, codecs=("int8",))
    selector.calibrate("ppermute", 10.0, 0.1)
    d_flat = selector.select("all_reduce", 1 << 20, 30, codec="int8",
                             axes_sig=(("dp", 30),))
    d_mesh = selector.select("all_reduce", 1 << 20, 30, codec="int8",
                             axes_sig=(("ep", 5), ("dp", 6)))
    assert d_flat.algorithm.startswith("compiled:")
    assert d_mesh.algorithm.startswith("compiled:")
    assert d_flat.algorithm != d_mesh.algorithm
    assert "ep*" in d_mesh.algorithm and "ep*" not in d_flat.algorithm


def test_tier_beta_scale_surfaces_mixed_placement():
    # free inner tier (NVLink-like): exact wire on the first level, int8
    # outside — the ZeRO++ shape from search, not hard-coding
    selector.configure(compiled_search=True, codecs=("int8",))
    selector.cost_model().set_tier_beta_scale((0.0, 1.0))
    d = selector.select("all_reduce", 1 << 20, 8, axes_sig=(("dp", 8),))
    assert d.algorithm.startswith("compiled:"), d
    levels = schedule.parse_signature(d.algorithm.split(":", 1)[1])
    assert levels[0].codec == "none"
    assert levels[-1].codec == "int8"


def test_candidate_signatures_feed_sweep_rows():
    sigs = schedule.candidate_signatures("all_reduce", "dp", 8,
                                         codecs=("none", "int8"))
    assert 0 < len(sigs) <= 3
    for sig in sigs:
        levels = schedule.parse_signature(sig)
        assert np.prod([lv.size for lv in levels]) == 8
    # the sweep enumerates compiled rows next to the hand algorithms
    pairs = benchmark.candidate_pairs(8, ("none", "int8"),
                                      op="all_reduce", axis="dp")
    compiled = [a for a, _ in pairs if a.startswith("compiled:")]
    assert compiled, pairs
