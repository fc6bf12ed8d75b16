"""``ops/gdn.py``: the two forms of the gated delta rule against the plain
reference's sequential one (``benchmarks/reference/qwen3_next.py::delta_net``
is a whole mixer; here the recurrence alone is written out the same way, one
token after another, in NumPy float64), in float32 on the CPU; the mixer whole
(convolution, gates, norm) against that reference's ``delta_net``; and the
decode kernel (``ops/pallas/gdn_update.py``) in interpret mode against XLA's
form on a row of the pool.

Tolerance 1e-5 of the largest entry: the chunked form solves a chunk's
corrections at once where the recurrence applies them in turn (read 2e-7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import GDNConfig
from deepspeed_tpu.ops import gdn

HK, HV, DK, DV, CHUNK = 2, 4, 8, 6, 8
SIZES = GDNConfig(n_k_heads=HK, n_v_heads=HV, head_k_dim=DK, head_v_dim=DV, d_conv=4, chunk_size=CHUNK)


def _inputs(rows, T, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return dict(
        q=unit(jax.random.normal(keys[0], (rows, T, HK, DK))) * DK ** -0.5,
        k=unit(jax.random.normal(keys[1], (rows, T, HK, DK))),
        v=jax.random.normal(keys[2], (rows, T, HV, DV)),
        g=-jax.random.uniform(keys[3], (rows, T, HV), minval=0.0, maxval=0.7),
        beta=jax.nn.sigmoid(jax.random.normal(keys[4], (rows, T, HV))),
        state=jax.random.normal(keys[5], (rows, HV, DK, DV)))


def recurrence(q, k, v, g, beta, state, lens=None):
    """One row at a time, one token after another; tokens past ``lens`` are not fed."""
    outs, states = [], []
    for r in range(v.shape[0]):
        S = np.asarray(state[r], np.float64)
        o = np.zeros(v.shape[1:], np.float64)
        for t in range(v.shape[1] if lens is None else lens[r]):
            for h in range(HV):
                kh, qh = (np.asarray(a[r, t, h // (HV // HK)], np.float64) for a in (k, q))
                S[h] = np.exp(float(g[r, t, h])) * S[h]
                d = float(beta[r, t, h]) * (np.asarray(v[r, t, h], np.float64) - S[h].T @ kh)
                S[h] = S[h] + np.outer(kh, d)
                o[t, h] = S[h].T @ qh
        outs.append(o)
        states.append(S)
    return np.stack(outs), np.stack(states)


def close(got, want, tol=1e-5):
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("T", [5, 8, 29, 64])  # less than a chunk, one, three and a part, eight
def test_the_chunked_form_is_the_recurrence(T):
    a = _inputs(2, T)
    o, state = gdn.gdn_chunked(a["q"], a["k"], a["v"], a["g"], a["beta"], CHUNK, a["state"])
    want_o, want_state = recurrence(**a)
    close(o, want_o)
    close(state, want_state)


def test_the_chunked_form_starts_from_zeros_without_a_state():
    a = _inputs(2, 19, seed=1)
    o, state = gdn.gdn_chunked(a["q"], a["k"], a["v"], a["g"], a["beta"], CHUNK)
    want_o, want_state = recurrence(**dict(a, state=jnp.zeros_like(a["state"])))
    close(o, want_o)
    close(state, want_state)


def test_rows_go_a_group_at_a_time_to_the_same_numbers(monkeypatch):
    a = _inputs(4, 21, seed=2)
    whole = gdn.gdn_chunked(a["q"], a["k"], a["v"], a["g"], a["beta"], CHUNK, a["state"])
    monkeypatch.setattr(gdn, "_GROUP_ELEMENTS", 2 * 24 * CHUNK * HV)  # two rows a group
    grouped = gdn.gdn_chunked(a["q"], a["k"], a["v"], a["g"], a["beta"], CHUNK, a["state"])
    for got, want in zip(grouped, whole):
        close(got, np.asarray(want, np.float64), tol=1e-6)


def test_one_token_is_the_recurrence():
    a = _inputs(3, 1, seed=3)
    o, state = gdn.gdn_step(a["state"], *(a[n][:, 0] for n in ("q", "k", "v", "g", "beta")))
    want_o, want_state = recurrence(**a)
    close(o, want_o[:, 0])
    close(state, want_state)


def test_an_initial_state_continues_a_prompt_cut_in_two():
    a = _inputs(2, 27, seed=4)
    args = lambda lo, hi: tuple(a[n][:, lo:hi] for n in ("q", "k", "v", "g", "beta"))  # noqa: E731
    whole_o, whole_state = gdn.gdn_chunked(*args(0, 27), CHUNK, a["state"])
    first_o, mid = gdn.gdn_chunked(*args(0, 11), CHUNK, a["state"])
    second_o, last = gdn.gdn_chunked(*args(11, 27), CHUNK, mid)
    close(jnp.concatenate([first_o, second_o], axis=1), np.asarray(whole_o, np.float64))
    close(last, np.asarray(whole_state, np.float64))


def test_pad_tokens_and_dead_rows_leave_a_state_bitwise():
    """Right-padded rows: the state after a row's last live token comes out,
    whatever the padding holds; a dead row's state comes out as it went in."""
    a = _inputs(3, 20, seed=5)
    lens = np.asarray([13, 0, 20])
    live = jnp.arange(20)[None, :] < jnp.asarray(lens)[:, None]
    o, state = gdn.gdn_chunked(a["q"], a["k"], a["v"], a["g"], a["beta"], CHUNK, a["state"], live)
    want_o, want_state = recurrence(**a, lens=lens)
    close(o[0, :13], want_o[0, :13])
    close(state, want_state)
    assert np.array_equal(np.asarray(state[1]), np.asarray(a["state"][1]))  # bitwise
    # and one token: a dead row's slot is the slot it was
    step = tuple(a[n][:, 0] for n in ("q", "k", "v", "g", "beta"))
    _, after = gdn.gdn_step(a["state"], *step, live=jnp.asarray([True, False, True]))
    assert np.array_equal(np.asarray(after[1]), np.asarray(a["state"][1]))
    assert not np.array_equal(np.asarray(after[0]), np.asarray(a["state"][0]))


def _leaves(seed=6):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    hidden = 24
    return hidden, {
        "w_qkvz": jax.random.normal(keys[0], (hidden, SIZES.proj_dim)) * hidden ** -0.5,
        "w_ba": jax.random.normal(keys[1], (hidden, 2 * HV)) * hidden ** -0.5,
        "gdn_conv": jax.random.normal(keys[2], (SIZES.d_conv, SIZES.conv_dim)) * 0.5,
        "A_log": jnp.log(jax.random.uniform(keys[3], (HV,), minval=1.0, maxval=16.0)),
        "dt_bias": jax.random.normal(keys[4], (HV,)) - 3.0,
        "gdn_norm": {"scale": 1.0 + 0.1 * jax.random.normal(keys[5], (DV,))}}


def _reference_mixer(u, p, w_out):
    """``benchmarks/reference/qwen3_next.py::delta_net`` on one row."""
    from benchmarks.lib import harness

    reference = harness.load_reference("qwen3_next")
    cfg = {"linear_num_key_heads": HK, "linear_num_value_heads": HV, "linear_key_head_dim": DK,
           "linear_value_head_dim": DV, "linear_conv_kernel_dim": SIZES.d_conv, "rms_norm_eps": 1e-6}
    w = {"w_qkvz": p["w_qkvz"], "w_ba": p["w_ba"], "conv_w": p["gdn_conv"], "A_log": p["A_log"],
         "dt_bias": p["dt_bias"], "norm_w": p["gdn_norm"]["scale"], "w_out": w_out}
    with jax.default_matmul_precision("highest"):
        return reference.delta_net(u, w, cfg)


@pytest.mark.parametrize("T", [7, 21])
def test_the_mixer_whole_is_the_reference_s(T):
    """Convolution, gates, norms and the rule, prompt and then one token more
    from the state and the tail the prompt left."""
    hidden, p = _leaves()
    u = jax.random.normal(jax.random.PRNGKey(7), (2, T + 1, hidden))
    w_out = jnp.eye(HV * DV)
    y, state, tail = gdn.mix(u[:, :T] @ p["w_qkvz"], u[:, :T] @ p["w_ba"], p, SIZES, 1e-6)
    nxt, _, _ = gdn.mix(u[:, T:] @ p["w_qkvz"], u[:, T:] @ p["w_ba"], p, SIZES, 1e-6, state=state, tail=tail)
    for r in range(2):
        want = np.asarray(_reference_mixer(u[r], p, w_out), np.float64)
        close(y[r], want[:T], tol=2e-5)
        close(nxt[r, 0], want[T], tol=2e-5)


def test_the_tail_is_gathered_from_the_last_live_inputs():
    hidden, p = _leaves()
    u = jax.random.normal(jax.random.PRNGKey(8), (2, 12, hidden))
    lens = jnp.asarray([12, 5])
    _, state, tail = gdn.mix(u @ p["w_qkvz"], u @ p["w_ba"], p, SIZES, 1e-6, new_lens=lens)
    _, want_state, want_tail = gdn.mix(u[1:, :5] @ p["w_qkvz"], u[1:, :5] @ p["w_ba"], p, SIZES, 1e-6)
    close(state[1], np.asarray(want_state[0], np.float64))
    assert np.array_equal(np.asarray(tail[1]), np.asarray(want_tail[0]))


@pytest.mark.parametrize("rows, tokens, want", [
    (128, 256, 32),  # the benchmark's prefill: 2 ** 24 / (256 x 64 x 32 heads)
    (8, 256, 8), (128, 1, 128),  # the check's prefill and a decode step: whole
    (6, 2048, 3), (7, 2048, 1)])  # the largest divisor of the rows that fits
def test_a_call_s_rows_are_grouped_by_their_in_chunk_matrices(rows, tokens, want):
    assert gdn.group_rows(rows, tokens, 64, 32) == want


def test_float32_sums_between_the_projections_cut_a_bfloat16_mixer_s_error():
    """bfloat16 activations and weights: with ``[q | k | v | z]`` handed over as
    the in-projection's float32 sums, nothing between the projections rounds
    them, the result leaves in the activations' dtype, and it lies closer to
    the reference on the same weights than with the sums rounded first (read
    0.0017 against 0.0063 here; ``ops/gdn.py`` says what the rounding costs
    where a head's state holds few terms)."""
    hidden, p = _leaves()
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
    u = jax.random.normal(jax.random.PRNGKey(11), (4, 40, hidden)).astype(jnp.bfloat16)
    sums = jnp.matmul(u, p["w_qkvz"], preferred_element_type=jnp.float32)
    ba = u @ p["w_ba"]
    want = np.stack([np.asarray(_reference_mixer(
        u[r].astype(jnp.float32), jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p), jnp.eye(HV * DV)))
        for r in range(4)])
    errs = []
    for qkvz in (sums, sums.astype(jnp.bfloat16)):
        y, state, tail = gdn.mix(qkvz, ba, p, SIZES, 1e-6)
        assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32 and tail.dtype == qkvz.dtype
        errs.append(float(np.linalg.norm(np.asarray(y, np.float32) - want) / np.linalg.norm(want)))
    assert errs[0] < 0.5 * errs[1] < 0.01, errs


# --- the pool's row and the decode kernel --------------------------------------

def _pool_inputs(rows, seed=9, Hk=2, Hv=4, D=128, layers=3, slots=6):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return dict(
        pool=jax.random.normal(keys[0], (layers, slots, Hv, D, D)),
        q=unit(jax.random.normal(keys[1], (rows, Hk, D))) * D ** -0.5, k=unit(jax.random.normal(keys[2], (rows, Hk, D))),
        v=jax.random.normal(keys[3], (rows, Hv, D)), g=-jax.random.uniform(keys[4], (rows, Hv)),
        beta=jax.nn.sigmoid(jax.random.normal(keys[5], (rows, Hv))))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_pool_step_is_the_step_on_the_layer_s_first_rows(impl):
    a = _pool_inputs(4)
    live, fresh = jnp.asarray([True, False, True, True]), jnp.asarray([False, False, True, False])
    args = (a["q"], a["k"], a["v"], a["g"], a["beta"])
    o, pool = gdn.gdn_pool_step(a["pool"], jnp.int32(1), *args, live=live, fresh=fresh, impl=impl)
    came = jnp.where(fresh[:, None, None, None], 0.0, a["pool"][1, :4])
    want_o, want_state = gdn.gdn_step(came, *args, live=live)
    close(o, np.asarray(want_o, np.float64), tol=2e-6)
    close(pool[1, :4], np.asarray(want_state, np.float64), tol=2e-6)
    # nothing else of the pool is touched, and a dead row's slot is bitwise what it was
    for layer, rows in ((0, slice(None)), (2, slice(None)), (1, slice(4, None)), (1, slice(1, 2))):
        assert np.array_equal(np.asarray(pool[layer, rows]), np.asarray(a["pool"][layer, rows]))


def test_the_kernel_in_interpret_mode_is_xla_s_form():
    a = _pool_inputs(5, seed=10, Hk=2, Hv=2)  # one value head a key head, too
    args = (a["q"], a["k"], a["v"], a["g"], a["beta"])
    live, fresh = jnp.asarray([True] * 4 + [False]), jnp.asarray([True] + [False] * 4)
    o_x, pool_x = gdn.gdn_pool_step(a["pool"], jnp.int32(2), *args, live=live, fresh=fresh, impl="xla")
    o_k, pool_k = gdn.gdn_pool_step(a["pool"], jnp.int32(2), *args, live=live, fresh=fresh, impl="pallas")
    close(o_k, np.asarray(o_x, np.float64), tol=2e-6)
    close(pool_k, np.asarray(pool_x, np.float64), tol=2e-6)


def test_the_kernel_takes_whole_lane_tiles_only():
    from deepspeed_tpu.ops.pallas import gdn_update

    assert gdn_update.takes(16, 32, 128, 128) and gdn_update.takes(2, 2, 128, 128)
    assert not gdn_update.takes(2, 4, 16, 16) and not gdn_update.takes(2, 4, 128, 256)
    assert not gdn_update.takes(3, 4, 128, 128)


def test_auto_takes_xla_s_form_off_the_tpu():
    a = _pool_inputs(2, seed=11)
    args = (a["q"], a["k"], a["v"], a["g"], a["beta"])
    got = gdn.gdn_pool_step(a["pool"], jnp.int32(0), *args)
    want = gdn.gdn_pool_step(a["pool"], jnp.int32(0), *args, impl="xla")
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_the_bench_tool_runs_both_forms_at_a_toy_shape(monkeypatch):
    """``tools/gdn_update_bench.py::measure`` (a time comes only from a chip:
    here it is run for its shapes and its bytes alone)."""
    import importlib.util
    import os

    from benchmarks.lib import peaks

    spec = importlib.util.spec_from_file_location("gdn_update_bench", os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "tools", "gdn_update_bench.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    for name, value in dict(LAYERS=2, ROWS=3, HK=1, HV=2, CALLS=3, REPEATS=1).items():
        monkeypatch.setattr(tool, name, value)
    monkeypatch.setitem(peaks.DEVICE_PEAKS, jax.devices()[0].device_kind, peaks.DEVICE_PEAKS["TPU v5 lite"])
    for impl in ("xla", "pallas"):
        line = tool.measure(impl)
        assert line["impl"] == impl and line["own_bytes"] == 2 * 3 * 2 * 128 * 128 * 4 and line["ms_a_call"] > 0


# --- a decode step's convolution, in place on the conv pool ---------------------

def _conv_pool(rows, layers=2, slots=6, seed=12):
    pool = jax.random.normal(jax.random.PRNGKey(seed), (layers, slots, (SIZES.d_conv - 1) * SIZES.conv_dim))
    return pool.astype(jnp.bfloat16)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_decode_step_s_convolution_on_the_pool_is_the_mixer_s_on_an_array(impl, monkeypatch):
    """``mix`` at one token a row with the tail as a row of the conv pool
    (float32 inputs, no bias: the DeltaNet mixer's; ``conv_update`` in interpret
    mode, and XLA's form) against ``mix`` on the rows' tails as an array: the
    mixer's output to float32 rounding, the new tail to the bit, a dead row's
    and the other layer's and slots' untouched."""
    import functools

    from deepspeed_tpu.ops import ssm

    monkeypatch.setattr(ssm, "conv_pool_step", functools.partial(ssm.conv_pool_step, impl=impl))
    hidden, p = _leaves()
    rows, K, X = 4, SIZES.d_conv, SIZES.conv_dim
    u = jax.random.normal(jax.random.PRNGKey(13), (rows, 1, hidden))
    state = jax.random.normal(jax.random.PRNGKey(14), (rows, HV, DK, DV))
    pool = _conv_pool(rows)
    lens, fresh = jnp.asarray([1, 0, 1, 1]), jnp.asarray([False, False, True, False])
    y, left, pool_after = gdn.mix(u @ p["w_qkvz"], u @ p["w_ba"], p, SIZES, 1e-6, state=state,
                                  tail=gdn.PoolRow(pool, jnp.int32(1), fresh), new_lens=lens)
    came = jnp.where(fresh[:, None], 0, pool[1, :rows]).reshape(rows, K - 1, X)
    want_y, want_left, want_tail = gdn.mix(u @ p["w_qkvz"], u @ p["w_ba"], p, SIZES, 1e-6, state=state, tail=came,
                                           new_lens=lens)
    close(y, np.asarray(want_y, np.float64), tol=2e-6)
    close(left, np.asarray(want_left, np.float64), tol=2e-6)
    assert pool_after.dtype == jnp.bfloat16 and pool_after.shape == pool.shape
    bits = lambda a: np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))  # noqa: E731
    assert np.array_equal(bits(pool_after[1, :rows]), bits(want_tail.astype(jnp.bfloat16).reshape(rows, -1)))
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, [0, 2, 3]] = False
    assert np.array_equal(bits(pool_after)[untouched], bits(pool)[untouched])  # the dead row's too


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_prompt_then_single_steps_on_the_pools_give_what_one_longer_prompt_gives(impl, monkeypatch):
    """Both pools' rows through ``mix``: a prompt of 9 tokens (``T > 1``: the
    tail's slice and its write back are ``mix``'s now), then 4 single steps in
    place, against all 13 tokens at once. The inputs are ones bfloat16 holds
    exactly, so that what the conv pool keeps of them is what the longer prompt
    reads."""
    import functools

    from deepspeed_tpu.ops import ssm

    monkeypatch.setattr(ssm, "conv_pool_step", functools.partial(ssm.conv_pool_step, impl=impl))
    hidden, p = _leaves()
    rows, T, cut = 2, 13, 9
    keys = jax.random.split(jax.random.PRNGKey(15), 2)
    qkvz = jax.random.normal(keys[0], (rows, T, SIZES.proj_dim)).astype(jnp.bfloat16).astype(jnp.float32)
    ba = jax.random.normal(keys[1], (rows, T, 2 * HV))
    states, tails = jnp.ones((2, 3, HV, DK, DV)), _conv_pool(rows, slots=3)
    fresh, ys = jnp.ones((rows,), bool), []
    for lo, hi in [(0, cut)] + [(t, t + 1) for t in range(cut, T)]:
        y, states, tails = gdn.mix(qkvz[:, lo:hi], ba[:, lo:hi], p, SIZES, 1e-6,
                                   state=gdn.PoolRow(states, jnp.int32(0), fresh),
                                   tail=gdn.PoolRow(tails, jnp.int32(0), fresh))
        ys.append(y)
        fresh = jnp.zeros((rows,), bool)
    want_y, want_state, want_tail = gdn.mix(qkvz, ba, p, SIZES, 1e-6)
    close(jnp.concatenate(ys, axis=1), np.asarray(want_y, np.float64), tol=1e-5)
    close(states[0, :rows], np.asarray(want_state, np.float64), tol=1e-5)
    assert np.array_equal(np.asarray(tails[0, :rows], np.float32), np.asarray(want_tail.reshape(rows, -1)))
    assert np.array_equal(np.asarray(states[1]), np.ones((3, HV, DK, DV))) and tails.dtype == jnp.bfloat16
