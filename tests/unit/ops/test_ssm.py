"""``ops/ssm.py``: the two forms of the Mamba-2 recurrence against the plain
reference's sequential one (``benchmarks/reference/granitemoehybrid.py::mamba``
is a whole mixer; here the recurrence alone is written out the same way, one
token after another), in float32 on the CPU.

Tolerance 2e-5 of the largest entry: the chunked form sums a chunk's
contributions in another order than the recurrence (read 2e-7 to 1e-6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import SSMConfig
from deepspeed_tpu.ops import ssm

H, P, G, N, CHUNK = 6, 8, 2, 5, 8
SIZES = SSMConfig(n_heads=H, head_dim=P, d_state=N, n_groups=G, d_conv=4, chunk_size=CHUNK)


def _inputs(rows, T, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        x=jax.random.normal(keys[0], (rows, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(keys[1], (rows, T, H)) - 2.0),
        A_log=jnp.log(jax.random.uniform(keys[2], (H,), minval=1.0, maxval=16.0)),
        B=jax.random.normal(keys[3], (rows, T, G, N)), C=jax.random.normal(keys[4], (rows, T, G, N)),
        D=jax.random.uniform(keys[5], (H,), minval=0.5, maxval=1.5),
        state=jax.random.normal(keys[6], (rows, H, P, N)))


def recurrence(x, dt, A_log, B, C, D, state, lens=None):
    """One row at a time, one token after another; tokens past ``lens`` are not fed."""
    ys, states = [], []
    for r in range(x.shape[0]):
        S = np.asarray(state[r], np.float64)
        y = np.zeros(x.shape[1:], np.float64)
        for t in range(x.shape[1] if lens is None else lens[r]):
            for h in range(H):
                g = h // (H // G)
                a = np.exp(-float(dt[r, t, h]) * np.exp(float(A_log[h])))
                S[h] = a * S[h] + float(dt[r, t, h]) * np.outer(x[r, t, h], B[r, t, g])
                y[t, h] = S[h] @ np.asarray(C[r, t, g], np.float64) + float(D[h]) * np.asarray(x[r, t, h])
        ys.append(y)
        states.append(S)
    return np.stack(ys), np.stack(states)


def close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("T", [5, 8, 29])  # less than a chunk, one, three and a part
def test_the_chunked_form_is_the_recurrence(T):
    a = _inputs(2, T)
    y, state = ssm.ssd_chunked(a["x"], a["dt"], a["A_log"], a["B"], a["C"], a["D"], CHUNK, a["state"])
    want_y, want_state = recurrence(**a)
    close(y, want_y)
    close(state, want_state)


def test_ragged_rows_stop_at_their_last_live_token():
    a = _inputs(3, 21, seed=1)
    lens = np.asarray([21, 9, 0])
    live = jnp.arange(21)[None, :] < jnp.asarray(lens)[:, None]
    y, state = ssm.ssd_chunked(a["x"], a["dt"], a["A_log"], a["B"], a["C"], a["D"], CHUNK, a["state"], live)
    want_y, want_state = recurrence(**a, lens=lens)
    for r, n in enumerate(lens):
        close(y[r, :n], want_y[r, :n]) if n else None
    close(state, want_state)
    # a row with no live token keeps its state to the bit
    assert np.array_equal(np.asarray(state[2]), np.asarray(a["state"][2]))


def test_chunks_then_single_steps_hand_the_state_over():
    a = _inputs(2, 19, seed=2)
    cut = 11
    head = {k: (v[:, :cut] if k in ("x", "dt", "B", "C") else v) for k, v in a.items()}
    y0, state = ssm.ssd_chunked(head["x"], head["dt"], a["A_log"], head["B"], head["C"], a["D"], CHUNK,
                                a["state"])
    ys = [y0]
    for t in range(cut, 19):
        y, state = ssm.ssm_step(state, a["x"][:, t], a["dt"][:, t], a["A_log"], a["B"][:, t], a["C"][:, t],
                                a["D"])
        ys.append(y[:, None])
    want_y, want_state = recurrence(**a)
    close(jnp.concatenate(ys, axis=1), want_y)
    close(state, want_state)


def test_a_dead_row_s_step_leaves_its_state_to_the_bit():
    a = _inputs(3, 1, seed=3)
    live = jnp.asarray([True, False, True])
    _, state = ssm.ssm_step(a["state"], a["x"][:, 0], a["dt"][:, 0], a["A_log"], a["B"][:, 0], a["C"][:, 0],
                            a["D"], live)
    assert np.array_equal(np.asarray(state[1]), np.asarray(a["state"][1]))
    assert not np.array_equal(np.asarray(state[0]), np.asarray(a["state"][0]))


def test_rows_taken_a_group_at_a_time_are_the_rows_at_once(monkeypatch):
    a = _inputs(4, 16, seed=4)
    args = (a["x"], a["dt"], a["A_log"], a["B"], a["C"], a["D"], CHUNK, a["state"])
    whole = ssm.ssd_chunked(*args)
    monkeypatch.setattr(ssm, "_GROUP_ELEMENTS", 2 * 16 * CHUNK * H)  # two rows a group
    grouped = ssm.ssd_chunked(*args)
    for got, want in zip(grouped, whole):
        close(got, np.asarray(want, np.float64), tol=1e-6)


def _mixer_leaves(seed=5):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"ssm_conv": {"kernel": 0.5 * jax.random.normal(keys[0], (SIZES.d_conv, SIZES.conv_dim)),
                         "bias": 0.2 * jax.random.normal(keys[1], (SIZES.conv_dim,))},
            "A_log": jnp.log(jax.random.uniform(keys[2], (H,), minval=1.0, maxval=16.0)),
            "dt_bias": jax.random.normal(keys[3], (H,)) - 3.0,
            "D": jax.random.uniform(keys[4], (H,), minval=0.5, maxval=1.5),
            "ssm_norm": {"scale": 1.0 + 0.1 * jax.random.normal(keys[5], (SIZES.d_inner,))}}


def test_one_prompt_at_two_paddings_leaves_the_same_state_and_tail():
    """Pad tokens run through the layer (a prompt is padded to the call's
    shape) and must move neither the state nor the convolution's tail, whatever
    they hold."""
    leaves = _mixer_leaves()
    n = 11
    fed = jax.random.normal(jax.random.PRNGKey(6), (1, n, SIZES.proj_dim))
    lens = jnp.asarray([n])
    outs = []
    for padded in (n, 16, 24):
        junk = 3.0 * jax.random.normal(jax.random.PRNGKey(padded), (1, padded - n, SIZES.proj_dim))
        y, state, tail = ssm.mix(jnp.concatenate([fed, junk], axis=1), leaves, SIZES, 1e-5, new_lens=lens)
        outs.append((y[:, :n], state, tail))
    for y, state, tail in outs[1:]:
        close(y, np.asarray(outs[0][0], np.float64), tol=1e-6)
        close(state, np.asarray(outs[0][1], np.float64), tol=1e-6)
        assert np.array_equal(np.asarray(tail), np.asarray(outs[0][2]))
    # the tail is the last d_conv - 1 LIVE inputs
    _, xbc, _ = ssm.split_projection(fed, SIZES)
    assert np.array_equal(np.asarray(outs[0][2]), np.asarray(xbc[:, n - 3:n]))


def test_a_short_prompt_s_tail_starts_with_what_came_before():
    leaves = _mixer_leaves()
    fed = jax.random.normal(jax.random.PRNGKey(7), (2, 8, SIZES.proj_dim))
    before = jax.random.normal(jax.random.PRNGKey(8), (2, 3, SIZES.conv_dim))
    _, _, tail = ssm.mix(fed, leaves, SIZES, 1e-5, tail=before, new_lens=jnp.asarray([2, 0]))
    _, xbc, _ = ssm.split_projection(fed, SIZES)
    assert np.array_equal(np.asarray(tail[0]), np.asarray(jnp.concatenate([before[0, 2:], xbc[0, :2]])))
    assert np.array_equal(np.asarray(tail[1]), np.asarray(before[1]))  # a dead row's: as it came


def test_the_chunked_form_s_gradients_are_the_recurrence_s():
    a = _inputs(2, 13, seed=9)
    names = ("x", "dt", "A_log", "B", "C", "D", "state")

    def by_steps(*args):
        v = dict(zip(names, args))
        state, total = v["state"], 0.0
        for t in range(13):
            y, state = ssm.ssm_step(state, v["x"][:, t], v["dt"][:, t], v["A_log"], v["B"][:, t], v["C"][:, t],
                                    v["D"])
            total = total + jnp.sum(jnp.sin(y))
        return total + jnp.sum(state ** 2)

    def by_chunks(*args):
        v = dict(zip(names, args))
        y, state = ssm.ssd_chunked(v["x"], v["dt"], v["A_log"], v["B"], v["C"], v["D"], CHUNK, v["state"])
        return jnp.sum(jnp.sin(y)) + jnp.sum(state ** 2)

    args = tuple(a[k] for k in names)
    want = jax.grad(by_steps, argnums=range(len(names)))(*args)
    got = jax.grad(by_chunks, argnums=range(len(names)))(*args)
    for name, g, w in zip(names, got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        close(g, np.asarray(w, np.float64), tol=5e-5)


@pytest.mark.parametrize("heads, width, groups", [(6, 8, 1), (16, 16, 2), (16, 16, 1)])
def test_the_pool_kernel_is_the_step_on_a_row_of_the_pool(heads, width, groups):
    """``ops/pallas/ssm_update.py`` in interpret mode against XLA's form:
    three rows of a pool of five slots and four layers, one live, one dead, one
    that starts a sequence over another's state; the other layers' rows and the
    other slots come back to the bit. 48 channels are one tile of their own
    width, 256 two tiles of 128 (a group each, or both in one)."""
    keys = jax.random.split(jax.random.PRNGKey(11), 8)
    rows, slots, layers = 3, 5, 4
    pool = jnp.stack([ssm.to_pool(jax.random.normal(k, (slots, heads, width, N)))
                      for k in jax.random.split(keys[0], layers)])
    assert pool.shape == (layers, slots, heads * width // ssm.pool_tile(heads * width), N,
                          ssm.pool_tile(heads * width))
    a = dict(x=jax.random.normal(keys[1], (rows, heads, width)),
             dt=jax.nn.softplus(jax.random.normal(keys[2], (rows, heads)) - 2.0),
             A_log=jnp.log(jax.random.uniform(keys[3], (heads,), minval=1.0, maxval=16.0)),
             B=jax.random.normal(keys[4], (rows, groups, N)), C=jax.random.normal(keys[5], (rows, groups, N)),
             D=jax.random.uniform(keys[6], (heads,), minval=0.5, maxval=1.5))
    live, fresh = jnp.asarray([True, False, True]), jnp.asarray([False, False, True])
    layer = jnp.int32(2)
    args = (pool, layer, a["x"], a["dt"], a["A_log"], a["B"], a["C"], a["D"])
    y_k, pool_k = ssm.ssm_pool_step(*args, live=live, fresh=fresh, impl="pallas")
    y_x, pool_x = ssm.ssm_pool_step(*args, live=live, fresh=fresh, impl="xla")
    close(y_k, np.asarray(y_x, np.float64), tol=1e-6)
    close(pool_k, np.asarray(pool_x, np.float64), tol=1e-6)
    # and XLA's form is the step on the states themselves
    y_s, states = ssm.ssm_step(jnp.where(fresh[:, None, None, None], 0.0, ssm.from_pool(pool[2, :rows], heads, width)),
                               a["x"], a["dt"], a["A_log"], a["B"], a["C"], a["D"], live)
    close(y_x, np.asarray(y_s, np.float64), tol=1e-6)
    close(ssm.from_pool(pool_x[2, :rows], heads, width), np.asarray(states, np.float64), tol=1e-6)
    untouched = np.ones((layers, slots), bool)
    untouched[2, [0, 2]] = False
    assert np.array_equal(np.asarray(pool_k)[untouched], np.asarray(pool)[untouched])  # the dead row's too
    assert not np.array_equal(np.asarray(pool_k[2, 0]), np.asarray(pool[2, 0]))
    # the row that starts a sequence: from zeros, not from what its slot held
    want = np.asarray(a["dt"][2])[:, None, None] * np.einsum(
        "hp,hn->hpn", np.asarray(a["x"][2]), np.repeat(np.asarray(a["B"][2]), heads // groups, axis=0))
    close(ssm.from_pool(pool_k[2, 2:3], heads, width)[0], want, tol=1e-6)


def test_the_pool_s_layout_puts_the_channels_on_the_lanes():
    states = jax.random.normal(jax.random.PRNGKey(12), (2, 16, 16, N))
    tiles = ssm.to_pool(states)
    assert tiles.shape == (2, 2, N, 128)
    assert np.array_equal(np.asarray(ssm.from_pool(tiles, 16, 16)), np.asarray(states))
    # tile t, lane w is channel 128 t + w = (head, p); sublane n is the state's n
    assert float(tiles[1, 1, 3, 37]) == float(states[1, (128 + 37) // 16, (128 + 37) % 16, 3])


def test_a_prompt_s_states_go_into_and_out_of_the_pool_through_the_kernels():
    """``ssm_rows_in`` / ``ssm_rows_out`` in interpret mode: what the chunked
    scan leaves, ``[rows, H, P, N]``, into the call's slots of a layer's row of
    the pool and back, every other tile of the pool left to the bit."""
    from deepspeed_tpu.ops.pallas import ssm_update

    heads, width, rows = 16, 16, 3
    pool = jax.random.normal(jax.random.PRNGKey(13), (4, 5, 2, N, 128))
    states = jax.random.normal(jax.random.PRNGKey(14), (rows, heads, width, N))
    got = ssm_update.rows_in(pool, jnp.int32(1), states.reshape(rows, 2, 128, N))
    want = pool.at[1, :rows].set(ssm.to_pool(states))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    back = ssm_update.rows_out(got, jnp.int32(1), rows).reshape(rows, heads, width, N)
    assert np.array_equal(np.asarray(back), np.asarray(states))


# --- a decode step's convolution, in place on the conv pool ---------------------

CONV_CASES = [  # the inputs' dtype, a bias, the projection's width, the inputs' first column
    pytest.param(jnp.bfloat16, True, 704, 128, id="bf16_bias_inside_a_projection"),  # a state-space mixer's
    pytest.param(jnp.float32, False, 384, 0, id="f32_no_bias_a_projection_s_head"),  # a DeltaNet mixer's
    pytest.param(jnp.float32, True, 512, 256, id="f32_bias_a_block_of_its_own"),
    pytest.param(jnp.bfloat16, False, 256, 0, id="bf16_no_bias_alone"),
]


def _conv_pool(rows, dtype, bias, W, X=256, K=4, layers=3, slots=12, seed=20):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return dict(pool=jax.random.normal(keys[0], (layers, slots, (K - 1) * X)).astype(jnp.bfloat16),
                x=jax.random.normal(keys[1], (rows, W)).astype(dtype),
                taps=(0.5 * jax.random.normal(keys[2], (K, X))).astype(jnp.bfloat16),
                bias=(0.2 * jax.random.normal(keys[3], (X,))).astype(jnp.bfloat16) if bias else None)


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16 if a.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("dtype, bias, W, at", CONV_CASES)
def test_the_conv_pool_step_is_conv_inputs_at_one_token_on_the_layer_s_first_rows(impl, dtype, bias, W, at):
    """``ops/pallas/conv_update.py`` in interpret mode, and XLA's form beside
    it: eight rows of a pool of twelve slots and three layers (live, dead, one
    that starts a sequence over another's tail), ``K = 4``, against
    ``conv_inputs`` on the rows' tails as arrays: the outputs to float32
    rounding, the new tail to the bit, a dead row's tail and every other
    layer's and slot's untouched to the bit."""
    X, K, rows = 256, 4, 8
    a = _conv_pool(rows, dtype, bias, W)
    live = jnp.asarray([True, False, True, True, False, True, True, True])
    fresh = jnp.asarray([False, False, True, False, False, False, True, False])
    y, pool = ssm.conv_pool_step(a["pool"], jnp.int32(1), a["x"], a["taps"], a["bias"], live, fresh, at=at, impl=impl)
    came = jnp.where(fresh[:, None], 0, a["pool"][1, :rows]).reshape(rows, K - 1, X)
    want_y, want_tail = ssm.conv_inputs(a["x"][:, None, at:at + X], came, a["taps"], a["bias"],
                                        live.astype(jnp.int32))
    assert y.shape == (rows, X) and y.dtype == dtype and pool.dtype == jnp.bfloat16
    close(y, np.asarray(want_y[:, 0], np.float64), tol=1e-6 if dtype == jnp.float32 else 1e-2)
    assert np.array_equal(_bits(pool[1, :rows]), _bits(want_tail.astype(jnp.bfloat16).reshape(rows, -1)))
    # a live row's tail moved on by one input, which is the token's own, rounded as the pool keeps it
    assert np.array_equal(_bits(pool[1, 0]), _bits(jnp.concatenate(
        [a["pool"][1, 0, X:], a["x"][0, at:at + X].astype(jnp.bfloat16)])))
    untouched = np.ones(a["pool"].shape[:2], bool)
    untouched[1, np.flatnonzero(np.asarray(live))] = False
    assert np.array_equal(_bits(pool)[untouched], _bits(a["pool"])[untouched])  # the dead rows' too


def test_the_conv_kernel_over_several_blocks_of_rows_is_xla_s_form_to_the_bit():
    """Twenty-four rows are three grid steps of eight: one all live, one with
    dead rows, one with rows that start a sequence."""
    rows = 24
    a = _conv_pool(rows, jnp.bfloat16, True, 256, slots=32, seed=27)
    live = jnp.ones((rows,), bool).at[jnp.asarray([9, 12])].set(False)
    fresh = jnp.zeros((rows,), bool).at[jnp.asarray([17, 23])].set(True)
    got = ssm.conv_pool_step(a["pool"], jnp.int32(2), a["x"], a["taps"], a["bias"], live, fresh, impl="pallas")
    want = ssm.conv_pool_step(a["pool"], jnp.int32(2), a["x"], a["taps"], a["bias"], live, fresh, impl="xla")
    for g, w in zip(got, want):
        assert np.array_equal(_bits(g), _bits(w))
    assert np.array_equal(_bits(got[1][2, 9]), _bits(a["pool"][2, 9]))
    assert not np.any(np.asarray(got[1][2, 17, :512], np.float32))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_fresh_row_s_convolution_starts_from_zeros_whatever_its_slot_held(impl):
    X = 256
    a = _conv_pool(8, jnp.float32, True, X, seed=21)
    fresh = jnp.arange(8) % 2 == 0
    y, pool = ssm.conv_pool_step(a["pool"], jnp.int32(2), a["x"], a["taps"], a["bias"], None, fresh, impl=impl)
    want_y, want_tail = ssm.conv_inputs(a["x"][:, None], None, a["taps"], a["bias"])  # no tail: zeros
    close(y[::2], np.asarray(want_y[::2, 0], np.float64), tol=1e-6)
    assert np.array_equal(_bits(pool[2, :8:2]), _bits(want_tail[::2].astype(jnp.bfloat16).reshape(4, -1)))
    assert not np.any(np.asarray(pool[2, :8:2, :2 * X], np.float32))  # [0, 0, x]
    assert np.abs(np.asarray(y[1::2]) - np.asarray(want_y[1::2, 0])).max() > 1e-3  # the others from their own tails


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_prompt_s_tail_handed_to_single_steps_gives_what_one_longer_prompt_gives(impl):
    """A prompt of 11 tokens through ``conv_inputs`` with a row of the pool as
    its tail (``T > 1``: the slice and the update around today's lines), then
    8 single steps in place on that row, against all 19 tokens at once: row 1
    a prompt of 7 padded to 11, whose tail ends at its seventh token."""
    X, K, rows, T, cut, short = 256, 4, 8, 19, 11, 7
    a = _conv_pool(rows, jnp.float32, True, X, seed=22)
    # (inputs that bfloat16 holds exactly: what the pool keeps of them is then what the longer prompt reads)
    xs = jax.random.normal(jax.random.PRNGKey(23), (rows, T, X)).astype(jnp.bfloat16).astype(jnp.float32)
    lens = jnp.full((rows,), cut).at[1].set(short)
    prompt = xs[:, :cut].at[1, short:].set(9.0)  # row 1's pad tokens, whatever they hold
    steps = xs[:, cut:].at[1].set(xs[1, short:short + T - cut])  # its tokens 7.. come as steps
    y0, pool = ssm.conv_inputs(prompt, ssm.PoolRow(a["pool"], jnp.int32(0), jnp.ones((rows,), bool)), a["taps"],
                               a["bias"], lens)
    ys = []
    for t in range(T - cut):
        y, pool = ssm.conv_pool_step(pool, jnp.int32(0), steps[:, t], a["taps"], a["bias"], impl=impl)
        ys.append(y[:, None])
    ys = jnp.concatenate(ys, axis=1)
    want, _ = ssm.conv_inputs(xs, None, a["taps"], a["bias"])
    close(y0[0], np.asarray(want[0, :cut], np.float64), tol=1e-6)
    close(y0[1, :short], np.asarray(want[1, :short], np.float64), tol=1e-6)
    close(ys[0], np.asarray(want[0, cut:], np.float64), tol=1e-6)
    close(ys[1], np.asarray(want[1, short:short + T - cut], np.float64), tol=1e-6)
    assert np.array_equal(_bits(pool[0, :rows]), _bits(steps[:, -(K - 1):].astype(jnp.bfloat16).reshape(rows, -1)))
    assert np.array_equal(_bits(pool[1:]), _bits(a["pool"][1:]))


def test_the_mixer_s_tail_as_a_row_of_the_pool_is_its_tail_as_an_array():
    """``mix`` with both the state and the tail as rows of their pools (what
    ``inference/paged.py::state_layer`` hands it): a prompt, then two single
    steps, against ``mix`` on arrays."""
    leaves = _mixer_leaves()
    rows, slots = 2, 3
    fed = jax.random.normal(jax.random.PRNGKey(24), (rows, 7, SIZES.proj_dim))
    fresh = jnp.ones((rows,), bool)
    states = jnp.zeros((2, slots, SIZES.d_inner // ssm.pool_tile(SIZES.d_inner), N, ssm.pool_tile(SIZES.d_inner)))
    tails = jax.random.normal(jax.random.PRNGKey(25), (2, slots, 3 * SIZES.conv_dim))  # float32: no rounding here
    state = tail = None
    for lo, hi in ((0, 5), (5, 6), (6, 7)):
        y, states, tails = ssm.mix(fed[:, lo:hi], leaves, SIZES, 1e-5, state=ssm.PoolRow(states, jnp.int32(1), fresh),
                                   tail=ssm.PoolRow(tails, jnp.int32(1), fresh))
        want_y, state, tail = ssm.mix(fed[:, lo:hi], leaves, SIZES, 1e-5, state=state, tail=tail)
        close(y, np.asarray(want_y, np.float64), tol=1e-6)
        assert np.array_equal(np.asarray(tails[1, :rows]), np.asarray(tail.reshape(rows, -1)))
        fresh = jnp.zeros((rows,), bool)
    assert not np.any(np.asarray(tails[1, rows:] == 0)) and tails.shape == (2, slots, 3 * SIZES.conv_dim)


def test_the_conv_kernel_takes_whole_lane_tiles_only():
    from deepspeed_tpu.ops.pallas import conv_update

    assert conv_update.takes(4352, 64, 4096) and conv_update.takes(8192, 128) and conv_update.takes(4352, 8, 4096)
    assert not conv_update.takes(SIZES.conv_dim, 8) and not conv_update.takes(4352, 64, 4000)
    assert not conv_update.takes(4352, 3, 4096)


def test_auto_takes_xla_s_convolution_off_the_tpu():
    a = _conv_pool(8, jnp.bfloat16, True, 256, seed=26)
    got = ssm.conv_pool_step(a["pool"], jnp.int32(0), a["x"], a["taps"], a["bias"])
    want = ssm.conv_pool_step(a["pool"], jnp.int32(0), a["x"], a["taps"], a["bias"], impl="xla")
    for g, w in zip(got, want):
        assert np.array_equal(_bits(g), _bits(w))
    jaxpr = str(jax.make_jaxpr(lambda *args: ssm.conv_pool_step(*args))(
        a["pool"], jnp.int32(0), a["x"], a["taps"], a["bias"]))
    assert "pallas_call" not in jaxpr


def test_the_conv_bench_tool_runs_both_forms_at_a_toy_shape(monkeypatch):
    """``tools/conv_update_bench.py::measure`` (a time comes only from a chip:
    here it is run for its shapes and its bytes alone)."""
    import importlib.util
    import os

    from benchmarks.lib import peaks

    spec = importlib.util.spec_from_file_location("conv_update_bench", os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "tools", "conv_update_bench.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.SHAPES["granite"][:5] == (36, 64, 4352, 8512, 4096) and tool.SHAPES["qwen3_next"][:5] == (
        9, 128, 8192, 12288, 0)
    monkeypatch.setattr(tool, "SHAPES", {"toy": (2, 8, 256, 640, 128, "bfloat16", True),
                                         "toy_f32": (2, 8, 256, 384, 0, "float32", False)})
    for name, value in dict(UNROLLED=3, TURNS=2, REPEATS=1).items():
        monkeypatch.setattr(tool, name, value)
    monkeypatch.setitem(peaks.DEVICE_PEAKS, jax.devices()[0].device_kind, peaks.DEVICE_PEAKS["TPU v5 lite"])
    for shape, own in (("toy", 8 * (2 * 3 * 256 * 2 + 2 * 256 * 2)), ("toy_f32", 8 * (2 * 3 * 256 * 2 + 2 * 256 * 4))):
        for impl, dead_rows in (("xla", False), ("pallas", False), ("pallas", True)):
            line = tool.measure(shape, impl, dead_rows)
            assert line["impl"] == impl and line["own_bytes"] == own and line["ms_a_call"] > 0
