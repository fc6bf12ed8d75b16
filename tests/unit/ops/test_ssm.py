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
