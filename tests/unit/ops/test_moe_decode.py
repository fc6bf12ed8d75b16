"""``ops/pallas/moe_decode.py``: a routed layer's decode product over the
experts its rows picked, in interpret mode, against the dense form
(``inference/model.py::_all_experts``) in float32 on the same (rounded)
operands: on a toy and at each routed cell's ``(T, E, M, H, k)`` cut to four
experts; every expert touched, one, none; a config without ``w_gate``; a layer
index other than 0; a chip's share whose rows pick other chips' experts; and
through ``_experts`` itself, handed the stack where the layer scan hands it.

Tolerances, of the largest entry: 1e-5 in float32 (the sums in another order);
1e-2 in bf16 (the kernel rounds ``h`` to bf16 before the down-projection, as
the dense form does, and sums the experts in float32, which it does not)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import model
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops import registry
from deepspeed_tpu.ops.pallas import moe_decode

TOY = (16, 8, 128, 256, 2)
CELLS = {"toy": TOY, "qwen": (128, 4, 2048, 512, 2), "glm": (64, 4, 2048, 1536, 4), "xing": (64, 4, 3584, 1024, 4)}
LAYERS = 2


def _weights(E, M, H, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    make = lambda key, shape: (jax.random.normal(key, shape) * shape[-2] ** -0.5).astype(dtype)  # noqa: E731
    return (make(keys[0], (LAYERS, E, M, H)), make(keys[1], (LAYERS, E, M, H)), make(keys[2], (LAYERS, E, H, M)))


def _gate(T, E, k, among, seed=1):
    """Each row's ``k`` picks among the experts ``among``, softmax weights; no pick where ``among`` is empty."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    if not among:
        return jnp.zeros((T, E), jnp.float32)
    k = min(k, len(among))
    picks = jnp.asarray(among)[jnp.argsort(jax.random.uniform(keys[0], (T, len(among))), axis=1)[:, :k]]
    weights = jax.nn.softmax(jax.random.normal(keys[1], (T, k)))
    return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], picks].set(weights)


def _close(got, want, tol):
    want = np.asarray(want, np.float64)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol * max(np.abs(want).max(), 1e-30), rtol=0)


def _dense(x, gate, w_gate, w_up, w_down, layer, activation):
    f32 = lambda a: None if a is None else a.astype(jnp.float32)  # noqa: E731
    return model._all_experts(f32(x), gate, f32(w_gate), f32(w_up), f32(w_down), layer, activation)


SCENARIOS = {
    # (the experts some row picks, the layer, the activation)
    "all-touched": (None, 0, "silu_glu"),
    "one-touched": ([5], 0, "silu_glu"),
    "none-touched": ([], 0, "silu_glu"),
    "some-touched-layer-1": ([1, 2, 6], 1, "silu_glu"),
    "no-w_gate": ([0, 3, 7], 1, "gelu"),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_the_kernel_is_the_dense_product_on_a_toy(scenario, dtype):
    T, E, M, H, k = TOY
    among, layer, activation = SCENARIOS[scenario]
    w_gate, w_up, w_down = _weights(E, M, H, dtype)
    if activation != "silu_glu":
        w_gate = None
    x = jax.random.normal(jax.random.PRNGKey(2), (T, M)).astype(dtype)
    gate = _gate(T, E, k, list(range(E)) if among is None else among)
    assert moe_decode.takes(T, M, H, x.dtype, w_up.dtype)
    got = moe_decode.moe_decode(x, gate, w_gate, w_up, w_down, jnp.int32(layer), activation, th=128)
    assert got.shape == (T, M) and got.dtype == x.dtype
    if among == []:
        assert not np.asarray(got, np.float32).any()  # no expert read, no term: zeros
    _close(got, _dense(x, gate, w_gate, w_up, w_down, layer, activation), 1e-5 if dtype == jnp.float32 else 1e-2)


@pytest.mark.parametrize("among", [None, [2]], ids=["all-touched", "one-touched"])
@pytest.mark.parametrize("cell", ["qwen", "glm", "xing"])
def test_the_kernel_is_the_dense_product_at_a_cell_s_widths(cell, among):
    """At the tile of the hidden width the kernel picks for the cell's shapes."""
    T, E, M, H, k = CELLS[cell]
    w_gate, w_up, w_down = _weights(E, M, H, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(2), (T, M)).astype(jnp.bfloat16)
    gate = _gate(T, E, k, list(range(E)) if among is None else among)
    assert moe_decode.takes(T, M, H, x.dtype, w_up.dtype)
    got = moe_decode.moe_decode(x, gate, w_gate, w_up, w_down, jnp.int32(1), "silu_glu")
    _close(got, _dense(x, gate, w_gate, w_up, w_down, 1, "silu_glu"), 1e-2)


@pytest.mark.parametrize("flags,ids,n", [
    ([0, 1, 0, 1, 1, 0], [1, 3, 4, 4, 4, 4], 3),
    ([1, 1, 1, 1, 1, 1], [0, 1, 2, 3, 4, 5], 6),
    ([0, 0, 0, 0, 0, 1], [5, 5, 5, 5, 5, 5], 1),
    ([0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0], 0),
], ids=["some", "all", "last", "none"])
def test_the_touched_experts_come_packed_to_the_front(flags, ids, n):
    """In their own order, the rest of the list naming the last of them: a slot
    past the count asks for the block already there."""
    gate = jnp.asarray([[0.0] * len(flags), [-0.5 * f for f in flags], [0.25 * f for f in flags]], jnp.float32)
    got_ids, got_n = moe_decode.touched_experts(gate)
    assert got_ids.tolist() == ids and got_n.tolist() == [n] and got_ids.dtype == got_n.dtype == jnp.int32


@pytest.mark.parametrize("rows,dtype,weights,taken", [
    (16, jnp.bfloat16, jnp.bfloat16, True), (8, jnp.float32, jnp.float32, True),
    (8, jnp.bfloat16, jnp.bfloat16, True),  # half a sublane tile of bf16 rows: row_bucket's least
    (12, jnp.bfloat16, jnp.bfloat16, False),
    (16, jnp.bfloat16, jnp.float32, False),  # weights that would be cast, a layer's slice at a time
    (16, jnp.bfloat16, None, False),  # a quantized leaf
], ids=["bf16", "f32", "8-bf16-rows", "12-rows", "f32-weights", "quantized"])
def test_takes(rows, dtype, weights, taken):
    assert moe_decode.takes(rows, 2048, 512, jnp.dtype(dtype), weights and jnp.dtype(weights)) is taken
    assert not moe_decode.takes(16, 2048 + 64, 512, jnp.dtype(dtype), jnp.dtype(dtype))
    assert not moe_decode.takes(16, 2048, 512 + 64, jnp.dtype(dtype), jnp.dtype(dtype))


def _cfg(E, M, H, k, **kw):
    return TransformerConfig(vocab_size=64, hidden_size=M, num_layers=LAYERS, num_heads=2, intermediate_size=H,
                             max_seq_len=32, num_experts=E, moe_top_k=k, moe_router="sigmoid", activation="silu_glu",
                             dtype=jnp.float32,
                             **kw)


@pytest.mark.parametrize("share", [None, {"size": 8, "rank": 1}], ids=["whole", "a-chips-share"])
def test_experts_hands_the_kernel_the_stack_where_the_scan_does(monkeypatch, share):
    """``_experts`` on an :class:`ExpertStack`, on the chip (as the registry
    reports it here), is the kernel; off it, and on a layer's own leaves, the
    dense form: the same sum. A chip's share (rank 1 of 8: experts 8..15 of 64)
    leaves out the picks of other chips' experts: weight 0, not in ``ids``."""
    T, (_, E, M, H, k) = 8, TOY  # under 2E rows: the decode regime
    cfg = _cfg(E, M, H, k) if share is None else _cfg(E, M, H, k, expert_parallel=share)
    assert cfg.num_experts == E and cfg.router_experts == (E if share is None else 8 * E)
    w_gate, w_up, w_down = _weights(E, M, H, jnp.float32)
    stack = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    x = jax.random.normal(jax.random.PRNGKey(2), (T, M))
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    top_i = jnp.argsort(jax.random.uniform(keys[0], (T, cfg.router_experts)), axis=1)[:, :k].astype(jnp.int32)
    if share is not None:  # rows 0-3 pick a held expert and another chip's, rows 4-7 others' alone
        top_i = jnp.where((top_i >= cfg.first_expert) & (top_i < cfg.first_expert + E), 0, top_i)
        top_i = top_i.at[:4, 0].set(cfg.first_expert + jnp.arange(4) % 3)
    top_p = jax.nn.softmax(jax.random.normal(keys[1], (T, k)))
    calls = []
    kernel = registry.dispatch("moe_decode", "pallas")
    monkeypatch.setitem(registry._REGISTRY["moe_decode"], "pallas",
                        lambda *a, **kw: calls.append(moe_decode.touched_experts(a[1])[1]) or kernel(*a, **kw))
    dense = model._experts(jax.tree_util.tree_map(lambda a: a[1], stack), cfg, x, top_p, top_i)
    off_chip = model._experts(model.ExpertStack(stack, jnp.int32(1)), cfg, x, top_p, top_i)
    assert not calls
    np.testing.assert_array_equal(np.asarray(off_chip), np.asarray(dense))  # the layer's slice, the same program
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    on_chip = model._experts(model.ExpertStack(stack, jnp.int32(1)), cfg, x, top_p, top_i)
    assert len(calls) == 1
    if share is not None:
        assert int(calls[0][0]) == 3  # the three held experts rows 0-3 were sent to, of 8 x 2 picks
    _close(on_chip, dense, 1e-5)
    # a quantized or differently typed stack goes a layer's slice at a time through the dense form
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    model._experts(model.ExpertStack(stack, jnp.int32(1)), half, x, top_p, top_i)
    assert len(calls) == 1
