"""What a scanned layer saves for its backward (since PR 38): the stacked
outputs of the forward ``scan`` in ``jax.make_jaxpr(jax.grad(loss))``, which
the backward ``scan`` reads back, counted by dtype and trailing shape. bf16
activations AND bf16 parameters, as ``runtime/engine.py::_compute_params``
hands them to the loss; XLA attention (the flash kernels' residuals are
theirs). ``models/transformer.py``'s rule: a layer keeps what a product or a
kernel made and the inputs they need; what an elementwise function computed
inside itself (a norm's fp32 copy, centred and normalised value; an
activation's derivative) is made again in the backward.

The SAME census of the parent (commit 782de13, PR 37; ``B, S, h`` = 2, 32, 64):

| config | arrays a layer | ``[B,S,4h]`` | f32 ``[B,S,h]`` | bytes a layer |
|---|---|---|---|---|
| pythia (layernorm, ``gelu_exact``, parallel residual) | 30 | 4 | 6 | 348,672 |
| pythia, sequential residual | 30 | 4 | 6 | 348,672 |
| llama-like (rmsnorm, ``silu_glu``, grouped queries) | 28 | 6 | 6 | 400,896 |
| ``remat=True`` (either) | 2 | 0 | 0 | 8,448 |

and now 13, 14, 15 and 2 arrays with 2, 2, 3 and 0 of ``[B,S,4h]`` and no f32
``[B,S,h]`` (the bytes pinned below; at this toy size XLA attention's own
``[S,S]`` scores are most of what is left). At Pythia-410M's
widths (2 x 2048 x 1024) the first row is 252 MB a layer-step and now 92.
"""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import CausalLM, TransformerConfig

B, S, H = 2, 32, 64
PYTHIA = TransformerConfig(
    vocab_size=256, hidden_size=H, intermediate_size=4 * H, num_layers=3, num_heads=4, max_seq_len=S,
    norm="layernorm", activation="gelu_exact", parallel_block=True, parallel_mlp_norm=True, rotary_dim=4,
    rope_theta=10000.0, attn_impl="xla", dtype=jnp.bfloat16)
LLAMA = TransformerConfig(
    vocab_size=256, hidden_size=H, intermediate_size=4 * H, num_layers=3, num_heads=4, num_kv_heads=2,
    max_seq_len=S, attn_impl="xla", dtype=jnp.bfloat16)
# (config, most arrays of [B,S,4h], most bytes a layer): the bytes are this PR's reading, to the byte
CASES = {
    "pythia": (PYTHIA, 2, 190_464),
    "pythia_sequential": (dataclasses.replace(PYTHIA, parallel_block=False, parallel_mlp_norm=False), 2, 198_656),
    "llama": (LLAMA, 3, 219_136),
    "pythia_remat": (dataclasses.replace(PYTHIA, remat=True), 0, 8_448),
    "llama_remat": (dataclasses.replace(LLAMA, remat=True), 0, 8_448),
}


def _scans(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                _scans(inner, found)
    return found


def scan_residuals(cfg, batch, seq):
    """{(dtype, shape of one layer's slice): how many} over the stacked
    outputs of the forward layer scan of ``grad(loss)``."""
    model = CausalLM(cfg)
    data = {"input_ids": jnp.zeros((batch, seq), jnp.int32)}
    params = jax.eval_shape(lambda key: model.init({"params": key}, data, train=False)["params"],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, cfg.dtype), params)
    grad = jax.make_jaxpr(jax.grad(lambda p: model.apply({"params": p}, data, train=True)[0]))(params)
    forward = _scans(grad.jaxpr, [])[0]  # the backward's comes after it
    assert forward.params["length"] == cfg.num_layers
    stacked = forward.outvars[forward.params["num_carry"]:]
    return collections.Counter((str(v.aval.dtype), tuple(v.aval.shape[1:])) for v in stacked)


def bytes_a_layer(census):
    return sum(n * int(np.prod(shape)) * jnp.dtype(dtype).itemsize for (dtype, shape), n in census.items())


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_layer_saves_no_inside_of_an_elementwise_function(case):
    cfg, most_wide, most_bytes = CASES[case]
    census = scan_residuals(cfg, B, S)
    wide = sum(n for (_, shape), n in census.items() if shape == (B, S, cfg.intermediate_size))
    assert wide <= most_wide, census
    assert census[("float32", (B, S, cfg.hidden_size))] == 0, census
    assert bytes_a_layer(census) <= most_bytes, (bytes_a_layer(census), census)


def test_the_pythia_layer_keeps_thirteen_arrays():
    """The issue's own count: 30 arrays a layer became 13, TWO of ``[B,S,4h]``
    (``w_up``'s output and ``w_down``'s input) and THREE bf16 ``[B,S,h]``
    (the carry that both norms read, and each norm's output, which the
    products after it read)."""
    census = scan_residuals(PYTHIA, B, S)
    assert sum(census.values()) == 13, census
    assert census[("bfloat16", (B, S, 4 * H))] == 2
    assert census[("bfloat16", (B, S, H))] == 3
