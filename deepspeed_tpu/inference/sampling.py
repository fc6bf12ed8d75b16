"""Token sampling for generation (greedy / temperature / top-k / top-p).

The reference delegates sampling to HF ``generate`` (its engines only guard it,
``inference/engine.py:583``); FastGen's serving layer (MII) samples outside the
engine. Here sampling compiles INTO the serving step programs: the v1 engine
jits it alongside its scan decode, and the v2 engine fuses it into both the
ragged prefill step and the K-step decode chain
(``paged.ragged_decode_chain``), so decode dispatches return int32 token ids
and the ``[rows, vocab]`` logits never leave the device. All knobs are static
(compile-time) arguments; the PRNG key is threaded through the step carry.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def greedy_tokens(logits: jax.Array) -> jax.Array:
    """logits [..., V] -> argmax ids (int32), any leading dims. The single
    definition of "the target's greedy choice" — shared by plain sampling
    and the speculative verify-and-accept step (``paged.
    ragged_spec_decode_chain``), so acceptance compares against exactly the
    tokens the plain chain would have emitted."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.named_scope("sample")  # names on-device sampling in a device trace
def sample_logits(
    logits: jax.Array,
    rng: jax.Array,
    *,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """logits [B, V] -> token ids [B] (int32)."""
    if not do_sample:
        return greedy_tokens(logits)

    logits = logits.astype(jnp.float32)
    if temperature != 1.0:
        logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (always keep 1)
        keep = cum - probs < top_p
        cutoff = jnp.where(keep, sorted_logits, jnp.inf).min(axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
