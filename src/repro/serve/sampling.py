"""The serving scheduler's sampler, run on the device.

Every token :class:`repro.serve.scheduler.Scheduler` emits is drawn by
:func:`sample_rows` from logits that stay on the device; only the int32
ids cross to the host.  Greedy rows (``temperature <= 0``) take the
argmax of the float32 logits.  Sampled rows take the Gumbel-max draw
``argmax(logits / T + g)`` over the full vocabulary, with float32
Gumbel noise ``g`` from a key derived from the row's seed and the
token's position in the request's output stream alone
(``ntok_base + ntok``, plus ``t`` inside a speculative block).

A token therefore depends only on its row's logits, temperature, seed
and position: never on the batch size, the slot, the ragged decode
group or the speculative depth.  That is what keeps speculative
decoding token-identical to target-only decoding at any temperature, a
journal-resumed request drawing what the uninterrupted one drew, and
the serving mesh emitting what one device emits.

The sampler holds no sharding constraint, so the one jit serves the
single-device and the mesh sessions alike.
"""
from __future__ import annotations

import operator
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> Tuple[int, int]:
    """A seed in ``[0, 2**64)`` as its (low, high) 32-bit words: every
    bit reaches the key, so seeds that share their low 32 bits draw
    different streams.  Raises ``ValueError`` outside that range and
    ``TypeError`` for a non-integer."""
    s = operator.index(seed)
    if not 0 <= s < 1 << 64:
        raise ValueError(f"sampling seed {seed!r} is outside [0, 2**64)")
    return s & 0xFFFFFFFF, s >> 32


def _noise(words, position, vocab: int):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    key = jax.random.fold_in(key, position)
    return jax.random.gumbel(key, (vocab,), jnp.float32)


@jax.jit
def _draw(logits, temperature, seed, position):
    n, V = position.size, logits.shape[-1]
    k = n // temperature.shape[0]
    x = logits.astype(jnp.float32).reshape(n, V)
    t = jnp.repeat(temperature, k)
    g = jax.vmap(_noise, (0, 0, None))(jnp.repeat(seed, k, axis=0),
                                       position.reshape(n), V)
    hot = t > 0
    drawn = jnp.argmax(x / jnp.where(hot, t, 1.0)[:, None] + g, axis=-1)
    ids = jnp.where(hot, drawn, jnp.argmax(x, axis=-1))
    return ids.astype(jnp.int32).reshape(position.shape)


def sample_rows(logits: jax.Array, temperature: Sequence[float],
                seed: Sequence[int], position) -> jax.Array:
    """Draw token ids on the device.

    logits: (B, K, V) or (B, V) on the device, any float dtype (drawn
    in float32); a single row may also come as (V,).  temperature: (B,)
    per row, ``<= 0`` for greedy.  seed: (B,) ints in ``[0, 2**64)``
    (ignored on greedy rows).  position: (B, K) or (B,) stream
    positions, one per logits row.  Returns int32 ids shaped like
    ``position``, still on the device.
    """
    t = np.asarray(temperature, np.float32)
    words = np.asarray([seed_words(s) for s in seed],
                       np.uint32).reshape(len(t), 2)
    return _draw(logits, t, words, np.asarray(position, np.int32))
