"""Inputs, keys and weights made from ``--seed``, on the device.

Every stream is a key derived from the seed: the same seed gives the same
weights, data and noise; steps fold their index into the stream's key, so
the rows of every step differ.  The generators are copies of the
repository's synthetic data (``repro.data.synthetic``), with the seed added:
class-conditional Gaussian blobs for images, and a noisy first-order Markov
chain for tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STREAMS = {"weights": 0, "data": 1, "noise": 2, "classes": 3}


def stream_key(seed: int, stream: str) -> jax.Array:
    """The key of one named stream of ``seed`` (any whole number below 2**63)."""
    if not 0 <= seed < 2**63:
        raise ValueError(f"seed {seed} is outside [0, 2**63)")
    return jax.random.fold_in(jax.random.PRNGKey(seed), STREAMS[stream])


def image_batch(
    data_key: jax.Array, class_key: jax.Array, step, *, batch: int, image: int,
    channels: int, n_classes: int,
) -> dict:
    """Class-conditional Gaussian blobs: prototypes fixed by the seed, a label
    and fresh pixel noise for every row of every step."""
    key = jax.random.fold_in(data_key, step)
    k1, k2 = jax.random.split(key)
    labels = jax.random.randint(k1, (batch,), 0, n_classes)
    protos = jax.random.normal(class_key, (n_classes, image, image, channels))
    x = protos[labels] + 0.5 * jax.random.normal(k2, (batch, image, image, channels))
    return {
        "image": x.astype(jnp.float32),
        "label": labels.astype(jnp.int32),
        "mask": jnp.ones((batch,), jnp.float32),
    }


def token_batch(
    data_key: jax.Array, step, *, batch: int, seq: int, vocab: int,
    markov_mult: int = 31, noise: float = 0.1,
) -> dict:
    """Token rows of a noisy Markov chain: next = (31 t + 7) mod V, replaced
    by a uniform draw with probability ``noise``; labels are the next token."""
    key = jax.random.fold_in(data_key, step)
    k1, k2, k3 = jax.random.split(key, 3)
    start = jax.random.randint(k1, (batch,), 0, vocab)
    flip = jax.random.bernoulli(k2, noise, (seq + 1, batch))
    rand = jax.random.randint(k3, (seq + 1, batch), 0, vocab)

    def next_token(tok, xs):
        nz, rnd = xs
        nxt = jnp.where(nz, rnd, (tok * markov_mult + 7) % vocab)
        return nxt, nxt

    _, rows = jax.lax.scan(next_token, start, (flip, rand))
    rows = rows.T  # (B, S+1)
    return {
        "tokens": rows[:, :-1].astype(jnp.int32),
        "labels": rows[:, 1:].astype(jnp.int32),
        "mask": jnp.ones((batch,), jnp.float32),
    }


def gaussian_noise_like(tree, key: jax.Array, std: float):
    """``std * N(0, I)`` for every leaf of ``tree``: leaf i draws from
    ``split(key, n_leaves)[i]`` in float32, in the tree's leaf order (the
    Gaussian mechanism as the privacy engine states it)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    noise = [std * jax.random.normal(k, x.shape, jnp.float32) for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, noise)
