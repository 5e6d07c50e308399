"""Weights drawn from the seed, on the device, in one jitted call.

The benchmark makes the weights itself, so the reference never takes
anything the program made.  Each family's ``draw`` builds them from the
draws here: matrices are normal with variance 1/fan-in, the embedding
(and the untied head) with variance 1/d_model, so logits have a spread
near 1 at any width; norm scales are 1 + 0.1·N so a forgotten scale
shows.  Layers are drawn one at a time inside the call (``lax.map``), and
the vocabulary tables in row blocks, so no full-size float32 copy of a
weight exists beside the result in the served dtype.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness.arch import Arch, family_of

Weights = Dict[str, object]


def seed_key(seed: int):
    """A PRNG key from any whole number (more than 32 bits allowed)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _row_blocks(rows: int) -> int:
    for nb in (128, 64, 32, 16, 8, 4, 2):
        if rows % nb == 0:
            return nb
    return 1


def normal(key, shape, std: float, dt):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


def scale(key, n: int, dt):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(dt)


def table(key, rows: int, cols: int, std: float, dt):
    """A (rows, cols) normal table drawn in row blocks."""
    nb = _row_blocks(rows)
    blocks = jax.lax.map(lambda kk: normal(kk, (rows // nb, cols), std, dt),
                         jax.random.split(key, nb))
    return blocks.reshape(rows, cols)


def draw_model(a: Arch, key, layers: Callable) -> Weights:
    """The embedding, the final norm, the head where it is not tied, and
    under ``"layers"`` what ``layers(key)`` draws; call it inside the
    family's jitted ``draw``."""
    dt = jnp.dtype(a.dtype)
    k = jax.random.split(key, 4)
    std = a.d_model ** -0.5
    w = {"embed": table(k[0], a.vocab, a.d_model, std, dt),
         "final_norm": scale(k[1], a.d_model, dt),
         "layers": layers(k[2])}
    if not a.tied:
        w["unembed"] = table(k[3], a.d_model, a.vocab, std, dt)
    return w


def make_weights(a: Arch, seed: int) -> Weights:
    """All weights of ``a`` in its served dtype, drawn on the default
    device by one compiled program."""
    return family_of(a).draw(a, seed_key(seed))
