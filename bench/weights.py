"""Random weights of a decoder, made from the run's seed.

The benchmark makes the weights itself, so that the plain reference
(``bench/reference.py``) and the program under test start from the same
numbers without the reference taking anything the program made.  The
program gets them through the configuration's architecture module
(``bench/arch/<arch>.py:program_params``), which maps this layout onto
its own parameter tree.

Layout (one dict; ``layers`` is a list):

    embed       (vocab, d)       tied token embedding and readout
    final_norm  (d,)
    layers[i]   the architecture module's ``layer_params``

The embedding is N(0, 0.02^2) from ``fold_in(key, 0)``, the final norm's
scale 1.  Every layer's leaves come from ``fold_in(key, 1 + i)``, so one
layer can be made again alone, bit for bit, without the others; one
compiled program makes any layer, so set-up compiles it once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import arch


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed: its two 32-bit halves are folded
    in one after the other, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def embedding(cfg: dict, key: jax.Array, dtype) -> jax.Array:
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return (jax.random.normal(jax.random.fold_in(key, 0), shape,
                              jnp.float32) * 0.02).astype(dtype)


def make(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights, on the default device: one compiled program makes a
    layer (called once per layer), one the embedding."""
    key = root_key(seed)
    mod = arch.of(cfg)
    layer = jax.jit(lambda key, i: mod.layer_params(cfg, key, i, dtype))
    return {"embed": jax.jit(lambda key: embedding(cfg, key, dtype))(key),
            "final_norm": jnp.ones((cfg["hidden_size"],), jnp.float32),
            "layers": [layer(key, i)
                       for i in range(cfg["num_hidden_layers"])]}
