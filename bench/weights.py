"""Random weights of a llama-arch decoder, made from the run's seed.

The benchmark makes the weights itself, so that the plain reference
(``bench/reference.py``) and the program under test start from the same
numbers without the reference taking anything the program made.  The
program gets them through ``bench/hooks.py``, which maps this layout onto
its own parameter tree.

Layout (one dict; ``layers`` is a list):

    embed       (vocab, d)       tied token embedding and readout
    final_norm  (d,)
    layers[i]   attn_norm (d,), wq (d, H*D), wk (d, KV*D), wv (d, KV*D),
                wo (H*D, d), mlp_norm (d,), w_gate (d, F), w_up (d, F),
                w_down (F, d)

Matrices are N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales 1:
the usual initialisation, so activations keep their scale through depth.
Every layer's leaves come from ``fold_in(key, 1 + i)``, so one layer can
be made again alone, bit for bit, without the others; one compiled
program makes any layer, so set-up compiles it once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed: its two 32-bit halves are folded
    in one after the other, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def matrix_shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def layer_params(cfg: dict, key: jax.Array, i, dtype) -> dict:
    """Layer ``i``'s leaves (traceable in ``i``)."""
    ks = jax.random.split(jax.random.fold_in(key, 1 + i), len(MATRICES))
    d = cfg["hidden_size"]
    out = {"attn_norm": jnp.ones((d,), jnp.float32),
           "mlp_norm": jnp.ones((d,), jnp.float32)}
    for k, (name, shape) in zip(ks, matrix_shapes(cfg).items()):
        std = shape[0] ** -0.5
        out[name] = (jax.random.normal(k, shape, jnp.float32) * std
                     ).astype(dtype)
    return out


def embedding(cfg: dict, key: jax.Array, dtype) -> jax.Array:
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return (jax.random.normal(jax.random.fold_in(key, 0), shape,
                              jnp.float32) * 0.02).astype(dtype)


def make(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights, on the default device: one compiled program makes a
    layer (called once per layer), one the embedding."""
    key = root_key(seed)
    layer = jax.jit(lambda key, i: layer_params(cfg, key, i, dtype))
    return {"embed": jax.jit(lambda key: embedding(cfg, key, dtype))(key),
            "final_norm": jnp.ones((cfg["hidden_size"],), jnp.float32),
            "layers": [layer(key, i)
                       for i in range(cfg["num_hidden_layers"])]}
