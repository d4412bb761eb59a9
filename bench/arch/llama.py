"""The llama-arch decoder: RMSNorm, GQA attention with rotary positions,
SwiGLU MLP, tied embedding.

Per layer: RMSNorm -> GQA attention with rotary positions (rotate-half,
base ``rope_theta``; query head h reads key/value head h // (H / KV)) ->
add -> RMSNorm -> SwiGLU MLP -> add.  Leaves of layer ``i``:

    attn_norm (d,), wq (d, H*D), wk (d, KV*D), wv (d, KV*D), wo (H*D, d),
    mlp_norm (d,), w_gate (d, F), w_up (d, F), w_down (F, d)

Matrices are N(0, 1/fan_in), norm scales 1: the usual initialisation, so
activations keep their scale through depth.  The contract every such
module keeps is in ``bench/arch/__init__.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import hooks
from bench.reference import _rms, _rope


def matrix_shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def layer_params(cfg: dict, key: jax.Array, i, dtype) -> dict:
    """Layer ``i``'s leaves (traceable in ``i``)."""
    shapes = matrix_shapes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, 1 + i), len(shapes))
    d = cfg["hidden_size"]
    out = {"attn_norm": jnp.ones((d,), jnp.float32),
           "mlp_norm": jnp.ones((d,), jnp.float32)}
    for k, (name, shape) in zip(ks, shapes.items()):
        std = shape[0] ** -0.5
        out[name] = (jax.random.normal(k, shape, jnp.float32) * std
                     ).astype(dtype)
    return out


def reference_layer(cfg: dict, lw: dict, x):
    """One decoder layer over one sequence x (S, d)."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, s = cfg["rms_norm_eps"], x.shape[0]
    pos = jnp.arange(s)
    a = _rms(x, lw["attn_norm"], eps)
    q = _rope((a @ lw["wq"]).reshape(s, h, hd), pos, cfg["rope_theta"])
    k = _rope((a @ lw["wk"]).reshape(s, kv, hd), pos, cfg["rope_theta"])
    v = (a @ lw["wv"]).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + o.reshape(s, h * hd) @ lw["wo"]
    m = _rms(x, lw["mlp_norm"], eps)
    g = m @ lw["w_gate"]
    return x + (g * jax.nn.sigmoid(g) * (m @ lw["w_up"])) @ lw["w_down"]


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file (Hugging
    Face key names)."""
    hooks.import_program()
    from repro.configs.base import ModelConfig

    if not cfg["tie_word_embeddings"]:
        raise ValueError("the program's llama-arch stack has tied "
                         "embeddings only")
    return ModelConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        tie_embeddings=True, rope_base=float(cfg["rope_theta"]))


def program_params(weights: dict) -> dict:
    """The weights of ``bench/weights.py:make`` as the program's parameter
    tree."""
    stack = {}
    for i, lw in enumerate(weights["layers"]):
        stack[f"layer{i}"] = {
            "pre_norm": {"scale": lw["attn_norm"]},
            "attn": {n: {"w": lw[n]} for n in ("wq", "wk", "wv", "wo")},
            "ffn_norm": {"scale": lw["mlp_norm"]},
            "ffn": {"gate": {"w": lw["w_gate"]}, "up": {"w": lw["w_up"]},
                    "down": {"w": lw["w_down"]}},
        }
    stack["final_norm"] = {"scale": weights["final_norm"]}
    return {"embed": {"table": weights["embed"]}, "stack": stack}


def norm_count(cfg: dict) -> int:
    """Two norms a layer and the final one."""
    return 2 * cfg["num_hidden_layers"] + 1


def model_ops(cfg: dict) -> dict:
    """The matrix products (2 per weight), and per key attended the score
    and value products (4 * heads * head_dim per layer)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f
    return {"matmul": 2 * per_layer * cfg["num_hidden_layers"],
            "readout": 2 * d * v,
            "per_key": 4 * h * hd * cfg["num_hidden_layers"]}


def projections(cfg: dict) -> list:
    """The seven projections of a layer, every matrix weight once."""
    return list(matrix_shapes(cfg).values())
