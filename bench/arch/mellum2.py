"""Mellum2: sparse experts on every layer, 3:1 sliding and full attention,
YaRN on the full layers, untied readout.

Per layer: RMSNorm -> GQA attention with rotary positions (rotate-half;
a sliding layer attends its last ``sliding_window`` keys with plain RoPE
at ``rope_theta``, a full layer attends every earlier key with YaRN) ->
add -> RMSNorm -> experts -> add.  The experts: a float32 router over
``num_experts``, softmax, the ``num_experts_per_tok`` largest,
renormalized (``norm_topk_prob``), each a SwiGLU of width
``moe_intermediate_size``; no shared expert.  Leaves of layer ``i``:

    attn_norm (d,), wq (d, H*D), wk (d, KV*D), wv (d, KV*D), wo (H*D, d),
    mlp_norm (d,), router (d, E), w_gate (E, d, F), w_up (E, d, F),
    w_down (E, F, d), is_global () float32: 1 on a full-attention layer

``is_global`` carries the layer's kind to ``reference_layer``, which is
not told the layer's index; the program gets no such leaf.  Matrices are
N(0, 1/fan_in), norm scales 1.  The readout's values are the embedding's
(``bench/weights.py`` and ``bench/reference.py`` make one table); the
program runs its untied readout with those values.  The contract every
such module keeps is in ``bench/arch/__init__.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import hooks
from bench.reference import _rms


def matrix_shapes(cfg: dict) -> dict:
    d, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "router": (d, e), "w_gate": (e, d, f),
            "w_up": (e, d, f), "w_down": (e, f, d)}


def kinds(cfg: dict) -> list:
    """1.0 for each full-attention layer of the stack, else 0.0."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [float(t == "full_attention") for t in types]


def layer_params(cfg: dict, key: jax.Array, i, dtype) -> dict:
    """Layer ``i``'s leaves (traceable in ``i``)."""
    shapes = matrix_shapes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, 1 + i), len(shapes))
    d = cfg["hidden_size"]
    out = {"attn_norm": jnp.ones((d,), jnp.float32),
           "mlp_norm": jnp.ones((d,), jnp.float32),
           "is_global": jnp.asarray(kinds(cfg), jnp.float32)[i]}
    for k, (name, shape) in zip(ks, shapes.items()):
        std = shape[-2] ** -0.5
        out[name] = (jax.random.normal(k, shape, jnp.float32) * std
                     ).astype(dtype)
    return out


def inv_freq(cfg: dict, kind: str) -> tuple:
    """(head_dim/2 inverse frequencies, cos/sin scale) of ``kind``'s
    ``rope_parameters`` entry: default RoPE, or YaRN as transformers'
    ``_compute_yarn_parameters`` computes it with ``truncate`` true."""
    p = cfg["rope_parameters"][kind]
    dim, base = cfg["head_dim"], float(p["rope_theta"])
    extrap = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if p["rope_type"] == "default":
        return extrap.astype(np.float32), 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")

    def dim_at(rotations):
        return (dim * math.log(p["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_at(p["beta_fast"])), 0)
    high = min(math.ceil(dim_at(p["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    freqs = extrap / p["factor"] * ramp + extrap * (1.0 - ramp)
    return freqs.astype(np.float32), float(p["attention_factor"])


def _rotate(x, pos, freqs, scale):
    """x (S, H, D) rotated (rotate-half) at positions pos (S,)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs)
    c = (jnp.cos(ang) * scale)[:, None, :]
    s = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def reference_layer(cfg: dict, lw: dict, x):
    """One layer over one sequence x (S, d), every expert computed and the
    unchosen weighted 0."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, s = cfg["rms_norm_eps"], x.shape[0]
    full = lw["is_global"] > 0.5
    pos = jnp.arange(s)
    a = _rms(x, lw["attn_norm"], eps)

    def rope(t):
        loc = _rotate(t, pos, *inv_freq(cfg, "sliding_attention"))
        glob = _rotate(t, pos, *inv_freq(cfg, "full_attention"))
        return jnp.where(full, glob, loc)

    q = rope((a @ lw["wq"]).reshape(s, h, hd))
    k = rope((a @ lw["wk"]).reshape(s, kv, hd))
    v = (a @ lw["wv"]).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    seen = pos[:, None] >= pos[None, :]
    seen &= full | (pos[:, None] - pos[None, :] < cfg["sliding_window"])
    scores = jnp.where(seen, scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + o.reshape(s, h * hd) @ lw["wo"]

    m = _rms(x, lw["mlp_norm"], eps)
    probs = jax.nn.softmax(m @ lw["router"], -1)                  # (S, E)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[pos[:, None], idx].set(top)   # (S, E)
    g = jnp.einsum("sd,edf->sef", m, lw["w_gate"])
    u = jnp.einsum("sd,edf->sef", m, lw["w_up"])
    hid = g * jax.nn.sigmoid(g) * u * gate[:, :, None]
    return x + jnp.einsum("sef,efd->sd", hid, lw["w_down"])


def program_config(cfg: dict):
    """The program's ``ModelConfig`` (Hugging Face key names)."""
    hooks.import_program()
    try:
        from repro.configs.base import ModelConfig, Yarn
    except ImportError as e:
        raise ValueError(f"the program has no YaRN rotary: {e}") from e

    n = cfg["num_hidden_layers"]
    if kinds(cfg) != [float(i % 4 == 3) for i in range(n)]:
        raise ValueError("the program serves a 3:1 sliding:full pattern "
                         "only")
    full = cfg["rope_parameters"]["full_attention"]
    local = cfg["rope_parameters"]["sliding_attention"]
    if (full["rope_theta"] != local["rope_theta"]
            or local["rope_type"] != "default" or full["rope_type"] != "yarn"
            or not cfg["norm_topk_prob"] or cfg["hidden_act"] != "silu"):
        raise ValueError("the program serves one RoPE base, YaRN on the "
                         "full layers, renormalized top-k and SiLU only")
    return ModelConfig(
        name=cfg["name"], n_layers=n, d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        ffn="moe", n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], local_global_ratio=(3, 1),
        window=cfg["sliding_window"], rope_base=float(local["rope_theta"]),
        global_yarn=Yarn(
            factor=float(full["factor"]),
            original_max=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        tie_embeddings=False)


def program_params(weights: dict) -> dict:
    """The weights of ``bench/weights.py:make`` as the program's parameter
    tree: the readout gets the embedding's values."""
    stack = {}
    for i, lw in enumerate(weights["layers"]):
        stack[f"layer{i}"] = {
            "pre_norm": {"scale": lw["attn_norm"]},
            "attn": {n: {"w": lw[n]} for n in ("wq", "wk", "wv", "wo")},
            "ffn_norm": {"scale": lw["mlp_norm"]},
            "ffn": {"router": {"w": lw["router"].astype(jnp.float32)},
                    "gate": {"w": lw["w_gate"]}, "up": {"w": lw["w_up"]},
                    "down": {"w": lw["w_down"]}},
        }
    stack["final_norm"] = {"scale": weights["final_norm"]}
    return {"embed": {"table": weights["embed"]}, "stack": stack,
            "lm_head": {"w": weights["embed"].T}}


def norm_count(cfg: dict) -> int:
    """Two norms a layer and the final one."""
    return 2 * cfg["num_hidden_layers"] + 1


def model_ops(cfg: dict) -> dict:
    """The matrix products active for a token (2 per weight): attention,
    the router and the ``num_experts_per_tok`` experts it picks; the
    readout; and per key attended the score and value products (4 *
    heads * head_dim per layer, every layer counted as full)."""
    d, f, v = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["vocab_size"])
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = (d * (h + 2 * kv) * hd + h * hd * d + d * cfg["num_experts"]
                 + cfg["num_experts_per_tok"] * 3 * d * f)
    return {"matmul": 2 * per_layer * cfg["num_hidden_layers"],
            "readout": 2 * d * v,
            "per_key": 4 * h * hd * cfg["num_hidden_layers"]}


def projections(cfg: dict) -> list:
    """The four attention projections, and the untied readout: the experts
    run ``expert_matmul`` (``bench/kernels/expert_matmul.py``), not
    ``quant_matmul``.  The readout (d, vocab) is a ``quant_matmul`` call
    once a decode step, where the shared count multiplies each entry by
    the layers, so it enters as one slice of vocab / layers columns: exact
    in operations and weight bytes, its activations read once a layer
    instead of once (0.9% of its bytes at the published widths).  An
    admission reads it for one row, not every chunk: the prefill counts
    would overstate it."""
    attn = [s for n, s in matrix_shapes(cfg).items()
            if n in ("wq", "wk", "wv", "wo")]
    n = cfg["num_hidden_layers"]
    v = cfg["vocab_size"]
    return attn + [(cfg["hidden_size"], v // n if v % n == 0 else v / n)]
