"""Plain float32 forward pass of a decoder.

Independent of the program: it reads the configuration file's Hugging
Face keys and makes its weights from the seed through ``bench/weights.py``
and the configuration's architecture module (the same bits the program
was given), one layer at a time, so that granite-8b's layers never have
to sit in float32 all at once.

Shared by every architecture: token embedding; each layer as the module's
``reference_layer`` computes it; final RMSNorm (scale 1, as
``bench/weights.py`` makes it); logits = h @ embed^T.  ``_rms`` and
``_rope`` are the helpers the architecture modules import.  Every matrix
product runs at ``highest`` precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch
from bench import weights as W


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, base):
    """x (S, H, D) at positions pos (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def gaps(cfg: dict, seed: int, seqs: list, starts: list, *, rows: int,
         length: int, dtype=jnp.bfloat16) -> list:
    """For each sequence (prompt + served tokens) and the index where its
    served tokens start: per served token, how far the reference's logit
    of that token lies below the reference's best logit at that position.

    The sequences are padded to ``rows`` x ``length`` (the cell's largest
    sample and sequence), so every run of a cell compiles the same
    programs.  ``dtype`` is the type the weights were made in for the
    program; the reference computes with exactly those values, in
    float32."""
    key = W.root_key(seed)
    mod = arch.of(cfg)
    pad = -(-length // 128) * 128
    if len(seqs) > rows or max(len(t) for t in seqs) > pad:
        raise ValueError("sample larger than the padded reference batch")
    toks = np.zeros((rows, pad), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t

    @jax.jit
    def make(key, i):
        return jax.tree.map(lambda a: a.astype(jnp.float32),
                            mod.layer_params(cfg, key, i, dtype))

    @jax.jit
    def layer(lw, x):
        return jax.lax.map(lambda xi: mod.reference_layer(cfg, lw, xi), x)

    @jax.jit
    def gap_row(emb, h, nxt):
        logits = _rms(h, 1.0, cfg["rms_norm_eps"]) @ emb.T
        picked = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
        return logits.max(-1) - picked

    with jax.default_matmul_precision("highest"):
        emb = W.embedding(cfg, key, dtype).astype(jnp.float32)
        x = jnp.take(emb, jnp.asarray(toks), axis=0)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(make(key, i), x)
        out = []
        for i, (t, st) in enumerate(zip(seqs, starts)):
            # logits at position j predict token j + 1
            nxt = np.zeros((pad,), np.int32)
            nxt[:len(t) - 1] = t[1:]
            g = np.asarray(gap_row(emb, x[i], jnp.asarray(nxt)))
            out.append(g[st - 1:len(t) - 1])
    return out
