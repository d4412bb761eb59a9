"""Common layers: norms, embeddings, rotary position encoding.

Norm layers carry their scale explicitly so the FAT folding pass
(repro.core.folding) can fold gamma/beta into downstream projections the
way the paper folds batch-norm into conv weights (§3.1.2, eqs. 10-11).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.module import Module, normal_init


class RMSNorm(Module):
    def __init__(self, dim: int, *, path: str, eps: float = 1e-6, dtype=jnp.bfloat16):
        self.dim = dim
        self.path = path
        self.eps = eps
        self.dtype = dtype

    def init(self, key):
        return {"scale": jnp.ones((self.dim,), jnp.float32)}

    def __call__(self, params, x, ctx=None):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps) * params["scale"]
        return y.astype(x.dtype)


class LayerNorm(Module):
    def __init__(self, dim: int, *, path: str, eps: float = 1e-5, dtype=jnp.bfloat16):
        self.dim = dim
        self.path = path
        self.eps = eps
        self.dtype = dtype

    def init(self, key):
        return {
            "scale": jnp.ones((self.dim,), jnp.float32),
            "bias": jnp.zeros((self.dim,), jnp.float32),
        }

    def __call__(self, params, x, ctx=None):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + self.eps)
        y = y * params["scale"] + params["bias"]
        return y.astype(x.dtype)


class Embedding(Module):
    """Token embedding; vocab-sharded under TP.

    The table is padded to ``vocab_padded`` (multiple of 128) so the vocab
    axis shards evenly on any power-of-two mesh; padded rows are masked to
    -inf at readout.  Quantizable to int8 (per-row thresholds) on the
    serving path — an embedding gather is pure memory traffic, so int8
    halves its HBM bytes; this is the paper's per-filter vector
    quantization applied to rows.
    """

    def __init__(self, vocab: int, dim: int, *, path: str, dtype=jnp.bfloat16,
                 vocab_padded: int | None = None):
        self.vocab = vocab
        self.vocab_padded = vocab_padded or (-(-vocab // 128) * 128)
        self.dim = dim
        self.path = path
        self.dtype = dtype
        self.logical_axes = ("vocab", "embed")

    def init(self, key):
        return {
            "table": normal_init(key, (self.vocab_padded, self.dim), self.dtype)
        }

    def __call__(self, params, tokens, ctx=None):
        return jnp.take(params["table"], tokens, axis=0)

    def attend(self, params, x, ctx=None):
        """Tied-weight readout: (..., d) @ (d, Vp) -> logits (padded rows
        masked to a large negative so argmax/softmax never select them)."""
        logits = x @ params["table"].T
        if self.vocab_padded != self.vocab:
            pad_mask = jnp.arange(self.vocab_padded) >= self.vocab
            logits = jnp.where(pad_mask, jnp.asarray(-1e9, logits.dtype), logits)
        return logits


def yarn_inv_freq(head_dim: int, base: float, yarn) -> np.ndarray:
    """YaRN's (head_dim/2,) inverse frequencies, as transformers'
    ``_compute_yarn_parameters`` computes them with ``truncate`` true:
    dims that turn fewer than ``beta_slow`` times over ``original_max``
    positions are interpolated (divided by ``factor``), those that turn
    more than ``beta_fast`` times are kept, and a linear ramp blends the
    dims between."""
    half = head_dim // 2

    def dim_at(rotations):
        return (head_dim * math.log(yarn.original_max
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_at(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_at(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extrap = 1.0 / (base ** (np.arange(0, head_dim, 2, dtype=np.float32)
                             / head_dim))
    interp = extrap / yarn.factor
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (interp * ramp + extrap * (1.0 - ramp)).astype(np.float32)


def rotary_angles(positions: jax.Array, head_dim: int, base: float = 10000.0,
                  yarn=None):
    """(..., S) int positions -> (cos, sin) of shape (..., S, head_dim/2).
    ``yarn`` (a ``configs.base.Yarn``) swaps in YaRN's frequencies and
    scales cos and sin by its attention factor."""
    half = head_dim // 2
    if yarn is None:
        freqs = 1.0 / (base ** (np.arange(0, half, dtype=np.float32) / half))
    else:
        freqs = yarn_inv_freq(head_dim, base, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    m = jnp.float32(yarn.attention_factor)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D). cos/sin: (..., S, D/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # add head axis; rotate-half convention (llama family)
    c = cos[..., None, :]
    s = sin[..., None, :]
    y1 = x1 * c - x2 * s
    y2 = x2 * c + x1 * s
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x)


def relu6(x):
    """Bounded activation central to the paper's §3.3 analysis."""
    return jnp.clip(x, 0.0, 6.0)


ACTIVATIONS = {"silu": silu, "gelu": gelu, "relu": jax.nn.relu, "relu6": relu6}
