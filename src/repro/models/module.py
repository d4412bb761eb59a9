"""Minimal functional module system (no flax dependency).

Modules are plain Python objects built from configs; parameters live in
nested-dict pytrees that mirror the module tree.  Every module exposes:

  * ``init(key) -> params``   — build the parameter pytree
  * ``__call__(params, ..., ctx=...)`` — pure forward

Quantization (the paper's contribution) threads through a ``QuantCtx``
(see ``repro.core.api``): every quantizable layer carries a stable string
``path`` used to key threshold state, calibration updates and sharding
rules.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def split(key: jax.Array, n: int) -> Sequence[jax.Array]:
    return jax.random.split(key, n)


class Module:
    """Base class; subclasses define ``init`` and ``__call__``.

    ``iter_quant_layers`` recursively yields every quantizable leaf layer
    (``Dense``/``ExpertDense``) so the quantization state, the int8
    conversion and the sharding rules can be built by traversal.
    """

    path: str = ""

    def init(self, key: jax.Array) -> dict:  # pragma: no cover - abstract
        raise NotImplementedError

    def param_children(self) -> dict:
        """Mapping param-tree key -> child Module.

        Default: attribute name == param key (the convention throughout
        models/).  Containers that init under computed keys (Stack's
        ``layer{i}``) override this.
        """
        return {
            k: v for k, v in self.__dict__.items() if isinstance(v, Module)
        }

    def walk_with_params(self, params: dict):
        """Yield (module, params_subtree) for self and all descendants."""
        yield self, params
        for key, child in self.param_children().items():
            if isinstance(params, dict) and key in params:
                yield from child.walk_with_params(params[key])

    def iter_quant_layers(self) -> Iterator["Module"]:
        seen = set()
        stack = [self]
        while stack:
            m = stack.pop()
            if id(m) in seen:
                continue
            seen.add(id(m))
            if isinstance(m, (Dense, ExpertDense)):
                yield m
            for v in m.__dict__.values():
                if isinstance(v, Module):
                    stack.append(v)
                elif isinstance(v, (list, tuple)):
                    stack.extend(x for x in v if isinstance(x, Module))

    def iter_modules(self) -> Iterator["Module"]:
        stack = [self]
        seen = set()
        while stack:
            m = stack.pop()
            if id(m) in seen:
                continue
            seen.add(id(m))
            yield m
            for v in m.__dict__.values():
                if isinstance(v, Module):
                    stack.append(v)
                elif isinstance(v, (list, tuple)):
                    stack.extend(x for x in v if isinstance(x, Module))


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def normal_init(key, shape, dtype, stddev=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


def fan_in_init(key, shape, dtype):
    """LeCun-normal over the penultimate (fan-in) axis."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Dense — the quantization unit of the framework
# ---------------------------------------------------------------------------


class Dense(Module):
    """y = x @ W (+ b), quantizable per the paper's scheme.

    Logical axis names (``logical_axes``) drive sharding rules, e.g.
    ("embed", "mlp") for an up-projection.  ``act_unsigned`` marks inputs
    known to be non-negative (post-ReLU family) so the unsigned integer
    range is used (paper eq. 9).
    """

    def __init__(
        self,
        in_dim: int,
        out_dim: int,
        *,
        path: str,
        bias: bool = False,
        dtype=jnp.bfloat16,
        quantize: bool = True,
        act_unsigned: bool = False,
        logical_axes: tuple[str, str] = ("in", "out"),
        init_fn: Callable = fan_in_init,
    ):
        self.in_dim = in_dim
        self.out_dim = out_dim
        self.path = path
        self.bias = bias
        self.dtype = dtype
        self.quantize = quantize
        self.act_unsigned = act_unsigned
        self.logical_axes = logical_axes
        self.init_fn = init_fn

    # number of output channels for per-channel ("vector") thresholds
    @property
    def channels(self) -> int:
        return self.out_dim

    def init(self, key: jax.Array) -> dict:
        p = {"w": self.init_fn(key, (self.in_dim, self.out_dim), self.dtype)}
        if self.bias:
            p["b"] = jnp.zeros((self.out_dim,), self.dtype)
        return p

    def __call__(self, params: dict, x: jax.Array, ctx=None) -> jax.Array:
        from repro.core import api  # local import to avoid cycle

        return api.dense_forward(self, params, x, ctx)


class ExpertDense(Module):
    """Batched expert weights (E, in, out) for MoE layers.

    Quantized in vector mode with per-(expert, out-channel) thresholds —
    the natural generalization of the paper's per-filter thresholds — and
    one activation threshold per expert.
    """

    def __init__(
        self,
        num_experts: int,
        in_dim: int,
        out_dim: int,
        *,
        path: str,
        dtype=jnp.bfloat16,
        quantize: bool = True,
        logical_axes: tuple[str, str, str] = ("expert", "in", "out"),
    ):
        self.num_experts = num_experts
        self.in_dim = in_dim
        self.out_dim = out_dim
        self.path = path
        self.dtype = dtype
        self.quantize = quantize
        self.act_unsigned = False
        self.logical_axes = logical_axes

    @property
    def channels(self) -> int:
        # flattened (expert, out) channel count for vector thresholds
        return self.num_experts * self.out_dim

    def init(self, key: jax.Array) -> dict:
        std = 1.0 / np.sqrt(self.in_dim)
        w = (
            jax.random.normal(
                key, (self.num_experts, self.in_dim, self.out_dim), jnp.float32
            )
            * std
        ).astype(self.dtype)
        return {"w": w}

    def __call__(self, params: dict, x: jax.Array, ctx=None, *,
                 groups=None) -> jax.Array:
        """x: (M, in) rows grouped by expert as ``groups`` lays them out
        (``repro.models.moe.group_rows``) -> (M, out); without ``groups``,
        the training forward's capacity layout (..., E, C, in) ->
        (..., E, C, out)."""
        from repro.core import api

        return api.expert_dense_forward(self, params, x, ctx, groups)
