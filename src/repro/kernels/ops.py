"""jit'd public wrappers around the Pallas kernels.

Interpret mode follows the backend and nothing else: on a TPU the
kernels compile through Mosaic; on any other backend (the CPU test runs)
they execute in ``interpret=True`` mode (Python emulation of the kernel
body), validated against the pure-jnp oracles in ref.py.  There is no
override, so a TPU run can never emulate its kernels by accident.

``fake_quant`` carries the STE custom_vjp (paper eqs. 16-19): forward runs
the fused Pallas kernel, backward computes
  dx     = 1{|x| <= T_adj}                                  (round STE + clip)
  dalpha = d/dalpha [ q(x; alpha) ]  via the threshold scale
in plain jnp (the backward is bandwidth-trivial relative to the matmuls
around it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import expert_matmul as _em
from repro.kernels import fake_quant as _fq
from repro.kernels import prefill_attention as _pa
from repro.kernels import quant_matmul as _qm
from repro.kernels import ref as _ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def quant_matmul(x, w_q, w_scale, act_scale, **kw):
    """Fused quantize -> int8 matmul -> dequant (serving hot path).

    x: (M, K) raw bf16/f32 activations; w_q: (K, N) int8; w_scale: (N,)
    combined dequant scale (already folds 1/act_scale); act_scale: scalar
    quantization scale levels/T_adj applied to x inside the kernel.
    act_scale is mandatory — quantizing raw activations with an implicit
    scale of 1 silently clips them to small ints.
    """
    return _qm.quant_matmul(x, w_q, w_scale, act_scale,
                            interpret=_interpret(), **kw)


quant_matmul_ref = _ref.quant_matmul_ref


def expert_matmul(x, w_q, w_scale, act_scale, tile_expert, n_live, **kw):
    """Grouped int8 expert matmul (dropless MoE serving): rows grouped by
    expert in ``block_m``-row tiles (``repro.models.moe.group_rows``),
    each tile through its expert's int8 weights with per-expert static
    activation scales.  x: (M, K); w_q: (E, K, N) int8; w_scale: (E, N);
    act_scale: (E,); tile_expert: (M / block_m,); n_live: tiles in use."""
    return _em.expert_matmul(x, w_q, w_scale, act_scale, tile_expert,
                             n_live, interpret=_interpret(), **kw)


expert_matmul_ref = _ref.expert_matmul_ref


def decode_attention(q, k_cache, v_cache, k_scale, v_scale, cur_pos, **kw):
    """Fused one-token flash-decode over the int8 KV cache.

    q: (B, KV, G, D); k/v_cache: (B, S, KV, D) int8 with per-head dequant
    scales (KV,) — the serving decode hot path.  A bf16 cache runs through
    the same kernel with scales of ones.
    """
    return _da.decode_attention_int8(q, k_cache, v_cache, k_scale, v_scale,
                                     cur_pos, interpret=_interpret(), **kw)


decode_attention_ref = _ref.decode_attention_ref


def decode_attention_view(q, view, k_scale, v_scale, cur_pos, **kw):
    """Fused decode over a cache's ``KernelView`` (repro.cache): a dense/
    ring view (``block_table is None``) routes through the identity-table
    entry point, a paged view streams its page pool through the same
    kernel body via the block table.  ``view.bits`` picks the dequant
    epilogue (int4 views unpack nibbles in-kernel)."""
    if view.block_table is None:
        return _da.decode_attention_int8(
            q, view.k, view.v, k_scale, v_scale, cur_pos,
            interpret=_interpret(), kv_bits=view.bits, **kw)
    return _da.decode_attention_tiles(
        q, view.k, view.v, view.block_table, k_scale, v_scale, cur_pos,
        interpret=_interpret(), kv_bits=view.bits, **kw)


def decode_attention_partials(q, k_cache, v_cache, k_scale, v_scale,
                              cur_pos, **kw):
    """Partial-softmax flash-decode over ONE shard's dense cache slice
    (sequence-parallel serving).  Same inputs as ``decode_attention`` but
    ``cur_pos`` counts the LOCAL visible slots and the return is the raw
    flash state ``(acc, m, l)`` — acc (B, KV, G, D) unnormalized (value-
    dequantized), m/l (B, KV, G) — for the cross-shard merge in
    ``repro.shard.partial_softmax.sp_partial_combine``."""
    return _da.decode_attention_partials(
        q, k_cache, v_cache, k_scale, v_scale, cur_pos,
        interpret=_interpret(), **kw)


def decode_attention_partials_view(q, view, k_scale, v_scale, cur_pos,
                                   **kw):
    """Partials variant of ``decode_attention_view``: same dense-vs-paged
    (and ``view.bits``) routing, raw ``(acc, m, l)`` flash state out."""
    if view.block_table is None:
        return _da.decode_attention_partials(
            q, view.k, view.v, k_scale, v_scale, cur_pos,
            interpret=_interpret(), kv_bits=view.bits, **kw)
    return _da.decode_attention_partials_tiles(
        q, view.k, view.v, view.block_table, k_scale, v_scale, cur_pos,
        interpret=_interpret(), kv_bits=view.bits, **kw)


def prefill_attention(q, k, v, k_scale, v_scale, q_start, kv_len, **kw):
    """Fused flash-prefill over an int8 (or unit-scale float) KV stream.

    q: (B, Sq, KV, G, D); k/v: (B, Sk, KV, D) int8 with per-head dequant
    scales (KV,) — the serving prefill hot path.  ``q_start`` offsets
    query positions (chunked prefill); ``kv_len`` (B,) masks each
    request's valid KV slots (ragged prompt lengths).  Causal and
    sliding-window masks skip fully-dead tiles at block level.
    """
    return _pa.prefill_attention_int8(q, k, v, k_scale, v_scale, q_start,
                                      kv_len, interpret=_interpret(), **kw)


prefill_attention_ref = _ref.prefill_attention_ref


def prefill_attention_view(q, view, k_scale, v_scale, q_start, kv_len,
                           **kw):
    """Fused prefill over a cache's ``KernelView`` (repro.cache); same
    dense-vs-paged (and ``view.bits``) routing as
    ``decode_attention_view``."""
    if view.block_table is None:
        return _pa.prefill_attention_int8(
            q, view.k, view.v, k_scale, v_scale, q_start, kv_len,
            interpret=_interpret(), kv_bits=view.bits, **kw)
    return _pa.prefill_attention_tiles(
        q, view.k, view.v, view.block_table, k_scale, v_scale, q_start,
        kv_len, interpret=_interpret(), kv_bits=view.bits, **kw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fake_quant(x, t_max, alpha, levels=127.0, alpha_min=0.5, alpha_max=1.0):
    """Fused per-channel fake-quant with STE backward.

    x: (M, N); t_max/alpha: (N,) per-out-channel (paper vector mode).
    """
    return _fq.fake_quant_fwd(
        x, t_max, alpha, levels=levels, qmin=-levels, qmax=levels,
        alpha_min=alpha_min, alpha_max=alpha_max, interpret=_interpret(),
    )


def _fq_fwd(x, t_max, alpha, levels, alpha_min, alpha_max):
    y = fake_quant(x, t_max, alpha, levels, alpha_min, alpha_max)
    return y, (x, t_max, alpha)


def _fq_bwd(levels, alpha_min, alpha_max, res, g):
    x, t_max, alpha = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    a = jnp.clip(alpha.astype(jnp.float32), alpha_min, alpha_max)
    t_adj = jnp.maximum(a * t_max.astype(jnp.float32), 1e-8)
    s = levels / t_adj  # (N,)
    inside = (jnp.abs(xf) <= t_adj[None, :]).astype(jnp.float32)
    # STE: straight-through inside the clip range (eqs. 17, 19)
    dx = (gf * inside).astype(x.dtype)
    # d y / d t_adj:
    #   inside:  y = round(x s)/s -> dy/dt = (round(xs) - xs)/ (s t) * ...
    #            == (y - x)/t_adj   (rounding residual shrinks/grows with T)
    #   outside: y = sign(x) * levels / s = sign(x) * t_adj -> dy/dt = sign(x)
    y = _ref.fake_quant_ref(x, t_max, alpha, levels=levels, qmin=-levels,
                            qmax=levels, alpha_min=alpha_min,
                            alpha_max=alpha_max).astype(jnp.float32)
    dy_dt = jnp.where(inside > 0, (y - xf) / t_adj[None, :], jnp.sign(xf))
    # alpha gradient only inside the clip(alpha) passthrough band (eq. 19)
    pass_band = ((alpha >= alpha_min) & (alpha <= alpha_max)).astype(jnp.float32)
    dalpha = jnp.sum(gf * dy_dt, axis=0) * t_max.astype(jnp.float32) * pass_band
    # t_max is calibration data, not trained — zero cotangent
    return dx, jnp.zeros_like(t_max), dalpha.astype(alpha.dtype)


fake_quant.defvjp(_fq_fwd, _fq_bwd)

fake_quant_ref = _ref.fake_quant_ref
