"""Attention: GQA/MQA with full, chunked-flash and sliding-window paths,
plus KV-cache decode (the int8 serving hot path).

Memory discipline matters at the assigned shapes (32k prefill, 512k
decode):

  * ``flash_attention`` — double-scan (query chunks x kv chunks) online
    softmax; peak score tensor is (B, Hq, q_chunk, kv_chunk).
  * ``sliding_window_attention`` — per-query-chunk dynamic slice of the
    last ``window`` keys: O(S * window) compute, which is what makes the
    gemma3/mixtral local layers sub-quadratic.
  * ``decode_attention`` — one-token query against the cache; with a
    sequence-sharded cache GSPMD turns the softmax reductions into the
    flash-decode partial-max/partial-sum combine automatically.

The KV cache is consumed ONLY through the ``repro.cache.KVCache``
protocol: ``init_cache`` picks a layout (dense / SWA ring / paged),
``cache.ready`` is the single quantize-on-append point (K/V quantize ONCE
against the frozen calibrated per-head thresholds — paper §2 — and the
same tiles feed attention and the cache write), ``append`` /
``append_slots`` do the layout-appropriate writes, and ``kernel_view`` /
``dense_view`` feed the fused Pallas kernels and the jnp reference paths
respectively.  This file contains no layout math: ring rolls live in
``RingCache``, page-table scatters in ``PagedCache``.

Serving runs a TWO-KERNEL fused engine when policy.use_pallas: prefill
attends through kernels/prefill_attention.py, decode through
kernels/decode_attention.py — both read KV tiles through the cache's
kernel view (an identity block table for dense/ring, the page table for
paged), so one compiled kernel serves every layout.  ``decode`` takes a
(B,) position vector + active mask for continuous batching
(launch/scheduler.py, docs/serving.md).

All paths share GQA head grouping: Hq = KV * G, computed as einsum over a
(B, S, KV, G, D) view so no materialized head replication occurs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.cache import (DenseCache, KVCache, KV_LEVELS, RingCache,
                         dequantize_kv, kv_levels, make_cache, quantize_kv)
from repro.models.layers import apply_rotary, rotary_angles
from repro.models.module import Dense, Module

NEG_INF = -1e30


def _sp_info():
    """Trace-time sequence-parallel context (repro.shard.context), or
    None on the unsharded path.  Imported lazily: repro.shard layers on
    top of the model stack, so a module-level import would be a cycle —
    and the unsharded engine should not pay for it at all."""
    try:
        from repro.shard.context import sp_shard_info
    except ImportError:
        return None
    return sp_shard_info()


def _gqa_scores(q, k):
    """q: (B,Sq,KV,G,D)  k: (B,Sk,KV,D) -> (B,KV,G,Sq,Sk)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k)


def _gqa_out(p, v):
    """p: (B,KV,G,Sq,Sk)  v: (B,Sk,KV,D) -> (B,Sq,KV,G,D)."""
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


def full_attention(q, k, v, *, causal: bool, q_pos=None, k_pos=None,
                   window: int | None = None):
    """Reference full-materialization attention (small shapes / oracle)."""
    b, sq, kvh, g, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    s = _gqa_scores(q.astype(jnp.float32) * scale, k.astype(jnp.float32))
    if q_pos is None:
        q_pos = jnp.arange(sq)
    if k_pos is None:
        k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 512, q_offset: int = 0,
                    window: int | None = None):
    """Online-softmax attention, scanned over query and kv chunks.

    q: (B, Sq, KV, G, D); k/v: (B, Sk, KV, D).  ``q_offset`` shifts query
    positions (decode / continued prefill).  Pure JAX (lax.scan) so it
    lowers on any backend and GSPMD shards it; the Pallas TPU kernel in
    kernels/ is the hardware hot path for the same contraction.
    """
    b, sq0, kvh, g, d = q.shape
    sk0 = k.shape[1]
    q_chunk = min(q_chunk, sq0)
    kv_chunk = min(kv_chunk, sk0)
    # pad ragged sequence lengths up to a chunk multiple; padded keys are
    # masked by position (k_pos < sk0), padded query rows are sliced off.
    sq = -(-sq0 // q_chunk) * q_chunk
    sk = -(-sk0 // kv_chunk) * kv_chunk
    if sq != sq0:
        q = jnp.pad(q, [(0, 0), (0, sq - sq0), (0, 0), (0, 0), (0, 0)])
    if sk != sk0:
        k = jnp.pad(k, [(0, 0), (0, sk - sk0), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, sk - sk0), (0, 0), (0, 0)])
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    def q_body(_, qi):
        # slice in the storage dtype, upcast per chunk — a full-tensor f32
        # copy of q/k/v would cost 2x HBM for the whole sequence
        qc = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        qc = qc.astype(jnp.float32) * scale
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        # checkpoint: the (cq, ck) score/prob tiles must be recomputed in
        # the backward, not saved — saving them stacks (nq*nk) f32 tiles
        # and destroys flash attention's O(S) memory property
        @jax.checkpoint
        def kv_body(carry, ki):
            o, m, l = carry
            kc = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, axis=1)
            kc = kc.astype(jnp.float32)
            vc = vc.astype(jnp.float32)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = _gqa_scores(qc, kc)  # (B,KV,G,cq,ck)
            mask = (k_pos < sk0)[None, :]  # mask padded keys
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vc)
            return (o_new, m_new, l_new), None

        o0 = jnp.zeros((b, kvh, g, q_chunk, d), jnp.float32)
        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        (o, m, l), _ = jax.lax.scan(kv_body, (o0, m0, l0), jnp.arange(nk))
        o = o / jnp.maximum(l[..., None], 1e-30)
        # emit in storage dtype: the stacked output is (Sq, ...)-sized
        return None, jnp.moveaxis(o, 3, 1).astype(v.dtype)  # (B,cq,KV,G,D)

    _, chunks = jax.lax.scan(q_body, None, jnp.arange(nq))
    # chunks: (nq, B, cq, KV, G, D) -> (B, Sq, KV, G, D)
    out = jnp.moveaxis(chunks, 0, 1).reshape(b, sq, kvh, g, d)
    return out[:, :sq0].astype(v.dtype)


def sliding_window_attention(q, k, v, *, window: int, q_chunk: int = 512,
                             q_offset: int = 0):
    """Banded causal attention in O(S * (window + q_chunk)) compute.

    Pads KV on the left by ``window`` then, per query chunk, slices the
    (window + q_chunk) keys that can possibly be visible.  All shapes are
    static so this lowers/shards cleanly.
    """
    b, sq0, kvh, g, d = q.shape
    sk0 = k.shape[1]
    q_chunk = min(q_chunk, sq0)
    sq = -(-sq0 // q_chunk) * q_chunk
    if sq != sq0:
        q = jnp.pad(q, [(0, 0), (0, sq - sq0), (0, 0), (0, 0), (0, 0)])
        k = jnp.pad(k, [(0, 0), (0, sq - sk0), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, sq - sk0), (0, 0), (0, 0)])
    nq = sq // q_chunk
    span = window + q_chunk  # kv visible to one query chunk
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    # left-pad keys/values by `window` so slices never underflow; stay in
    # storage dtype and upcast per chunk (full-tensor f32 doubles HBM)
    pad = [(0, 0), (window, 0), (0, 0), (0, 0)]
    kp = jnp.pad(k, pad)
    vp = jnp.pad(v, pad)

    @jax.checkpoint
    def q_body(_, qi):
        q_start = qi * q_chunk
        qc = jax.lax.dynamic_slice_in_dim(q, q_start, q_chunk, axis=1)
        qc = qc.astype(jnp.float32) * scale
        kc = jax.lax.dynamic_slice_in_dim(kp, q_start, span, axis=1).astype(
            jnp.float32)
        vc = jax.lax.dynamic_slice_in_dim(vp, q_start, span, axis=1).astype(
            jnp.float32)
        # absolute positions: queries q_start..q_start+cq-1 (+offset);
        # keys (q_start - window)..(q_start + cq - 1); padding keys have
        # negative positions and are masked out.
        q_pos = q_offset + q_start + jnp.arange(q_chunk)
        k_pos = q_offset + q_start - window + jnp.arange(span)
        s = _gqa_scores(qc, kc)
        mask = (
            (q_pos[:, None] >= k_pos[None, :])
            & ((q_pos[:, None] - k_pos[None, :]) < window)
            & (k_pos[None, :] >= 0)
            & (k_pos[None, :] < q_offset + sk0)
        )
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return None, _gqa_out(p, vc).astype(v.dtype)

    _, chunks = jax.lax.scan(q_body, None, jnp.arange(nq))
    out = jnp.moveaxis(chunks, 0, 1).reshape(b, sq, kvh, g, d)
    return out[:, :sq0]


def decode_attention(q, k_cache, v_cache, cur_pos, *, window: int | None = None):
    """One-step decode: q (B,1,KV,G,D) against cache (B,Smax,KV,D).

    ``cur_pos`` is the number of valid cache entries — a scalar (uniform
    batch) or a (B,) per-slot vector (continuous batching: each slot of
    the batch decodes at its own position; a 0 entry means no visible key
    and returns exact zeros, matching the fused kernel's inactive-slot
    convention).  Positions beyond it (and outside the sliding window, if
    any) are masked.  With a sequence-sharded cache, GSPMD lowers the
    masked softmax into partial reductions + a tiny cross-shard combine
    (flash-decode).
    """
    b, _, kvh, g, d = q.shape
    smax = k_cache.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = _gqa_scores(q.astype(jnp.float32) * scale, k_cache.astype(jnp.float32))
    pos = jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,))
    k_pos = jnp.arange(smax)
    mask = k_pos[None, :] < pos[:, None]                       # (B, Smax)
    if window is not None:
        mask &= k_pos[None, :] >= (pos[:, None] - window)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no visible key (pos == 0) softmax uniformly over NEG_INF
    # scores — zero them so inactive slots are well-defined
    p = p * (pos > 0)[:, None, None, None, None]
    return _gqa_out(p, v_cache.astype(jnp.float32)).astype(q.dtype)


def verify_attention(q, k_cache, v_cache, pos_vec, *, window: int | None = None):
    """Speculative-verify attention: s draft-window queries per slot, each
    slot at its own position.  q: (B, s, KV, G, D) — query j of slot b
    sits at absolute position ``pos_vec[b] + j`` and sees cache keys
    ``<= pos_vec[b] + j`` (the just-appended draft window included, causal
    within it).  The s == 1 case is exactly ``decode_attention`` with
    ``cur_pos = pos_vec + 1`` — same contraction order, same mask
    convention — which is what makes speculative decoding bit-identical
    to greedy under deterministic acceptance.  A slot with
    ``pos_vec < 0`` (the inactive-slot encoding) sees no key and returns
    exact zeros."""
    b, s, kvh, g, d = q.shape
    smax = k_cache.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    sc = _gqa_scores(q.astype(jnp.float32) * scale,
                     k_cache.astype(jnp.float32))      # (B, KV, G, s, Smax)
    pos = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32).reshape(-1), (b,))
    q_pos = pos[:, None] + jnp.arange(s)               # (B, s)
    k_pos = jnp.arange(smax)
    mask = k_pos[None, None, :] <= q_pos[..., None]    # (B, s, Smax)
    if window is not None:
        mask &= (q_pos[..., None] - k_pos[None, None, :]) < window
    mask &= (pos >= 0)[:, None, None]
    sc = jnp.where(mask[:, None, None, :, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = p * (pos >= 0)[:, None, None, None, None]
    return _gqa_out(p, v_cache.astype(jnp.float32)).astype(q.dtype)


class Attention(Module):
    """GQA attention block with rotary embedding and optional SWA."""

    def __init__(
        self,
        d_model: int,
        n_heads: int,
        n_kv_heads: int,
        head_dim: int,
        *,
        path: str,
        window: int | None = None,
        rope_base: float = 10000.0,
        yarn=None,
        causal: bool = True,
        cross: bool = False,
        dtype=jnp.bfloat16,
        q_chunk: int = 512,
        kv_chunk: int = 512,
    ):
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_kv = n_kv_heads
        self.head_dim = head_dim
        self.groups = n_heads // n_kv_heads
        self.window = window
        self.rope_base = rope_base
        self.yarn = yarn
        self.causal = causal
        self.cross = cross
        self.path = path
        self.q_chunk = q_chunk
        self.kv_chunk = kv_chunk
        dd = dict(dtype=dtype)
        self.wq = Dense(d_model, n_heads * head_dim, path=f"{path}/wq",
                        logical_axes=("embed", "heads"), **dd)
        self.wk = Dense(d_model, n_kv_heads * head_dim, path=f"{path}/wk",
                        logical_axes=("embed", "kv_heads"), **dd)
        self.wv = Dense(d_model, n_kv_heads * head_dim, path=f"{path}/wv",
                        logical_axes=("embed", "kv_heads"), **dd)
        self.wo = Dense(n_heads * head_dim, d_model, path=f"{path}/wo",
                        logical_axes=("heads", "embed"), **dd)

    def init(self, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "wq": self.wq.init(k1),
            "wk": self.wk.init(k2),
            "wv": self.wv.init(k3),
            "wo": self.wo.init(k4),
        }

    # -- cache ------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   kv_int8: bool = False, layout: str = "ring",
                   page_size: int = 64, extra_pages: int = 0,
                   kv_bits: int = 8) -> KVCache:
        """Build this layer's ``KVCache`` (repro.cache.make_cache picks
        dense / SWA-ring / paged from ``layout`` and the layer's window).
        ``kv_int8`` stores entries as int8 + per-head f32 dequant scales
        (half the bf16 HBM stream — the decode bandwidth win); scales
        start at 1 and are written from the calibrated thresholds during
        prefill.  ``kv_bits=4`` narrows the quantized store to packed
        int4 nibbles (quarter of bf16).  Cross-attention memory stays
        float and dense (computed once per request, not the decode
        bottleneck)."""
        if self.cross:
            return DenseCache.init(batch, max_len, self.n_kv, self.head_dim,
                                   dtype=dtype, quantized=False)
        return make_cache(batch, max_len, self.n_kv, self.head_dim,
                          dtype=dtype, quantized=kv_int8, layout=layout,
                          window=self.window, page_size=page_size,
                          extra_pages=extra_pages,
                          bits=kv_bits if kv_int8 else 8)

    def _observe_kv(self, ctx, k, v):
        """Feed post-rope K / raw V into the KV calibration observers
        (same §2 calibration pass that feeds activation observers)."""
        if ctx is None or ctx.mode != "calibrate":
            return
        from repro.core import api as A
        from repro.core import calibration as calib

        key = A.kv_path(self.path)
        if key not in ctx.qparams:
            return
        ent = ctx.qparams[key]
        spec = ctx.policy.kv_spec()
        kw = dict(kind=ctx.policy.observer, percentile=ctx.policy.percentile)
        ctx.updates[key] = {
            "k": calib.update_observer(ent["k"], k, spec, **kw),
            "v": calib.update_observer(ent["v"], v, spec, **kw),
        }

    def _fake_quant_kv(self, ctx, k, v):
        """Trained-threshold fake-quant of the KV stream (paper §3 applied
        to the cache): fires in fake mode ONLY when finalize_calibration
        emitted trainable ``log2_t`` leaves, so the static-threshold
        training path is bit-identical to before.  This is the
        differentiable stand-in for ``cache.ready`` — the distill loss
        sees exactly the quantization error serving will pay, and the TQT
        backward moves the per-head thresholds."""
        if ctx is None or ctx.mode != "fake":
            return k, v
        from repro.core import api as A
        from repro.core import quant as Q

        ent = ctx.qparams.get(A.kv_path(self.path))
        if not ent or "log2_t" not in ent.get("k", {}):
            return k, v
        spec = ctx.policy.kv_spec()
        return (Q.fake_quant_log_t(k, ent["k"]["log2_t"], spec),
                Q.fake_quant_log_t(v, ent["v"]["log2_t"], spec))

    def _kv_scales(self, ctx) -> tuple[jax.Array, jax.Array]:
        """Frozen per-head dequant scales T/levels from calibrated
        qparams (levels = 127 at kv_bits=8, 7 at kv_bits=4)."""
        from repro.core import api as A

        ent = None if ctx is None else ctx.qparams.get(A.kv_path(self.path))
        # raw observer states also carry 't_max' (as running stats, zeros
        # before any batch) — only a finalized entry (observer fields
        # stripped) is a usable threshold
        finalized = (ent is not None and "t_max" in ent.get("k", {})
                     and "count" not in ent["k"])
        if not finalized:
            raise ValueError(
                f"{self.path}: int8 KV cache requires calibrated+finalized "
                "kv thresholds in qparams (QuantPolicy(kv_int8=True) at "
                "init_qparams, the calibration pass, then "
                "finalize_calibration)"
            )
        levels = kv_levels(ctx.policy.kv_bits)
        k_s = (jnp.maximum(ent["k"]["t_max"], 1e-8) / levels)
        v_s = (jnp.maximum(ent["v"]["t_max"], 1e-8) / levels)
        return k_s.astype(jnp.float32), v_s.astype(jnp.float32)

    def _qkv(self, params, x, ctx, kv_src=None):
        b, s, _ = x.shape
        q = self.wq(params["wq"], x, ctx).reshape(
            b, s, self.n_kv, self.groups, self.head_dim
        )
        src = x if kv_src is None else kv_src
        sk = src.shape[1]
        k = self.wk(params["wk"], src, ctx).reshape(b, sk, self.n_kv, self.head_dim)
        v = self.wv(params["wv"], src, ctx).reshape(b, sk, self.n_kv, self.head_dim)
        return q, k, v

    def _rope(self, q, k, q_positions, k_positions):
        cos_q, sin_q = rotary_angles(q_positions, self.head_dim,
                                     self.rope_base, self.yarn)
        b, s, kvh, g, d = q.shape
        qf = q.reshape(b, s, kvh * g, d)
        qf = apply_rotary(qf, cos_q, sin_q)
        cos_k, sin_k = rotary_angles(k_positions, self.head_dim,
                                     self.rope_base, self.yarn)
        k = apply_rotary(k, cos_k, sin_k)
        return qf.reshape(b, s, kvh, g, d), k

    def __call__(self, params, x, ctx=None, *, memory=None, q_offset: int = 0,
                 force_full=None):
        """Full-sequence forward (training / prefill without cache return).

        memory: encoder states for cross-attention.
        force_full: per-layer global-attention selector for scanned stacks
          (gemma3 5:1, hymba's 3 global layers).  None/False -> this
          layer's static behavior; True -> full attention; a traced bool ->
          lax.cond between the two (both branches share the same params).
        """
        b, s, _ = x.shape
        q, k, v = self._qkv(params, x, ctx, kv_src=memory)
        if not self.cross:
            q_pos = q_offset + jnp.arange(s)
            k_pos = q_offset + jnp.arange(k.shape[1])
            q, k = self._rope(q, k, q_pos, k_pos)
            self._observe_kv(ctx, k, v)
            k, v = self._fake_quant_kv(ctx, k, v)

            def windowed(q, k, v):
                if self.window is not None and s > self.window:
                    return sliding_window_attention(
                        q, k, v, window=self.window, q_chunk=self.q_chunk,
                        q_offset=q_offset,
                    )
                return flash_attention(
                    q, k, v, causal=self.causal, q_chunk=self.q_chunk,
                    kv_chunk=self.kv_chunk, q_offset=q_offset,
                    window=self.window,
                )

            def full(q, k, v):
                return flash_attention(
                    q, k, v, causal=self.causal, q_chunk=self.q_chunk,
                    kv_chunk=self.kv_chunk, q_offset=q_offset,
                )

            if force_full is None or force_full is False or self.window is None:
                o = windowed(q, k, v)
            elif force_full is True:
                o = full(q, k, v)
            else:
                o = jax.lax.cond(force_full, full, windowed, q, k, v)
        else:
            o = flash_attention(
                q, k, v, causal=False, q_chunk=self.q_chunk,
                kv_chunk=self.kv_chunk,
            )
        o = o.reshape(b, s, self.n_heads * self.head_dim)
        return self.wo(params["wo"], o, ctx)

    def prefill(self, params, x, cache: KVCache, ctx=None, *, memory=None,
                q_offset=0, lengths=None, kv_limit=None):
        """Forward + populate the KV cache (returns (y, cache)).

        K/V quantize ONCE via ``cache.ready`` (frozen calibrated per-head
        thresholds for an int8 cache) and the same cache-ready tiles feed
        both ``cache.append`` and — on the fused path (policy.use_pallas)
        — the Pallas flash-prefill kernel, which attends directly over
        the int8 stream through the cache's kernel view (identity block
        table for dense/ring, the page table for paged).  The jnp
        fallback reads ``dense_view`` and keeps the exact-K/V attention
        of the reference path.

        ``q_offset``/``lengths`` enable chunked ragged prefill: positions
        shift by ``q_offset``, the chunk's K/V append at position
        ``q_offset``, and attention runs against the updated cache masked
        to each request's valid length.  ``kv_limit`` (static int) bounds
        the cache extent attention reads — the step passes the padded
        prompt length so per-chunk work scales with the prompt, not the
        cache capacity.  ``lengths is None`` is the one-shot whole-prompt
        case."""
        b, s, _ = x.shape
        chunked = lengths is not None
        q, k, v = self._qkv(params, x, ctx, kv_src=memory)
        if not self.cross:
            pos = q_offset + jnp.arange(s)
            q, k = self._rope(q, k, pos, pos)
            self._observe_kv(ctx, k, v)
        if self.cross:
            # cross memory: written once per request, truncated to the
            # cache capacity; replaces the cache contents wholesale
            cap = min(cache.capacity, k.shape[1])
            kq, vq = cache.ready(k[:, :cap], v[:, :cap])
            new_cache = dataclasses.replace(cache, k=kq, v=vq)
            o = flash_attention(q, k, v, causal=False, q_chunk=self.q_chunk,
                                kv_chunk=self.kv_chunk)
            o = o.reshape(b, s, self.n_heads * self.head_dim)
            return self.wo(params["wo"], o, ctx), new_cache

        if cache.quantized:
            cache = cache.with_scales(*self._kv_scales(ctx))
        # quantize once: the same tiles are appended AND (kernel path)
        # attended — no bf16 K/V re-materialization between the two
        kq, vq = cache.ready(k, v)
        use_kernel = (ctx is not None and ctx.policy.use_pallas
                      and self.causal)
        sp = _sp_info()
        if sp is not None:
            return self._sp_prefill(params, x, cache, ctx, q, k, v, kq, vq,
                                    chunked, q_offset, kv_limit, sp,
                                    use_kernel)

        if chunked:
            if cache.layout == "ring":
                raise ValueError(
                    f"{self.path}: chunked prefill needs absolute slots (a "
                    "dense cache or paged layout); the SWA ring buffer "
                    "drops them (size the cache >= max_len or prefill "
                    "one-shot)")
            new_cache = cache.append(kq, vq, q_offset)
            kv_len = jnp.clip(jnp.asarray(lengths, jnp.int32), 0,
                              q_offset + s)
            # attend only the cache prefix that can hold prompt K/V —
            # without this every chunk pays for max_len (prompt + full
            # generation budget) worth of dequant + scores
            limit = (cache.capacity if kv_limit is None
                     else min(kv_limit, cache.capacity))
            if use_kernel:
                from repro.kernels import ops as kops

                o = kops.prefill_attention_view(
                    q, new_cache.kernel_view(limit), *new_cache.scales(),
                    q_offset, kv_len, causal=True, window=self.window,
                ).astype(x.dtype)
            else:
                k_eff, v_eff = new_cache.dequantize(
                    *new_cache.dense_view(limit))
                # cast back to the residual dtype: the dequantized f32
                # stream must not promote the carry (the fused path's
                # astype(x.dtype) contract; layer-scanned stacks require
                # a dtype-stable residual)
                o = flash_attention(q, k_eff, v_eff, causal=True,
                                    q_chunk=self.q_chunk,
                                    kv_chunk=self.kv_chunk,
                                    q_offset=q_offset,
                                    window=self.window).astype(x.dtype)
        else:
            # one-shot prompt write: the layout places the tiles (dense
            # at absolute slots, ring keeps the last `window` rolled,
            # paged scatters through the block table)
            new_cache = cache.append(kq, vq, 0)
            if use_kernel:
                o = self._prompt_kernel_attention(q, kq, vq, cache, x.dtype)
            elif self.window is not None and s > self.window:
                o = sliding_window_attention(q, k, v, window=self.window,
                                             q_chunk=self.q_chunk)
            else:
                o = flash_attention(q, k, v, causal=self.causal,
                                    q_chunk=self.q_chunk,
                                    kv_chunk=self.kv_chunk,
                                    window=self.window)
        o = o.reshape(b, s, self.n_heads * self.head_dim)
        return self.wo(params["wo"], o, ctx), new_cache

    def _prompt_kernel_attention(self, q, kq, vq, cache, dtype):
        """One-shot prompt attention through the fused prefill kernel,
        over the prompt's own cache-ready stream (identical tiles to what
        append writes — packed nibbles at bits=4, hence kv_bits from the
        cache)."""
        from repro.kernels import ops as kops

        b, s = q.shape[:2]
        return kops.prefill_attention(
            q, kq, vq, *cache.scales(), jnp.int32(0),
            jnp.full((b,), s, jnp.int32), causal=True,
            window=self.window, kv_bits=cache.bits,
        ).astype(dtype)

    def _sp_prefill(self, params, x, cache, ctx, q, k, v, kq, vq, chunked,
                    q_offset, kv_limit, sp, use_kernel):
        """Sequence-parallel prefill: each shard keeps the cache rows it
        owns (repro.shard.seq_cache owner writes — drop-mode scatters,
        never clamping slices).  One-shot chunks attend their own K/V
        stream directly (no gather needed — the prompt IS the visible
        sequence); chunked prefill all-gathers the int8 tiles and
        attends the bit-identical global view."""
        from repro.shard import seq_cache

        b, s, _ = x.shape
        if cache.layout != "dense":
            raise ValueError(
                f"{self.path}: sequence-parallel serving shards the dense "
                f"cache's S axis — layout {cache.layout!r} unsupported")
        if chunked:
            new_cache = seq_cache.owner_append(cache, kq, vq, q_offset,
                                               sp.axis)
            # inside shard_map ``capacity`` is the LOCAL slice length —
            # the gathered view below spans sp * capacity rows
            cap = cache.capacity * sp.sp
            limit = cap if kv_limit is None else min(kv_limit, cap)
            k_eff, v_eff = seq_cache.gathered_dense(new_cache, sp.axis,
                                                    limit)
            o = flash_attention(q, k_eff, v_eff, causal=True,
                                q_chunk=self.q_chunk,
                                kv_chunk=self.kv_chunk,
                                q_offset=q_offset,
                                window=self.window).astype(x.dtype)
        else:
            new_cache = seq_cache.owner_append(cache, kq, vq, 0, sp.axis)
            if use_kernel:
                # every shard holds the whole prompt: attend it exactly as
                # one chip does, so sharded and unsharded prefill agree
                o = self._prompt_kernel_attention(q, kq, vq, cache, x.dtype)
            elif self.window is not None and s > self.window:
                o = sliding_window_attention(q, k, v, window=self.window,
                                             q_chunk=self.q_chunk)
            else:
                o = flash_attention(q, k, v, causal=self.causal,
                                    q_chunk=self.q_chunk,
                                    kv_chunk=self.kv_chunk,
                                    window=self.window)
        o = o.reshape(b, s, self.n_heads * self.head_dim)
        return self.wo(params["wo"], o, ctx), new_cache

    def decode(self, params, x, cache: KVCache, cur_pos, ctx=None, *,
               memory=None, slot_mask=None):
        """Single-token decode. x: (B,1,d); cur_pos: tokens already cached
        — a scalar (uniform batch, the single-stream path) or a (B,)
        per-slot vector (continuous batching: each batch slot decodes at
        its own position, writes its K/V at its own cache index, and masks
        its own valid prefix).  ``slot_mask`` (B,) bool marks active slots
        when a scheduler drives the batch: inactive slots write nothing
        (bit-exact cache-neutral) and attend over zero keys (output rows
        zero).  The per-slot path needs absolute slots (dense or paged
        layout) — SWA ring buffers keep the scalar contract.

        With an int8 cache the new K/V quantize on append using the scales
        stored in the cache (written at prefill), so decode needs no
        threshold state.  The int8 path can run the fused Pallas
        flash-decode kernel (policy.use_pallas), which streams the cache's
        kernel-view tiles (block-table-mapped for paged) and dequantizes
        in VMEM, a windowed layer's tiles from its window's start only;
        otherwise the cache dequantizes into the jnp reference attention.
        """
        b, s, _ = x.shape
        q, k, v = self._qkv(params, x, ctx, kv_src=None if not self.cross else memory)
        if self.cross:
            o = decode_attention(q, cache.k, cache.v, cache.capacity)
            o = o.reshape(b, s, self.n_heads * self.head_dim)
            return self.wo(params["wo"], o, ctx), cache
        per_slot = jnp.ndim(cur_pos) > 0 or slot_mask is not None
        ring = cache.layout == "ring"
        if per_slot and ring:
            raise ValueError(
                f"{self.path}: per-slot decode (vector cur_pos / slot_mask) "
                "needs absolute slots (a dense cache or paged layout); the "
                "SWA ring buffer drops them — size the cache >= max_len or "
                "decode with a scalar position")
        sp = _sp_info()
        if sp is not None:
            return self._sp_decode(params, x, cache, cur_pos, ctx, q, k, v,
                                   per_slot, slot_mask, sp)
        if per_slot:
            pos_vec = jnp.broadcast_to(
                jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,))
            # per-slot rotary: positions (B, 1) batch the angle tables
            q, k = self._rope(q, k, pos_vec[:, None], pos_vec[:, None])
            kq, vq = cache.ready(k, v)
            upd = cache.append_slots(kq, vq, pos_vec, active=slot_mask)
        else:
            pos = jnp.full((s,), 0) + cur_pos
            q, k = self._rope(q, k, pos, pos)
            # same quantize-on-append point as prefill: the new token's
            # K/V become cache-ready tiles once, then a single write (the
            # ring layout wraps the index internally)
            kq, vq = cache.ready(k, v)
            upd = cache.append(kq, vq, cur_pos)

        if ring:
            # ring buffer: absolute decode against the layout's slot ->
            # position mapping
            k_eff, v_eff = upd.dequantize(upd.k, upd.v)
            abs_pos = upd.abs_positions(cur_pos)
            sc = _gqa_scores(
                q.astype(jnp.float32) / jnp.sqrt(jnp.asarray(self.head_dim, jnp.float32)),
                k_eff.astype(jnp.float32),
            )
            mask = (abs_pos >= 0) & (abs_pos >= cur_pos - self.window + 1)
            sc = jnp.where(mask[None, None, None, None, :], sc, NEG_INF)
            p = jax.nn.softmax(sc, axis=-1)
            o = _gqa_out(p, v_eff.astype(jnp.float32)).astype(x.dtype)
        else:
            if per_slot:
                # valid-prefix length per slot; an inactive slot attends
                # over zero keys -> exact-zero output rows
                valid = pos_vec + 1
                if slot_mask is not None:
                    valid = jnp.where(slot_mask, valid, 0)
            else:
                valid = cur_pos + 1
            use_kernel = (
                cache.quantized
                and ctx is not None
                and ctx.policy.use_pallas
            )
            if use_kernel:
                from repro.kernels import ops as kops

                # a windowed layer's cache holds every position: the
                # kernel masks, and skips the tiles left of, its window
                o = kops.decode_attention_view(
                    q[:, 0], upd.kernel_view(), *upd.scales(), valid,
                    window=self.window,
                )[:, None].astype(x.dtype)
            else:
                k_eff, v_eff = upd.dequantize(*upd.dense_view())
                # same dtype-stable-residual contract as the fused path
                o = decode_attention(q, k_eff, v_eff, valid,
                                     window=self.window).astype(x.dtype)
        o = o.reshape(b, s, self.n_heads * self.head_dim)
        return self.wo(params["wo"], o, ctx), upd

    def _sp_decode(self, params, x, cache, cur_pos, ctx, q, k, v, per_slot,
                   slot_mask, sp):
        """Sequence-parallel flash-decode: the owning shard writes the new
        token's K/V (drop-mode scatter), every shard scores its LOCAL
        visible keys into (m, l, acc) flash partials, and
        ``sp_partial_combine`` merges them into the exact unsharded
        softmax (repro.shard.partial_softmax) — the wire carries the
        tiny partial state, never the S-sized K/V stream."""
        from repro.shard import partial_softmax as PS
        from repro.shard import seq_cache

        b, s, _ = x.shape
        if cache.layout != "dense":
            raise ValueError(
                f"{self.path}: sequence-parallel serving shards the dense "
                f"cache's S axis — layout {cache.layout!r} unsupported")
        if self.window is not None:
            raise ValueError(
                f"{self.path}: sliding-window decode is local by "
                "construction — run SWA layers unsharded (sp=1)")
        if per_slot:
            pos_vec = jnp.broadcast_to(
                jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,))
            q, k = self._rope(q, k, pos_vec[:, None], pos_vec[:, None])
            kq, vq = cache.ready(k, v)
            upd = seq_cache.owner_append_slots(cache, kq, vq, pos_vec,
                                               sp.axis, active=slot_mask)
            valid = pos_vec + 1
            if slot_mask is not None:
                valid = jnp.where(slot_mask, valid, 0)
        else:
            pos = jnp.full((s,), 0) + cur_pos
            q, k = self._rope(q, k, pos, pos)
            kq, vq = cache.ready(k, v)
            upd = seq_cache.owner_append(cache, kq, vq, cur_pos, sp.axis)
            valid = jnp.broadcast_to(
                jnp.asarray(cur_pos, jnp.int32) + 1, (b,))
        shard_idx, s_local = seq_cache.shard_slice_info(upd, sp.axis)
        valid_local = jnp.clip(valid - shard_idx * s_local, 0, s_local)
        use_kernel = (cache.quantized and ctx is not None
                      and ctx.policy.use_pallas)
        if use_kernel:
            from repro.kernels import ops as kops

            acc, m, l = kops.decode_attention_partials_view(
                q[:, 0], upd.kernel_view(), *upd.scales(), valid_local)
            m, l, acc = m[..., None], l[..., None], acc[..., None, :]
        else:
            k_eff, v_eff = upd.dequantize(upd.k, upd.v)
            m, l, acc = PS.local_decode_partials(q, k_eff, v_eff,
                                                 valid_local)
        o = PS.sp_partial_combine(m, l, acc, sp.axis).astype(x.dtype)
        o = o.reshape(b, s, self.n_heads * self.head_dim)
        return self.wo(params["wo"], o, ctx), upd

    def verify(self, params, x, cache: KVCache, cur_pos, ctx=None, *,
               slot_mask=None):
        """Speculative-verify pass: s draft-window tokens per slot, each
        slot at its own position.  x: (B, s, d); ``cur_pos`` (B,) is the
        number of valid cache entries per slot — the window occupies
        absolute positions ``cur_pos[b] + [0, s)``, its K/V quantize once
        via ``cache.ready`` and append at those slots
        (``cache.append_slots``, the multi-token form), and attention is
        per-slot causal over the updated cache: query j sees keys
        ``<= cur_pos[b] + j``.  This is exactly a short per-slot chunked
        prefill: the fused path reuses the Pallas flash-prefill kernel
        through its per-request ``q_start`` vector, so one compiled
        verify executable serves every draft content and acceptance
        pattern (drafts are data, never shape).  ``slot_mask`` inactive
        slots write nothing (bit-exact cache-neutral) and return zero
        rows, matching ``decode``.  Rejected drafts need no physical
        erase — entries beyond the accepted position are dead until the
        next window overwrites them (``KVCache.rollback`` documents the
        layout contract).  Per-slot writes need absolute slots, so SWA
        ring buffers are rejected (same contract as ``decode``)."""
        b, s, _ = x.shape
        if self.cross:
            raise ValueError(
                f"{self.path}: speculative verify covers causal "
                "self-attention only")
        if cache.layout == "ring":
            raise ValueError(
                f"{self.path}: speculative verify needs absolute slots (a "
                "dense cache or paged layout); the SWA ring buffer drops "
                "them — size the cache >= max_len")
        q, k, v = self._qkv(params, x, ctx)
        pos_vec = jnp.broadcast_to(
            jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,))
        positions = pos_vec[:, None] + jnp.arange(s)            # (B, s)
        q, k = self._rope(q, k, positions, positions)
        kq, vq = cache.ready(k, v)
        sp = _sp_info()
        if sp is not None:
            from repro.shard import seq_cache

            if cache.layout != "dense":
                raise ValueError(
                    f"{self.path}: sequence-parallel serving shards the "
                    f"dense cache's S axis — layout {cache.layout!r} "
                    "unsupported")
            upd = seq_cache.owner_append_slots(cache, kq, vq, pos_vec,
                                               sp.axis, active=slot_mask)
            # a verify window is a short per-slot chunked prefill: gather
            # the int8 tiles (integer on the wire) and attend globally
            k_eff, v_eff = seq_cache.gathered_dense(upd, sp.axis)
            pos_eff = (pos_vec if slot_mask is None
                       else jnp.where(slot_mask, pos_vec, -1))
            o = verify_attention(q, k_eff, v_eff, pos_eff,
                                 window=self.window).astype(x.dtype)
            o = o.reshape(b, s, self.n_heads * self.head_dim)
            return self.wo(params["wo"], o, ctx), upd
        upd = cache.append_slots(kq, vq, pos_vec, active=slot_mask)

        use_kernel = (
            cache.quantized
            and ctx is not None
            and ctx.policy.use_pallas
        )
        if use_kernel:
            from repro.kernels import ops as kops

            # keys visible to the window: the prefix + the window itself,
            # causally restricted per row by the kernel's q_start vector
            kv_len = pos_vec + s
            if slot_mask is not None:
                kv_len = jnp.where(slot_mask, kv_len, 0)
            o = kops.prefill_attention_view(
                q, upd.kernel_view(), *upd.scales(), pos_vec, kv_len,
                causal=True, window=self.window,
            ).astype(x.dtype)
        else:
            k_eff, v_eff = upd.dequantize(*upd.dense_view())
            pos_eff = (pos_vec if slot_mask is None
                       else jnp.where(slot_mask, pos_vec, -1))
            o = verify_attention(q, k_eff, v_eff, pos_eff,
                                 window=self.window).astype(x.dtype)
        o = o.reshape(b, s, self.n_heads * self.head_dim)
        return self.wo(params["wo"], o, ctx), upd


