"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import unpack_int4


def quant_matmul_ref(x, w_q, w_scale, act_scale, out_dtype=jnp.bfloat16,
                     w_bits=8):
    """Oracle for kernels.quant_matmul: quantize -> int8 matmul -> dequant.

    ``w_bits == 4``: w_q arrives nibble-packed along K ((K/2, N) bytes).
    """
    if w_bits == 4:
        w_q = unpack_int4(w_q, axis=0)
    x_q = jnp.clip(
        jnp.round(x.astype(jnp.float32) * act_scale), -127, 127
    ).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    return (acc.astype(jnp.float32) * w_scale[None, :]).astype(out_dtype)


def expert_matmul_ref(x, w_q, w_scale, act_scale, row_expert, sizes, *,
                      bits=8, out_dtype=jnp.bfloat16):
    """Oracle for kernels.expert_matmul, over the same grouped layout:
    each row quantizes with its expert's scale, the groups (``sizes``
    rows each, in expert order) multiply their own expert's int8 weights
    with int32 accumulation (``lax.ragged_dot``), and each row dequantizes
    by its expert's per-channel scale.  Rows past the groups read 0.

    x: (M, K) float; w_q: (E, K, N) int8; w_scale: (E, N) f32 (already /
    act scale); act_scale: (E,) f32; row_expert: (M,) int32; sizes: (E,).
    """
    levels = float(2 ** (bits - 1) - 1)
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32)
                             * act_scale[row_expert][:, None]),
                   -levels, levels).astype(jnp.int8)
    acc = jax.lax.ragged_dot(x_q, w_q, sizes,
                             preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * w_scale[row_expert]).astype(out_dtype)


def decode_attention_ref(q, k_cache, v_cache, k_scale, v_scale, cur_pos,
                         out_dtype=jnp.float32, kv_bits=8, window=None):
    """Oracle for kernels.decode_attention_int8: dequantize the cache,
    masked softmax over valid positions, GQA-grouped output.

    q: (B, KV, G, D); k/v_cache: (B, S, KV, D) int8 (or float) — at
    ``kv_bits == 4`` the caches are (B, S, KV, D/2) packed nibbles;
    k/v_scale: (KV,) dequant scales; cur_pos: valid cache length — a
    scalar (uniform batch) or a (B,) per-slot vector (continuous
    batching).  A row with cur_pos == 0 (empty cache / inactive slot)
    returns zeros, matching the kernel.  ``window``: the query (at
    cur_pos - 1) sees keys from cur_pos - window on.
    """
    b = q.shape[0]
    d = q.shape[-1]
    if kv_bits == 4:
        k_cache = unpack_int4(k_cache, axis=-1, size=d)
        v_cache = unpack_int4(v_cache, axis=-1, size=d)
    kf = k_cache.astype(jnp.float32) * k_scale.reshape(1, 1, -1, 1)
    vf = v_cache.astype(jnp.float32) * v_scale.reshape(1, 1, -1, 1)
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum("bkgd,bskd->bkgs", qf, kf)
    pos = jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,))
    k_pos = jnp.arange(k_cache.shape[1])[None, :]
    mask = k_pos < pos[:, None]                                  # (B, S)
    if window is not None:
        mask &= k_pos >= pos[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    return (out * (pos > 0)[:, None, None, None]).astype(out_dtype)


def prefill_attention_ref(q, k, v, k_scale, v_scale, q_start, kv_len, *,
                          causal=True, window=None, out_dtype=jnp.float32,
                          kv_bits=8):
    """Oracle for kernels.prefill_attention_int8: dequantize the K/V
    stream, masked softmax per query row, GQA-grouped output.

    q: (B, Sq, KV, G, D); k/v: (B, Sk, KV, D) int8 (or float) — at
    ``kv_bits == 4`` they are (B, Sk, KV, D/2) packed nibbles;
    k/v_scale: (KV,) dequant scales; q_start: absolute position of query
    row 0 (scalar); kv_len: (B,) valid KV count per request.  Query rows
    with no visible key return zeros, matching the kernel.
    """
    b, sq, kvh, g, d = q.shape
    if kv_bits == 4:
        k = unpack_int4(k, axis=-1, size=d)
        v = unpack_int4(v, axis=-1, size=d)
    sk = k.shape[1]
    kf = k.astype(jnp.float32) * k_scale.reshape(1, 1, -1, 1)
    vf = v.astype(jnp.float32) * v_scale.reshape(1, 1, -1, 1)
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)
    q_pos = jnp.asarray(q_start) + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
    mask = (k_pos[None, :] < kv_len[:, None])[:, None, None, None, :]
    mask = jnp.broadcast_to(mask, s.shape)
    if causal:
        mask &= (q_pos[:, None] >= k_pos[None, :])[None, None, None]
    if window is not None:
        mask &= ((q_pos[:, None] - k_pos[None, :]) < window)[None, None, None]
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p / denom, vf)
    return out.astype(out_dtype)


def fake_quant_ref(x, t_max, alpha, *, levels=127.0, qmin=-127.0, qmax=127.0,
                   alpha_min=0.5, alpha_max=1.0):
    """Oracle for kernels.fake_quant_fwd (per-out-channel thresholds)."""
    a = jnp.clip(alpha.astype(jnp.float32), alpha_min, alpha_max)
    t_adj = jnp.maximum(a * t_max.astype(jnp.float32), 1e-8)
    s = levels / t_adj
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) * s[None, :]), qmin, qmax)
    return (xq / s[None, :]).astype(x.dtype)
