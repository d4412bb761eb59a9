"""Cross-shard partial-softmax combine for sequence-parallel flash-decode.

With the KV cache's S axis split across ``sp`` shards, each shard can
only score its local keys.  Flash-decode's online-softmax state makes
the split exact: a shard emits (m, l, acc) — running max, normalizer,
and UNNORMALIZED value accumulator over its local visible keys — and
the merge

    M     = max_i m_i
    l_tot = sum_i l_i * exp(m_i - M)
    out   = sum_i acc_i * exp(m_i - M) / l_tot

reproduces the unsharded softmax up to f32 summation order (ulp-bounded;
the parity tests in tests/test_sharded.py pin it).  A shard with zero
visible keys contributes l = 0 / acc = 0 (its masked scores sit at the
finite NEG_INF sentinel, and the mask multiplies its probabilities to
exact zero), so inactive slots keep the engine's exact-zero-rows
convention through the merge.

Wire discipline: the partials are gathered with ``all_gather`` — a
float payload, but NOT an all-reduce, so the HLO-level "every all-reduce
carries integer bytes" assertion (launch/hlo_analysis.py) stays strict.
At the jaxpr level the gather is sanctioned by the ``drift.collective``
AllowRule scoped to ``sp_partial_combine`` (the per-token partial state
is KV*G*D floats per slot — orders of magnitude below the S-sized K/V
stream the sharding exists to avoid moving).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import NEG_INF, _gqa_scores


def local_decode_partials(q, k_local, v_local, valid_local):
    """Per-shard flash-decode partials.

    q: (B, 1, KV, G, D) — the decode query (replicated across shards);
    k_local/v_local: (B, S_local, KV, D) — this shard's cache slice
    (already dequantized); valid_local: (B,) — number of visible keys
    IN THIS SHARD (clip(valid_global - shard*S_local, 0, S_local)).

    Returns (m, l, acc): (B, KV, G, 1), (B, KV, G, 1), (B, KV, G, 1, D)
    f32.  Scores masked beyond valid_local sit at NEG_INF and their
    probabilities are multiplied to exact zero, so a shard with nothing
    visible returns (NEG_INF, 0, 0) — the merge identity element.
    """
    b = q.shape[0]
    d = q.shape[-1]
    s_local = k_local.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = _gqa_scores(q.astype(jnp.float32) * scale,
                    k_local.astype(jnp.float32))       # (B, KV, G, 1, S_l)
    valid = jnp.broadcast_to(
        jnp.asarray(valid_local, jnp.int32).reshape(-1), (b,))
    mask = jnp.arange(s_local)[None, :] < valid[:, None]          # (B, S_l)
    maskb = mask[:, None, None, None, :]
    s = jnp.where(maskb, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                       # (B,KV,G,1)
    p = jnp.exp(s - m[..., None]) * maskb                         # exact 0s
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgqs,bskd->bkgqd", p, v_local.astype(jnp.float32))
    return m, l, acc


def sp_partial_combine(m, l, acc, axis_name: str):
    """Merge per-shard (m, l, acc) partials into the exact softmax output.

    All inputs are this shard's locals; the function all-gathers them
    over ``axis_name`` (shard count known statically from the mesh) and
    reduces in deterministic shard-index order, so every shard computes
    the identical merged value — the combined output is replicated.

    Returns (B, 1, KV, G, D) in f32 (callers cast to the residual
    dtype).  All-empty rows (l_tot == 0, e.g. an inactive scheduler
    slot) return exact zeros.
    """
    # float payloads — gathers, not reduces; see module docstring
    return merge_partials(jax.lax.all_gather(m, axis_name),
                          jax.lax.all_gather(l, axis_name),
                          jax.lax.all_gather(acc, axis_name))


def merge_partials(mg, lg, ag):
    """The merge itself, over partials stacked on a leading shard axis:
    mg/lg (sp, B, KV, G, 1), ag (sp, B, KV, G, 1, D).  Returns
    ``sp_partial_combine``'s (B, 1, KV, G, D) f32."""
    m_tot = jnp.max(mg, axis=0)
    # NEG_INF is a finite sentinel: an all-empty row has m_i == M, the
    # weights come out exp(0) == 1, and the l_i == 0 terms still produce
    # l_tot == 0 — the zero-guard below owns that case, never a NaN
    w = jnp.exp(mg - m_tot[None])                # (sp, B, KV, G, 1)
    l_tot = jnp.sum(lg * w, axis=0)
    o = jnp.sum(ag * w[..., None], axis=0)
    o = o / jnp.maximum(l_tot[..., None], 1e-30)
    o = o * (l_tot[..., None] > 0)
    # (B, KV, G, 1, D) -> (B, 1, KV, G, D): the attention output layout
    return jnp.moveaxis(o, 3, 1)
