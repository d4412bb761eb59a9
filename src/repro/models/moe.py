"""Mixture-of-Experts with sort-based dispatch: dropless when serving,
by capacity per sequence in the training forward.

Serving (prefill chunks, decode) and calibration route dropless: every
token reaches each of its ``top_k`` experts, nothing is dropped.  One
call routes the (flattened) tokens, sorts the ``T * top_k`` assignments
by expert, and lays them out in
``block_m``-row tiles, each tile holding rows of one expert only
(``group_rows``): an expert with ``n`` assignments takes ``ceil(n /
block_m)`` tiles, an expert with none takes no tile.  Each expert
projection then runs once over that layout (``ExpertDense`` with a
``Groups``), and the results are gathered back to token order and
weight-summed.  The layout's shapes depend only on ``T``, ``top_k`` and
the expert count, so one compiled program serves every routing; which
tiles are live, and whose, is data.

A row mask (the decode loop's inactive slots, a chunked prefill's
padding) routes the masked tokens to no expert: they cost no expert work
and their output is zero.

The training forward (``dropless=False``) dispatches each sequence on its
own (MaxText-style) into ``capacity`` slots per expert and drops the
overflow: the token->slot cumsum stays local to a data shard, so no
global sort crosses the mesh, and the (G, E, C, d) buffers are anchored
on the batch axes.  Expert weights are TP-sharded on the hidden dim, so
every data shard holds all experts and dispatch needs no all-to-all.  A
dropless layout per sequence would need a grouped matmul batched over
sequences with shared expert weights, which ``lax.ragged_dot`` cannot
vmap.

Quantization: expert weights quantize in vector mode with per-(expert,
out-channel) thresholds, the paper's per-filter thresholds one level up;
activations get one threshold per expert, observed over that expert's
routed rows only.  The router stays unquantized (tiny, accuracy-critical
— same spirit as the paper keeping accumulators in int32).

Routing counters: inside ``routing_stats()`` every MoE layer that runs
adds its assignments routed, its largest group and its experts with a
row to the sink, as traced scalars.  The slot decode loop sums them over
a block's steps and layers (``launch/strategies.py``).
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ACTIVATIONS
from repro.models.module import Dense, ExpertDense, Module

_SINKS: list = []


@contextlib.contextmanager
def routing_stats():
    """Collect the routing counters of the MoE layers traced inside: a
    list of ``{"expert_rows", "expert_rows_max", "experts_live"}`` dicts
    of int32 scalars, one per layer call."""
    sink: list = []
    _SINKS.append(sink)
    try:
        yield sink
    finally:
        _SINKS.remove(sink)


class Groups(NamedTuple):
    """Rows grouped by expert in ``block_m``-row tiles (``group_rows``).

    ``sizes`` (E,) are the padded group sizes (whole tiles), in expert
    order; ``tile_expert`` (n_tiles,) the expert of each tile (the last
    live tile's for the dead tail); ``n_live`` () the tiles in use;
    ``row_expert``/``row_valid`` (n_tiles * block_m,) each row's expert
    and whether it holds an assignment (a tile's padding rows do not)."""
    sizes: jax.Array
    tile_expert: jax.Array
    n_live: jax.Array
    row_expert: jax.Array
    row_valid: jax.Array
    block_m: int


def block_rows(assignments: int, num_experts: int) -> int:
    """Rows per tile: the mean group size rounded up to 16 (a bf16
    sublane tile), between 16 and 128."""
    mean = -(-assignments // num_experts)
    return min(128, max(16, -(-mean // 16) * 16))


def group_rows(expert, num_experts: int, block_m: int):
    """Lay assignments out by expert.  ``expert`` (A,) int32 in [0, E],
    where E routes the assignment nowhere.  Returns (Groups, row_of (A,):
    each assignment's row, ``n_tiles * block_m`` for the unrouted, and
    counts (E,): assignments per expert)."""
    a, e = expert.shape[0], num_experts
    n_tiles = -(-a // block_m) + e          # enough for any routing
    m_pad = n_tiles * block_m
    counts = jnp.zeros((e + 1,), jnp.int32).at[expert].add(1)[:e]
    tiles = -(-counts // block_m)
    tile_end = jnp.cumsum(tiles)
    order = jnp.argsort(expert, stable=True)
    se = expert[order]
    ec = jnp.minimum(se, e - 1)
    rank = jnp.arange(a, dtype=jnp.int32) - (jnp.cumsum(counts)
                                             - counts)[ec]
    row = jnp.where(se < e, (tile_end - tiles)[ec] * block_m + rank, m_pad)
    row_of = jnp.zeros((a,), jnp.int32).at[order].set(row.astype(jnp.int32))
    n_live = tile_end[-1]
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.searchsorted(tile_end, jnp.minimum(
        t, jnp.maximum(n_live - 1, 0)), side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, e - 1)
    row_valid = jnp.zeros((m_pad,), bool).at[row_of].set(True, mode="drop")
    groups = Groups(sizes=tiles * block_m, tile_expert=tile_expert,
                    n_live=n_live, row_expert=jnp.repeat(tile_expert, block_m),
                    row_valid=row_valid, block_m=block_m)
    return groups, row_of, counts


def _dispatch(x, logits, top_k: int, capacity: int, num_experts: int):
    """Batched sort-based dispatch by capacity: one independent dispatch
    per group (sequence).

    x: (G, T, d); logits: (G, T, E).
    Returns (x_dispatched (G, E, C, d), combine info for the return path).

    The wide (.., d) tensors move ONLY through gathers: GSPMD partitions
    gather batch dims cleanly, while a scatter on a (G, slots, d) buffer
    falls back to full replication (+ giant u32 index broadcasts).
    Scatters touch only small (G, T*K) int32 maps.
    """
    g, t, d = x.shape
    e = num_experts
    top_idx, top_vals = route(logits, top_k)                   # (G, T, K)

    e_flat = top_idx.reshape(g, t * top_k)                      # (G, T*K)
    t_flat = jnp.tile(jnp.repeat(jnp.arange(t), top_k), (g, 1))  # (G, T*K)
    w_flat = top_vals.reshape(g, t * top_k)

    order = jnp.argsort(e_flat, axis=-1)             # stable: ties by index
    se = jnp.take_along_axis(e_flat, order, axis=-1)
    st = jnp.take_along_axis(t_flat, order, axis=-1)

    counts = jnp.sum(jax.nn.one_hot(e_flat, e, dtype=jnp.int32), axis=1)  # (G, E)
    starts = jnp.cumsum(counts, axis=-1) - counts    # exclusive cumsum
    rank = jnp.arange(t * top_k)[None, :] - jnp.take_along_axis(starts, se, axis=-1)
    valid = rank < capacity
    slot = jnp.where(valid, se * capacity + rank, e * capacity)  # trash slot

    gi = jnp.arange(g)[:, None]
    # slot -> source token (small int32 scatter); unfilled slots read the
    # zero sentinel row of x_pad
    src_tok = jnp.full((g, e * capacity + 1), t, jnp.int32)
    src_tok = src_tok.at[gi, slot].set(st, mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((g, 1, d), x.dtype)], axis=1)
    x_disp = jnp.take_along_axis(x_pad, src_tok[:, :-1, None], axis=1)
    x_disp = x_disp.reshape(g, e, capacity, d)
    # (token, k) -> slot in original order (small int32 scatter)
    slot_unsorted = jnp.full((g, t * top_k), e * capacity, jnp.int32)
    slot_unsorted = slot_unsorted.at[gi, order].set(slot, mode="drop")
    return x_disp, (slot_unsorted, w_flat)


def _combine(y_exp, info, t: int):
    """Return path: gather expert outputs back to token order and
    weight-sum over the K assignments.  y_exp: (G, E, C, d) -> (G, T, d).
    Dropped assignments point at the zero sentinel row: no masking pass
    over the wide tensor needed."""
    slot_unsorted, w_flat = info  # (G, T*K)
    g, e, c, d = y_exp.shape
    k = (slot_unsorted.shape[1]) // t
    flat = y_exp.reshape(g, e * c, d)
    flat_pad = jnp.concatenate([flat, jnp.zeros((g, 1, d), flat.dtype)], axis=1)
    contrib = jnp.take_along_axis(flat_pad, slot_unsorted[..., None], axis=1)
    # weighted sum in float32, as the dropless combine does
    contrib = contrib.astype(jnp.float32) * w_flat[..., None]
    return contrib.reshape(g, t, k, d).sum(axis=2).astype(y_exp.dtype)


def route(logits, top_k: int):
    """Softmax over the experts, top-k, renormalized over the chosen."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    return top_idx, top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)


class MoE(Module):
    def __init__(
        self,
        d_model: int,
        d_ff: int,
        num_experts: int,
        top_k: int,
        *,
        path: str,
        capacity_factor: float = 1.25,
        activation: str = "silu",
        dtype=jnp.bfloat16,
    ):
        self.d_model = d_model
        self.d_ff = d_ff
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.act = ACTIVATIONS[activation]
        self.path = path
        self.router = Dense(d_model, num_experts, path=f"{path}/router",
                            quantize=False, dtype=jnp.float32,
                            logical_axes=("embed", "expert"))
        ea = ("expert", "embed", "mlp")
        self.gate = ExpertDense(num_experts, d_model, d_ff,
                                path=f"{path}/gate", dtype=dtype, logical_axes=ea)
        self.up = ExpertDense(num_experts, d_model, d_ff,
                              path=f"{path}/up", dtype=dtype, logical_axes=ea)
        self.down = ExpertDense(num_experts, d_ff, d_model,
                                path=f"{path}/down", dtype=dtype,
                                logical_axes=("expert", "mlp", "embed"))

    def init(self, key):
        ks = jax.random.split(key, 4)
        return {
            "router": self.router.init(ks[0]),
            "gate": self.gate.init(ks[1]),
            "up": self.up.init(ks[2]),
            "down": self.down.init(ks[3]),
        }

    def capacity(self, tokens_per_group: int) -> int:
        """Slots per expert per sequence in the training forward."""
        c = int(np.ceil(tokens_per_group * self.top_k / self.num_experts
                        * self.capacity_factor))
        return max(8, int(np.ceil(c / 8)) * 8)  # pad to sublane multiple

    def __call__(self, params, x, ctx=None, *, row_mask=None,
                 dropless=True):
        """x: (B, S, d) -> (y (B, S, d), aux load-balance loss).
        ``dropless`` False is the training forward's dispatch by capacity
        per sequence.  ``row_mask`` (B, S) or (B,) bool, dropless only:
        False tokens go to no expert and get y == 0."""
        with jax.named_scope("moe.route"):
            logits = self.router(params["router"], x.astype(jnp.float32),
                                 ctx)                          # (B, S, E)
        if dropless:
            y = self._dropless(params, x, logits, ctx, row_mask)
        else:
            y = self._by_capacity(params, x, logits, ctx)

        # standard load-balance auxiliary (Switch-style): E * sum(f_e * p_e)
        e = self.num_experts
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top1 = jnp.argmax(probs, axis=-1)
        f = jnp.mean(jax.nn.one_hot(top1, e), axis=(0, 1))
        p = jnp.mean(probs, axis=(0, 1))
        aux = e * jnp.sum(f * p)
        return y, aux

    def _by_capacity(self, params, x, logits, ctx):
        from repro.dist.constraints import constrain_activation

        s = x.shape[1]
        xd, info = _dispatch(x, logits, self.top_k, self.capacity(s),
                             self.num_experts)
        # scatter output defeats GSPMD propagation: anchor the dispatch
        # buffer's group dim on the batch axes or it replicates (G,E,C,d)
        # on every device
        xd = constrain_activation(xd)
        g = self.act(self.gate(params["gate"], xd, ctx))
        u = self.up(params["up"], xd, ctx)
        yd = self.down(params["down"], g * u, ctx)
        yd = constrain_activation(yd)
        return constrain_activation(_combine(yd, info, s))

    def _dropless(self, params, x, logits, ctx, row_mask):
        b, s, d = x.shape
        t, k, e = b * s, self.top_k, self.num_experts
        xf = x.reshape(t, d)
        with jax.named_scope("moe.route"):
            idx, weights = route(logits.reshape(t, e), k)      # (T, K)
            if row_mask is not None:
                keep = jnp.broadcast_to(
                    row_mask.reshape(b, -1), (b, s)).reshape(t, 1)
                idx = jnp.where(keep, idx, e)
        with jax.named_scope("moe.dispatch"):
            bm = block_rows(t * k, e)
            groups, row_of, counts = group_rows(
                idx.reshape(-1).astype(jnp.int32), e, bm)
            # row -> token; a tile's padding rows read the zero row
            src = jnp.full((groups.row_valid.shape[0],), t * k, jnp.int32)
            src = src.at[row_of].set(jnp.arange(t * k, dtype=jnp.int32),
                                     mode="drop")
            x_ext = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
            xg = jnp.take(x_ext, jnp.minimum(src // k, t), axis=0)
        for sink in _SINKS:
            sink.append({"expert_rows": jnp.sum(counts),
                         "expert_rows_max": jnp.max(counts),
                         "experts_live": jnp.sum(counts > 0)})
        g = self.act(self.gate(params["gate"], xg, ctx, groups=groups))
        u = self.up(params["up"], xg, ctx, groups=groups)
        yg = self.down(params["down"], g * u, ctx, groups=groups)
        with jax.named_scope("moe.combine"):
            # unrouted assignments point past the last row: they read 0
            ya = jnp.take(yg, row_of, axis=0, mode="fill", fill_value=0)
            w = jnp.where(idx < e, weights, 0.0).reshape(t * k, 1)
            y = (ya.astype(jnp.float32) * w).reshape(t, k, d).sum(axis=1)
            return y.astype(x.dtype).reshape(b, s, d)

    def equalization_pairs(self):
        """Per-expert up->down rescale (§3.3 through the gate product)."""
        return [(self.up.path, self.down.path)]
