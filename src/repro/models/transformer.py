"""Decoder/encoder stacks composing attention / MoE / SSM / hybrid blocks.

A ``Block`` is one residual layer; its kind is selected per-layer by the
config's ``layer_kinds`` pattern, which is how gemma3's 5:1 local:global
attention, hymba's parallel attn+SSM, and pure-SSM mamba2 are expressed in
one stack implementation.

Remat: each block is wrapped in ``jax.checkpoint`` (policy: save nothing /
dots) under training so the 4k x 256 batch fits; serving paths never remat.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.attention import Attention
from repro.models.layers import LayerNorm, RMSNorm
from repro.models.mlp import GeluMLP, SwiGLU
from repro.models.moe import MoE
from repro.models.module import Module
from repro.models.ssm import Mamba2Block


class Block(Module):
    """One pre-norm residual layer: norm -> mixer -> (+) -> norm -> ffn -> (+).

    kind: 'attn' | 'attn_local' | 'mamba' | 'hybrid'
    ffn:  'swiglu' | 'gelu' | 'moe' | 'none' (mamba2 has no separate FFN)
    """

    def __init__(self, cfg, layer_idx: int, *, path: str, cross: bool = False):
        self.cfg = cfg
        self.idx = layer_idx
        self.path = path
        self.cross = cross
        d = cfg.d_model
        dt = cfg.dtype
        norm_cls = LayerNorm if cfg.norm == "layernorm" else RMSNorm

        self.kind = cfg.layer_kind(layer_idx)
        self.ffn_kind = cfg.ffn_kind(layer_idx)

        self.pre_norm = norm_cls(d, path=f"{path}/pre_norm", dtype=dt)
        if self.kind in ("attn", "attn_local", "hybrid"):
            window = cfg.attn_window(layer_idx)
            self.attn = Attention(
                d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                path=f"{path}/attn", window=window, rope_base=cfg.rope_base,
                yarn=cfg.rope_yarn(layer_idx), causal=cfg.causal, dtype=dt,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
            )
        if self.kind in ("mamba", "hybrid"):
            self.mamba = Mamba2Block(
                d, path=f"{path}/mamba", d_state=cfg.ssm_state,
                n_heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
                expand=cfg.ssm_expand, n_groups=cfg.ssm_groups,
                chunk=cfg.ssm_chunk, dtype=dt,
            )
        if self.kind == "hybrid":
            # hymba: per-branch output norms before mean fusion
            self.attn_out_norm = norm_cls(d, path=f"{path}/attn_out_norm", dtype=dt)
            self.mamba_out_norm = norm_cls(d, path=f"{path}/mamba_out_norm", dtype=dt)
        if self.cross:
            self.cross_norm = norm_cls(d, path=f"{path}/cross_norm", dtype=dt)
            self.cross_attn = Attention(
                d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                path=f"{path}/cross_attn", causal=False, cross=True,
                rope_base=cfg.rope_base, dtype=dt,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
            )
        if self.ffn_kind != "none":
            self.ffn_norm = norm_cls(d, path=f"{path}/ffn_norm", dtype=dt)
            if self.ffn_kind == "moe":
                self.ffn = MoE(d, cfg.d_ff, cfg.n_experts, cfg.top_k,
                               path=f"{path}/moe", dtype=dt,
                               capacity_factor=cfg.capacity_factor,
                               activation=cfg.mlp_activation)
            elif self.ffn_kind == "gelu":
                self.ffn = GeluMLP(d, cfg.d_ff, path=f"{path}/mlp", dtype=dt,
                                   activation=cfg.mlp_activation)
            else:
                self.ffn = SwiGLU(d, cfg.d_ff, path=f"{path}/mlp", dtype=dt,
                                  activation=cfg.mlp_activation)

    def init(self, key):
        p = {}
        ks = iter(jax.random.split(key, 8))
        p["pre_norm"] = self.pre_norm.init(next(ks))
        if hasattr(self, "attn"):
            p["attn"] = self.attn.init(next(ks))
        if hasattr(self, "mamba"):
            p["mamba"] = self.mamba.init(next(ks))
        if self.kind == "hybrid":
            p["attn_out_norm"] = self.attn_out_norm.init(next(ks))
            p["mamba_out_norm"] = self.mamba_out_norm.init(next(ks))
        if self.cross:
            p["cross_norm"] = self.cross_norm.init(next(ks))
            p["cross_attn"] = self.cross_attn.init(next(ks))
        if self.ffn_kind != "none":
            p["ffn_norm"] = self.ffn_norm.init(next(ks))
            p["ffn"] = self.ffn.init(next(ks))
        return p

    def _mixer(self, params, h, ctx, memory, force_full=None):
        if self.kind == "hybrid":
            a = self.attn(params["attn"], h, ctx, force_full=force_full)
            m = self.mamba(params["mamba"], h, ctx)
            a = self.attn_out_norm(params["attn_out_norm"], a)
            m = self.mamba_out_norm(params["mamba_out_norm"], m)
            return 0.5 * (a + m)
        if self.kind == "mamba":
            return self.mamba(params["mamba"], h, ctx)
        return self.attn(params["attn"], h, ctx, force_full=force_full)

    def __call__(self, params, x, ctx=None, *, memory=None, force_full=None):
        """Returns (y, aux) where aux is the MoE load-balance loss (0 else)."""
        h = self.pre_norm(params["pre_norm"], x)
        x = x + self._mixer(params, h, ctx, memory, force_full)
        if self.cross:
            h = self.cross_norm(params["cross_norm"], x)
            x = x + self.cross_attn(params["cross_attn"], h, ctx, memory=memory)
        aux = jnp.zeros((), jnp.float32)
        if self.ffn_kind != "none":
            h = self.ffn_norm(params["ffn_norm"], x)
            if self.ffn_kind == "moe":
                # training dispatches by capacity per sequence; the
                # calibration pass observes every routed row
                y, aux = self.ffn(params["ffn"], h, ctx, dropless=(
                    ctx is not None and ctx.mode == "calibrate"))
            else:
                y = self.ffn(params["ffn"], h, ctx)
            x = x + y
        return x, aux

    # -- caches -------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   kv_int8: bool = False, layout: str = "ring",
                   page_size: int = 64, extra_pages: int = 0,
                   kv_bits: int = 8) -> dict:
        c = {}
        if hasattr(self, "attn"):
            c["attn"] = self.attn.init_cache(batch, max_len, dtype,
                                             kv_int8=kv_int8, layout=layout,
                                             page_size=page_size,
                                             extra_pages=extra_pages,
                                             kv_bits=kv_bits)
        if hasattr(self, "mamba"):
            c["mamba"] = self.mamba.init_cache(batch)
        if self.cross:
            # cross memory is prefill-only traffic; stays full precision
            c["cross"] = self.cross_attn.init_cache(batch, max_len, dtype)
        return c

    def prefill(self, params, x, cache, ctx=None, *, memory=None,
                q_offset=0, lengths=None, kv_limit=None):
        """``q_offset``/``lengths``/``kv_limit`` thread chunked ragged
        prefill down to attention (SSM state folding is offset-free: it
        continues from the carried cache state, so chunking composes;
        raggedness is an attention-mask concept guarded at the step
        level)."""
        h = self.pre_norm(params["pre_norm"], x)
        new_cache = dict(cache)
        if self.kind == "hybrid":
            a, new_cache["attn"] = self.attn.prefill(
                params["attn"], h, cache["attn"], ctx,
                q_offset=q_offset, lengths=lengths, kv_limit=kv_limit)
            m = self.mamba(params["mamba"], h, ctx)
            # rebuild mamba decode state from the full prefill (rerun tail):
            new_cache["mamba"] = self._mamba_state_from_prefill(params, h,
                                                                cache, ctx)
            a = self.attn_out_norm(params["attn_out_norm"], a)
            m = self.mamba_out_norm(params["mamba_out_norm"], m)
            mix = 0.5 * (a + m)
        elif self.kind == "mamba":
            mix = self.mamba(params["mamba"], h, ctx)
            new_cache["mamba"] = self._mamba_state_from_prefill(params, h,
                                                                cache, ctx)
        else:
            mix, new_cache["attn"] = self.attn.prefill(
                params["attn"], h, cache["attn"], ctx,
                q_offset=q_offset, lengths=lengths, kv_limit=kv_limit)
        x = x + mix
        if self.cross:
            h = self.cross_norm(params["cross_norm"], x)
            y, new_cache["cross"] = self.cross_attn.prefill(
                params["cross_attn"], h, cache["cross"], ctx, memory=memory
            )
            x = x + y
        if self.ffn_kind != "none":
            h = self.ffn_norm(params["ffn_norm"], x)
            if self.ffn_kind == "moe":
                # a chunk's padding past each request's length routes to
                # no expert
                mask = None if lengths is None else (
                    q_offset + jnp.arange(x.shape[1])[None, :]
                    < jnp.asarray(lengths, jnp.int32).reshape(-1, 1))
                y, _ = self.ffn(params["ffn"], h, ctx, row_mask=mask)
            else:
                y = self.ffn(params["ffn"], h, ctx)
            x = x + y
        return x, new_cache

    def _mamba_state_from_prefill(self, params, h, cache, ctx=None):
        """Sequentially folds the prefill into the SSD decode state.

        Uses the chunked scan's final carry — recomputed here in one pass
        (linear cost) rather than threaded through ssd_chunked, keeping the
        train path allocation-free."""
        # cheap correct path: run decode steps over the last conv window to
        # build conv state, and a full linear scan for the ssm state.
        m = self.mamba
        bsz, l, _ = h.shape
        z, xi, bi, ci, dt = m._project(params["mamba"], h, ctx)
        xbc = jnp.concatenate([
            xi.astype(jnp.float32), bi.astype(jnp.float32),
            ci.astype(jnp.float32)], axis=-1)
        from repro.models.ssm import causal_conv1d, ssd_decode_step
        from repro.models.layers import silu as _silu
        conv_state = xbc[:, -(m.conv_width - 1):, :]
        xbc_c = _silu(causal_conv1d(xbc, params["mamba"]["conv_w"],
                                    params["mamba"]["conv_b"]))
        di, gn = m.d_inner, m.n_groups * m.d_state
        x_h = xbc_c[..., :di].reshape(bsz, l, m.n_heads, m.head_dim)
        b_h = xbc_c[..., di:di + gn].reshape(bsz, l, m.n_groups, m.d_state)
        c_h = xbc_c[..., di + gn:].reshape(bsz, l, m.n_groups, m.d_state)
        dt_s = jax.nn.softplus(dt.astype(jnp.float32) + params["mamba"]["dt_bias"])

        def step(state, inp):
            x_t, dt_t, b_t, c_t = inp
            s, _ = ssd_decode_step(state, x_t, dt_t, params["mamba"]["a_log"],
                                   b_t, c_t)
            return s, None

        init = cache["mamba"]["ssm"]
        state, _ = jax.lax.scan(
            step, init,
            (jnp.moveaxis(x_h, 1, 0), jnp.moveaxis(dt_s, 1, 0),
             jnp.moveaxis(b_h, 1, 0), jnp.moveaxis(c_h, 1, 0)),
        )
        return {"ssm": state, "conv": conv_state}

    def decode(self, params, x, cache, cur_pos, ctx=None, *, memory=None,
               slot_mask=None):
        """``cur_pos`` may be a per-slot (B,) vector and ``slot_mask`` a
        (B,) active mask (continuous batching) — both thread down to
        attention; SSM decode has no per-request position concept, so the
        scheduler guards attention-only stacks at construction."""
        h = self.pre_norm(params["pre_norm"], x)
        new_cache = dict(cache)
        if self.kind == "hybrid":
            a, new_cache["attn"] = self.attn.decode(params["attn"], h,
                                                    cache["attn"], cur_pos, ctx,
                                                    slot_mask=slot_mask)
            m, new_cache["mamba"] = self.mamba.decode(params["mamba"], h,
                                                      cache["mamba"], ctx)
            a = self.attn_out_norm(params["attn_out_norm"], a)
            m = self.mamba_out_norm(params["mamba_out_norm"], m)
            mix = 0.5 * (a + m)
        elif self.kind == "mamba":
            mix, new_cache["mamba"] = self.mamba.decode(params["mamba"], h,
                                                        cache["mamba"], ctx)
        else:
            mix, new_cache["attn"] = self.attn.decode(params["attn"], h,
                                                      cache["attn"], cur_pos, ctx,
                                                      slot_mask=slot_mask)
        x = x + mix
        if self.cross:
            h = self.cross_norm(params["cross_norm"], x)
            y, _ = self.cross_attn.decode(params["cross_attn"], h,
                                          cache["cross"], cur_pos, ctx,
                                          memory=memory)
            x = x + y
        if self.ffn_kind != "none":
            h = self.ffn_norm(params["ffn_norm"], x)
            if self.ffn_kind == "moe":
                # inactive slots route to no expert
                y, _ = self.ffn(params["ffn"], h, ctx, row_mask=slot_mask)
            else:
                y = self.ffn(params["ffn"], h, ctx)
            x = x + y
        return x, new_cache


    def verify(self, params, x, cache, cur_pos, ctx=None, *,
               slot_mask=None):
        """Speculative-verify: s draft tokens per slot at per-slot
        offsets — the multi-token sibling of ``decode``.  Attention-only
        (SSM state stepping has no rewind, so strategies guard the config
        upstream, same as the slot decode loop)."""
        if self.kind not in ("attn", "attn_local") or self.cross:
            raise ValueError(
                f"{self.path}: speculative verify covers attention-only "
                f"causal stacks (got kind={self.kind!r}, "
                f"cross={self.cross})")
        h = self.pre_norm(params["pre_norm"], x)
        new_cache = dict(cache)
        mix, new_cache["attn"] = self.attn.verify(
            params["attn"], h, cache["attn"], cur_pos, ctx,
            slot_mask=slot_mask)
        x = x + mix
        if self.ffn_kind != "none":
            h = self.ffn_norm(params["ffn_norm"], x)
            if self.ffn_kind == "moe":
                # inactive slots route to no expert
                y, _ = self.ffn(params["ffn"], h, ctx, row_mask=slot_mask)
            else:
                y = self.ffn(params["ffn"], h, ctx)
            x = x + y
        return x, new_cache


class Stack(Module):
    """A stack of Blocks with optional remat and a final norm.

    Two parameter layouts:
      * unrolled (default; smoke scale): one params subtree per layer,
        heterogeneous blocks allowed.
      * scanned (cfg.scan_layers; production): all layer params stacked
        with a leading (L,) axis under params['layers'], forward is one
        ``lax.scan`` over layers — HLO size (and compile time) becomes
        O(1) in depth instead of O(L), which is what makes the 48-60 layer
        dry-runs compile.  Per-layer structural differences (gemma3's 5:1
        local:global, hymba's 3 global layers) are expressed by a traced
        ``force_full`` flag vector + lax.cond inside the body, since param
        *shapes* are identical across layers in every assigned arch.
    """

    def __init__(self, cfg, *, path: str, cross: bool = False,
                 n_layers: int | None = None, causal: bool = True):
        self.cfg = cfg
        self.path = path
        self.cross = cross
        self.n_layers = n_layers or cfg.n_layers
        if cfg.scan_layers and cfg.global_yarn is not None:
            raise ValueError(
                "YaRN on the global layers needs the unrolled stack: the "
                "scanned template carries one rotary (scan_layers=False)")
        if cfg.scan_layers:
            # one template block; layer differences via force_full flags
            self.template = Block(cfg, self._template_idx(), path=f"{path}/layers",
                                  cross=cross)
            self.blocks = [self.template]
        else:
            self.blocks = [
                Block(cfg, i, path=f"{path}/layer{i}", cross=cross)
                for i in range(self.n_layers)
            ]
        norm_cls = LayerNorm if cfg.norm == "layernorm" else RMSNorm
        self.final_norm = norm_cls(cfg.d_model, path=f"{path}/final_norm",
                                   dtype=cfg.dtype)

    def _template_idx(self) -> int:
        """Pick a layer index whose window is non-None if any layer has one
        (the template must carry the SWA machinery)."""
        for i in range(self.n_layers):
            if self.cfg.attn_window(i) is not None:
                return i
        return 0

    def _force_full_flags(self):
        """None if all layers share the template's window, else (L,) bool."""
        if not self.cfg.scan_layers:
            return None
        tw = self.cfg.attn_window(self._template_idx())
        flags = [self.cfg.attn_window(i) is None and tw is not None
                 for i in range(self.n_layers)]
        if not any(flags):
            return None
        return jnp.asarray(flags)

    @property
    def scanned(self) -> bool:
        return bool(self.cfg.scan_layers)

    def param_children(self):
        if self.scanned:
            c = {"layers": self.template}
        else:
            c = {f"layer{i}": b for i, b in enumerate(self.blocks)}
        c["final_norm"] = self.final_norm
        return c

    def init(self, key):
        if self.scanned:
            k1, k2 = jax.random.split(key)
            ks = jax.random.split(k1, self.n_layers)
            p = {"layers": jax.vmap(self.template.init)(ks)}
            p["final_norm"] = self.final_norm.init(k2)
            return p
        ks = jax.random.split(key, self.n_layers + 1)
        p = {f"layer{i}": b.init(ks[i]) for i, b in enumerate(self.blocks)}
        p["final_norm"] = self.final_norm.init(ks[-1])
        return p

    # -- scan helpers ---------------------------------------------------------
    def _stack_qparams(self, ctx):
        """Subset of ctx.qparams belonging to the scanned stack (leading L)."""
        if ctx is None:
            return {}
        prefix = self.template.path
        return {p: e for p, e in ctx.qparams.items() if p.startswith(prefix)}

    def _scan_call(self, params, x, ctx, memory, remat):
        from repro.core.api import QuantCtx

        flags = self._force_full_flags()
        qs = self._stack_qparams(ctx)
        mode = ctx.mode if ctx is not None else "none"
        policy = ctx.policy if ctx is not None else None

        from repro.dist.constraints import constrain_activation

        def body(x, xs):
            lp, lq, flag = xs
            # barrier: stops XLA hoisting per-layer transforms of the
            # sliced params (e.g. the fake-quant f32 upcast) out of the
            # loop, which would materialize (L, ...) f32 stacks
            lp = jax.lax.optimization_barrier(lp)
            lctx = None
            if ctx is not None:
                lctx = QuantCtx(mode=mode, policy=policy, qparams=lq)
            # carry enters sequence-sharded (SP residual stack), is
            # gathered for the block, and leaves sequence-sharded again
            x = constrain_activation(x, carry=True)
            y, aux = self.template(lp, constrain_activation(x), lctx,
                                   memory=memory, force_full=flag)
            y = constrain_activation(y, carry=True)
            ys = (aux, lctx.updates if lctx is not None else {})
            return y, ys

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)

        # lax.scan requires every xs leaf to have leading L; flags may be None
        if flags is None:
            def body2(x, xs2):
                lp, lq = xs2
                return body(x, (lp, lq, None))
            x, (auxs, updates) = jax.lax.scan(body2, x, (params["layers"], qs))
        else:
            x, (auxs, updates) = jax.lax.scan(
                body, x, (params["layers"], qs, flags))
        if ctx is not None and mode == "calibrate":
            ctx.updates.update(updates)
        return x, jnp.sum(auxs)

    def __call__(self, params, x, ctx=None, *, memory=None, remat: bool = False):
        if self.scanned:
            x, aux_total = self._scan_call(params, x, ctx, memory, remat)
            return self.final_norm(params["final_norm"], x), aux_total
        aux_total = jnp.zeros((), jnp.float32)
        for i, blk in enumerate(self.blocks):
            if remat:
                fn = jax.checkpoint(
                    lambda p, h, m, _blk=blk: _blk(p, h, ctx, memory=m),
                    policy=jax.checkpoint_policies.nothing_saveable,
                )
                x, aux = fn(params[f"layer{i}"], x, memory)
            else:
                x, aux = blk(params[f"layer{i}"], x, ctx, memory=memory)
            aux_total = aux_total + aux
        return self.final_norm(params["final_norm"], x), aux_total

    # -- serve structure ------------------------------------------------------
    @property
    def serve_homogeneous(self) -> bool:
        """True when every layer shares kind+window (cache shapes equal) —
        the scanned serve path applies (mixtral, mamba2, dense archs)."""
        kinds = {self.cfg.layer_kind(i) for i in range(self.n_layers)}
        wins = {self.cfg.attn_window(i) for i in range(self.n_layers)}
        return len(kinds) == 1 and len(wins) == 1 and not self.cross

    def _serve_blocks(self):
        """Per-layer block views for the unrolled serve path in scan mode.

        All views share the template's path: qparams entries are stacked
        (L, ...) and the serve loop slices both params and qparams per
        layer."""
        if not self.scanned:
            return self.blocks
        if not hasattr(self, "_serve_blocks_cache"):
            self._serve_blocks_cache = [
                Block(self.cfg, i, path=self.template.path, cross=self.cross)
                for i in range(self.n_layers)
            ]
        return self._serve_blocks_cache

    def _layer_view(self, params, ctx, i):
        """(sliced params, sliced ctx) for layer i of a scanned stack."""
        from repro.core.api import QuantCtx

        lp = jax.tree.map(lambda a: a[i], params["layers"])
        lctx = ctx
        if ctx is not None:
            qs = {p: jax.tree.map(lambda a: a[i], e)
                  for p, e in self._stack_qparams(ctx).items()}
            lctx = QuantCtx(mode=ctx.mode, policy=ctx.policy, qparams=qs,
                            updates=ctx.updates)
        return lp, lctx

    # -- caches -------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   kv_int8: bool = False, layout: str = "ring",
                   page_size: int = 64, extra_pages: int = 0,
                   kv_bits: int = 8):
        kw = dict(kv_int8=kv_int8, layout=layout, page_size=page_size,
                  extra_pages=extra_pages, kv_bits=kv_bits)
        if self.scanned and self.serve_homogeneous:
            one = self.template.init_cache(batch, max_len, dtype, **kw)
            # scale leaves init to ones, not zeros: a layer whose prefill
            # never runs (impossible today, defensive) must still dequant
            # to finite values
            return jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (self.n_layers,) + a.shape).astype(a.dtype), one
            )
        blocks = self._serve_blocks() if self.scanned else self.blocks
        return {
            f"layer{i}": b.init_cache(batch, max_len, dtype, **kw)
            for i, b in enumerate(blocks)
        }

    def prefill(self, params, x, cache, ctx=None, *, memory=None,
                q_offset=0, lengths=None, kv_limit=None):
        if self.scanned and self.serve_homogeneous:
            from repro.core.api import QuantCtx

            qs = self._stack_qparams(ctx)
            mode = ctx.mode if ctx is not None else "none"
            policy = ctx.policy if ctx is not None else None

            def body(x, xs):
                lp, lc, lq = xs
                lctx = QuantCtx(mode, policy, lq) if ctx is not None else None
                return self.template.prefill(lp, x, lc, lctx, memory=memory,
                                             q_offset=q_offset,
                                             lengths=lengths,
                                             kv_limit=kv_limit)

            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache, qs))
            return self.final_norm(params["final_norm"], x), new_cache
        if self.scanned:
            new_cache = {}
            for i, blk in enumerate(self._serve_blocks()):
                lp, lctx = self._layer_view(params, ctx, i)
                x, new_cache[f"layer{i}"] = blk.prefill(
                    lp, x, cache[f"layer{i}"], lctx, memory=memory,
                    q_offset=q_offset, lengths=lengths, kv_limit=kv_limit)
            return self.final_norm(params["final_norm"], x), new_cache
        new_cache = {}
        for i, blk in enumerate(self.blocks):
            x, new_cache[f"layer{i}"] = blk.prefill(
                params[f"layer{i}"], x, cache[f"layer{i}"], ctx,
                memory=memory, q_offset=q_offset, lengths=lengths,
                kv_limit=kv_limit,
            )
        return self.final_norm(params["final_norm"], x), new_cache

    def decode(self, params, x, cache, cur_pos, ctx=None, *, memory=None,
               slot_mask=None):
        if self.scanned and self.serve_homogeneous:
            from repro.core.api import QuantCtx

            qs = self._stack_qparams(ctx)
            mode = ctx.mode if ctx is not None else "none"
            policy = ctx.policy if ctx is not None else None

            def body(x, xs):
                lp, lc, lq = xs
                lctx = QuantCtx(mode, policy, lq) if ctx is not None else None
                return self.template.decode(lp, x, lc, cur_pos, lctx,
                                            memory=memory,
                                            slot_mask=slot_mask)

            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache, qs))
            return self.final_norm(params["final_norm"], x), new_cache
        if self.scanned:
            new_cache = {}
            for i, blk in enumerate(self._serve_blocks()):
                lp, lctx = self._layer_view(params, ctx, i)
                x, new_cache[f"layer{i}"] = blk.decode(
                    lp, x, cache[f"layer{i}"], cur_pos, lctx, memory=memory,
                    slot_mask=slot_mask)
            return self.final_norm(params["final_norm"], x), new_cache
        new_cache = {}
        for i, blk in enumerate(self.blocks):
            x, new_cache[f"layer{i}"] = blk.decode(
                params[f"layer{i}"], x, cache[f"layer{i}"], cur_pos, ctx,
                memory=memory, slot_mask=slot_mask,
            )
        return self.final_norm(params["final_norm"], x), new_cache

    def verify(self, params, x, cache, cur_pos, ctx=None, *,
               slot_mask=None):
        """Speculative-verify over the stack: the same three layouts as
        ``decode`` (scanned-homogeneous lax.scan, scanned-unrolled,
        unrolled), with each block running its multi-token per-slot
        verify window."""
        if self.scanned and self.serve_homogeneous:
            from repro.core.api import QuantCtx

            qs = self._stack_qparams(ctx)
            mode = ctx.mode if ctx is not None else "none"
            policy = ctx.policy if ctx is not None else None

            def body(x, xs):
                lp, lc, lq = xs
                lctx = QuantCtx(mode, policy, lq) if ctx is not None else None
                return self.template.verify(lp, x, lc, cur_pos, lctx,
                                            slot_mask=slot_mask)

            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache, qs))
            return self.final_norm(params["final_norm"], x), new_cache
        if self.scanned:
            new_cache = {}
            for i, blk in enumerate(self._serve_blocks()):
                lp, lctx = self._layer_view(params, ctx, i)
                x, new_cache[f"layer{i}"] = blk.verify(
                    lp, x, cache[f"layer{i}"], cur_pos, lctx,
                    slot_mask=slot_mask)
            return self.final_norm(params["final_norm"], x), new_cache
        new_cache = {}
        for i, blk in enumerate(self.blocks):
            x, new_cache[f"layer{i}"] = blk.verify(
                params[f"layer{i}"], x, cache[f"layer{i}"], cur_pos, ctx,
                slot_mask=slot_mask,
            )
        return self.final_norm(params["final_norm"], x), new_cache
