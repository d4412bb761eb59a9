"""Int8 decode fast path: quantized KV cache, fused decode-attention
kernel, scanned serving loop, and decode-shape quant_matmul.

The parity contract: int8-KV decode logits match bf16-KV decode within
atol 0.1 on the smoke config (ISSUE acceptance), the Pallas kernel matches
the jnp oracle to float tolerance, and the scanned loop is token-exact
against the per-token loop (same math, different dispatch).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import api as A
from repro.core import quant as Q
from repro.core.packing import pack_int4
from repro.kernels import decode_attention as DA
from repro.kernels import ops, ref as kref
from repro.launch import steps as ST
from repro.models import build_model

B, S, GEN = 2, 16, 6


def _calibrated(arch="smollm-135m", kv_int8=True, seed=0, **pol):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": toks}
    policy = A.QuantPolicy(kv_int8=kv_int8, **pol)
    qp = A.init_qparams(model, params, policy)
    qp = ST.make_calibrate_step(model, cfg, policy)(params, qp, batch)
    qp = A.finalize_calibration(qp, policy)
    return cfg, model, params, qp, policy, batch


def _greedy_decode(model, cfg, params, qp, policy, batch, *, kv_int8,
                   mode="none"):
    prefill = jax.jit(ST.make_prefill_step(model, cfg, policy, mode=mode))
    step = jax.jit(ST.make_serve_step(model, cfg, policy, mode=mode))
    cache = model.init_cache(B, S + GEN, cfg.dtype, kv_int8=kv_int8)
    logits, cache = prefill(params, qp, batch, cache)
    tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
    outs = []
    for i in range(GEN):
        tok, lg, cache = step(params, qp, tok[:, None], cache, S + i)
        outs.append(lg)
    return jnp.concatenate(outs, axis=1), cache


class TestInt8KVCache:
    def test_kv_qparams_created_and_finalized(self):
        cfg, model, params, qp, policy, _ = _calibrated()
        kv_keys = [p for p in qp if p.endswith("/kv")]
        assert len(kv_keys) == cfg.n_layers
        ent = qp[kv_keys[0]]
        assert set(ent) == {"k", "v"}
        assert ent["k"]["t_max"].shape == (cfg.n_kv_heads,)
        assert float(jnp.min(ent["k"]["t_max"])) > 0

    def test_int8_kv_decode_parity_vs_bf16_kv(self):
        """ISSUE acceptance: int8-KV decode logits within atol 0.1 of
        bf16-KV decode (fp weights isolate the KV quantization error)."""
        cfg, model, params, qp, policy, batch = _calibrated()
        lg8, cache8 = _greedy_decode(model, cfg, params, qp, policy, batch,
                                     kv_int8=True)
        lg16, _ = _greedy_decode(model, cfg, params, qp, policy, batch,
                                 kv_int8=False)
        np.testing.assert_allclose(
            np.asarray(lg8, np.float32), np.asarray(lg16, np.float32),
            atol=0.1)
        # the cache really is int8 + scales
        assert cache8["layer0"]["attn"]["k"].dtype == jnp.int8
        assert cache8["layer0"]["attn"]["k_scale"].shape == (cfg.n_kv_heads,)

    def test_int8_weights_plus_int8_kv_end_to_end(self):
        cfg, model, params, qp, policy, batch = _calibrated()
        p8 = A.convert_to_int8(model, params, qp, policy)
        lg, cache = _greedy_decode(model, cfg, p8, qp, policy, batch,
                                   kv_int8=True, mode="int8")
        assert not bool(jnp.any(jnp.isnan(lg)))
        n8 = sum(1 for l in jax.tree.leaves(cache) if l.dtype == jnp.int8)
        assert n8 == 2 * cfg.n_layers  # k and v per layer

    def test_missing_kv_thresholds_raises(self):
        cfg, model, params, qp, policy, batch = _calibrated(kv_int8=False)
        prefill = ST.make_prefill_step(model, cfg,
                                       A.QuantPolicy(kv_int8=True),
                                       mode="none")
        cache = model.init_cache(B, S + GEN, cfg.dtype, kv_int8=True)
        with pytest.raises(ValueError, match="kv thresholds"):
            prefill(params, qp, batch, cache)


class TestDecodeAttentionKernel:
    @pytest.mark.parametrize("pos", [1, 7, 16, 39, 40])
    def test_matches_oracle_int8(self, pos):
        rng = np.random.default_rng(0)
        b, s, kv, g, d = 2, 40, 3, 4, 16
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)), jnp.int8)
        ks = jnp.asarray(np.abs(rng.normal(size=(kv,))) * 0.02 + 0.01,
                         jnp.float32)
        vs = jnp.asarray(np.abs(rng.normal(size=(kv,))) * 0.02 + 0.01,
                         jnp.float32)
        got = ops.decode_attention(q, k, v, ks, vs, jnp.int32(pos),
                                   block_s=16)
        want = kref.decode_attention_ref(q, k, v, ks, vs, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_oracle_per_slot_positions(self):
        """Vector cur_pos (continuous batching): every batch row masks its
        own valid prefix, including a 0-entry inactive slot that must
        return exact zeros."""
        rng = np.random.default_rng(2)
        b, s, kv, g, d = 4, 48, 3, 4, 16
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)), jnp.int8)
        ks = jnp.asarray(np.abs(rng.normal(size=(kv,))) * 0.02 + 0.01,
                         jnp.float32)
        vs = jnp.asarray(np.abs(rng.normal(size=(kv,))) * 0.02 + 0.01,
                         jnp.float32)
        pos = jnp.asarray([48, 17, 0, 5], jnp.int32)
        got = ops.decode_attention(q, k, v, ks, vs, pos, block_s=16)
        want = kref.decode_attention_ref(q, k, v, ks, vs, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # the inactive slot (pos == 0) is exactly zero, not NaN/uniform
        np.testing.assert_array_equal(np.asarray(got)[2], 0.0)

    def test_vector_pos_rows_match_scalar_pos(self):
        """Row b of a vector-pos call equals a scalar-pos call at that
        row's position — per-slot masking is exact row-wise slicing."""
        rng = np.random.default_rng(3)
        b, s, kv, g, d = 3, 32, 2, 2, 8
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)), jnp.int8)
        ones = jnp.ones((kv,), jnp.float32)
        pos = [31, 8, 1]
        got = ops.decode_attention(q, k, v, ones, ones,
                                   jnp.asarray(pos, jnp.int32), block_s=8)
        for r, p in enumerate(pos):
            want = ops.decode_attention(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                        ones, ones, jnp.int32(p), block_s=8)
            np.testing.assert_allclose(np.asarray(got)[r],
                                       np.asarray(want)[0],
                                       rtol=1e-6, atol=1e-6)

    def test_bf16_cache_scales_of_one(self):
        """The same kernel serves an unquantized cache with unit scales."""
        rng = np.random.default_rng(1)
        b, s, kv, g, d = 1, 32, 2, 2, 8
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.bfloat16)
        ones = jnp.ones((kv,), jnp.float32)
        got = ops.decode_attention(q, k, v, ones, ones, jnp.int32(17))
        want = kref.decode_attention_ref(q, k, v, ones, ones, 17)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-3)

    # one row at each edge of the tile skip: empty, one key, a whole
    # tile, one key into the next, the whole cache (S = 3 tiles of 16)
    RAGGED = [0, 1, 16, 17, 48]

    @staticmethod
    def _ragged_case(layout, seed=5):
        """(kernel output, oracle) over rows at ``RAGGED`` positions
        through one entry point: the dense cache, a shuffled page pool,
        or packed int4 tiles."""
        rng = np.random.default_rng(seed)
        b, s, kv, g, d, bs = len(TestDecodeAttentionKernel.RAGGED), 48, 2, \
            3, 16, 16
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        ks = jnp.asarray(np.abs(rng.normal(size=(kv,))) * 0.02 + 0.01,
                         jnp.float32)
        vs = jnp.asarray(np.abs(rng.normal(size=(kv,))) * 0.02 + 0.01,
                         jnp.float32)
        pos = jnp.asarray(TestDecodeAttentionKernel.RAGGED, jnp.int32)
        bits = 4 if layout == "int4" else 8
        lim = 7 if bits == 4 else 127
        k, v = (jnp.asarray(rng.integers(-lim, lim + 1, size=(b, s, kv, d)),
                            jnp.int8) for _ in range(2))
        want = kref.decode_attention_ref(q, k, v, ks, vs, pos)
        if layout == "int4":
            got = ops.decode_attention(q, pack_int4(k), pack_int4(v), ks,
                                       vs, pos, block_s=bs, kv_bits=4)
        elif layout == "dense":
            got = ops.decode_attention(q, k, v, ks, vs, pos, block_s=bs)
        else:
            # the same logical blocks at shuffled pages of one pool
            n = s // bs
            perm = rng.permutation(b * n)
            pool = lambda c: jnp.zeros((b * n, bs, kv, d), c.dtype).at[
                perm].set(c.reshape(b * n, bs, kv, d))
            tab = jnp.asarray(perm.reshape(b, n), jnp.int32)
            got = DA.decode_attention_tiles(q, pool(k), pool(v), tab, ks, vs,
                                            pos, interpret=True)
        return np.asarray(got), np.asarray(want)

    @pytest.mark.parametrize("layout", ["dense", "paged", "int4"])
    def test_ragged_positions_match_oracle(self, layout):
        """Rows at 0, 1, block_s, block_s + 1 and S keys in one batch: the
        tile skip reads exactly each row's live tiles, on every entry
        point; the empty row is exact zeros."""
        got, want = self._ragged_case(layout)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(got[0], 0.0)

    def test_dead_tiles_repeat_the_last_live_page(self):
        """The K/V index map's clamp: past a row's last live tile the
        page repeats, so the pipeline elides the copy; an empty row
        stays on its first page."""
        tab = 100 + np.arange(15, dtype=np.int32).reshape(5, 3)
        pos = np.asarray(self.RAGGED, np.int32)
        pages = [[int(DA._kv_index(bi, si, tab, pos, block_s=16, n_s=3)[0])
                  for si in range(3)] for bi in range(5)]
        assert pages == [[100, 100, 100], [103, 103, 103],
                         [106, 106, 106], [109, 110, 110],
                         [112, 113, 114]]

    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_dead_tiles_are_never_read(self, layout):
        """Whole tiles past each row's last live tile hold NaN (a paged
        row's table past it points at a NaN page): the output stays
        finite and equals the oracle on a clean cache, so no dead tile
        reaches the softmax."""
        rng = np.random.default_rng(6)
        b, s, kv, g, d, bs = len(self.RAGGED), 48, 2, 3, 16, 16
        n = s // bs
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k, v = (rng.normal(size=(b, s, kv, d)).astype(np.float32)
                for _ in range(2))
        ones = jnp.ones((kv,), jnp.float32)
        pos = np.asarray(self.RAGGED, np.int32)
        want = kref.decode_attention_ref(q, jnp.asarray(k), jnp.asarray(v),
                                         ones, ones, jnp.asarray(pos))
        live = -(-pos // bs)                  # live tiles a row
        if layout == "dense":
            for r, t in enumerate(live):
                k[r, t * bs:] = np.nan
                v[r, t * bs:] = np.nan
            got = ops.decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                       ones, ones, jnp.asarray(pos),
                                       block_s=bs)
        else:
            # pages 0..b*n-1 hold the rows' blocks, page b*n is all NaN
            tab = np.arange(b * n, dtype=np.int32).reshape(b, n)
            for r, t in enumerate(live):
                tab[r, t:] = b * n
            nan = np.full((1, bs, kv, d), np.nan, np.float32)
            pool = lambda c: jnp.asarray(np.concatenate(
                [c.reshape(b * n, bs, kv, d), nan]))
            got = DA.decode_attention_tiles(q, pool(k), pool(v),
                                            jnp.asarray(tab), ones, ones,
                                            jnp.asarray(pos),
                                            interpret=True)
        got = np.asarray(got)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5,
                                   atol=1e-5)

    def test_int8_mode_pallas_matches_xla(self):
        """_int8_matmul's use_pallas branch (raw x + act_scale into the
        kernel's fused quantize) must match the XLA int8 path exactly —
        guards the double-quantize fix."""
        cfg, model, params, qp, policy, batch = _calibrated()
        p8 = A.convert_to_int8(model, params, qp, policy)
        out_xla, _ = model(p8, batch, A.make_ctx("int8", policy, qp))
        pol_p = A.QuantPolicy(kv_int8=True, use_pallas=True)
        out_pal, _ = model(p8, batch, A.make_ctx("int8", pol_p, qp))
        np.testing.assert_allclose(
            np.asarray(out_pal, np.float32), np.asarray(out_xla, np.float32),
            atol=2e-2)

    def test_in_model_decode_matches_jnp_path(self):
        """policy.use_pallas routes decode through the fused kernel; logits
        must match the dequantize-then-jnp reference path."""
        cfg, model, params, qp, policy, batch = _calibrated()
        lg_jnp, _ = _greedy_decode(model, cfg, params, qp, policy, batch,
                                   kv_int8=True)
        pol_pallas = A.QuantPolicy(kv_int8=True, use_pallas=True)
        lg_pal, _ = _greedy_decode(model, cfg, params, qp, pol_pallas, batch,
                                   kv_int8=True)
        np.testing.assert_allclose(
            np.asarray(lg_pal, np.float32), np.asarray(lg_jnp, np.float32),
            atol=2e-2)


class TestQuantMatmulDecodeShapes:
    @pytest.mark.parametrize("m", [1, 2, 3, 5, 7, 8])
    def test_non_tile_m(self, m):
        """Decode activations are (B*1, K) with tiny ragged M; the kernel
        pads to a sublane tile instead of asserting."""
        rng = np.random.default_rng(m)
        k, n = 64, 32
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        spec = Q.QuantSpec(bits=8, symmetric=True, per_channel=True,
                           channel_axis=-1)
        t_w = Q.max_abs_threshold(w, spec)
        w_q, w_scale = Q.quantize_weights_int8(w, t_w, jnp.ones_like(t_w),
                                               spec)
        act_scale = jnp.float32(127.0 / 3.0)
        comb = (w_scale / act_scale).astype(jnp.float32)
        got = ops.quant_matmul(x, w_q, comb, act_scale,
                               out_dtype=jnp.float32)
        want = kref.quant_matmul_ref(x, w_q, comb, act_scale,
                                     out_dtype=jnp.float32)
        assert got.shape == (m, n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestScannedDecodeLoop:
    def test_scan_matches_python_loop_tokens(self):
        """One compiled lax.scan call == N python-loop dispatches, exactly
        (same ops in the same order — only the dispatch changes)."""
        cfg, model, params, qp, policy, batch = _calibrated()
        p8 = A.convert_to_int8(model, params, qp, policy)
        prefill = jax.jit(ST.make_prefill_step(model, cfg, policy))
        step = jax.jit(ST.make_serve_step(model, cfg, policy))
        loop = jax.jit(ST.make_decode_loop(model, cfg, policy,
                                           n_steps=GEN))
        cache0 = model.init_cache(B, S + GEN, cfg.dtype, kv_int8=True)
        logits, cache = prefill(p8, qp, batch, cache0)
        tok0 = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)

        toks_loop = [tok0]
        c = cache
        for i in range(GEN - 1):
            nxt, _, c = step(p8, qp, toks_loop[-1][:, None], c, S + i)
            toks_loop.append(nxt)
        toks_loop = jnp.stack(toks_loop, axis=1)

        toks_scan, c_scan = loop(p8, qp, tok0, cache, S)
        np.testing.assert_array_equal(np.asarray(toks_scan),
                                      np.asarray(toks_loop))
        # final caches agree too (same writes)
        for a, b in zip(jax.tree.leaves(c_scan), jax.tree.leaves(c)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-5)
