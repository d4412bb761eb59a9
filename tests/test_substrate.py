"""Substrate tests: data pipeline determinism, checkpoint/restart fault
tolerance, optimizer recipe, gradient-compression collective."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.data import pipeline as DP
from repro.optim.adam import (adam_init, adam_update, cosine_restarts,
                              reset_moments, restart_boundary)


class TestDataPipeline:
    def test_deterministic_and_resumable(self):
        spec = DP.PipelineSpec(vocab=1000, seq_len=32, global_batch=4)
        a = DP.make_batch(spec, 7)
        b = DP.make_batch(spec, 7)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        c = DP.make_batch(spec, 8)
        assert not np.array_equal(a["tokens"], c["tokens"])

    def test_calibration_disjoint_from_training(self):
        spec = DP.PipelineSpec(vocab=1000, seq_len=32, global_batch=4)
        cal = DP.calibration_batches(spec, 2)
        train = [DP.make_batch(spec, i) for i in range(2)]
        for cb in cal:
            for tb in train:
                assert not np.array_equal(cb["tokens"], tb["tokens"])

    def test_zipf_marginal_is_skewed(self):
        spec = DP.PipelineSpec(vocab=1000, seq_len=256, global_batch=8)
        toks = np.asarray(DP.make_batch(spec, 0)["tokens"]).ravel()
        # low ids should dominate (Zipf) — token 0..9 occupy > 30%
        frac = np.mean(toks < 10)
        assert frac > 0.3, frac

    def test_modalities(self):
        spec = DP.PipelineSpec(vocab=100, seq_len=32, global_batch=2,
                               modality="vlm", mm_patches=8, mm_dim=16)
        b = DP.make_batch(spec, 0)
        assert b["patches"].shape == (2, 8, 16)
        assert b["tokens"].shape == (2, 24)
        spec = DP.PipelineSpec(vocab=100, seq_len=32, global_batch=2,
                               modality="audio", frame_dim=12)
        b = DP.make_batch(spec, 0)
        assert b["frames"].shape == (2, 32, 12)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"a": jnp.arange(5), "b": {"c": jnp.ones((2, 3))}}
        mgr.save(10, tree, {"note": "x"})
        got, meta = mgr.restore_latest()
        assert meta["step"] == 10
        np.testing.assert_array_equal(got["a"], tree["a"])
        np.testing.assert_array_equal(got["b"]["c"], tree["b"]["c"])

    def test_keep_n_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, {"x": jnp.asarray(s)})
        assert mgr.list_steps() == [3, 4]

    def test_incomplete_checkpoint_ignored(self, tmp_path):
        """A crash mid-write must not corrupt restore (atomicity)."""
        mgr = CheckpointManager(str(tmp_path), keep=3)
        mgr.save(1, {"x": jnp.asarray(1)})
        # simulate a torn write: directory without COMMITTED marker
        os.makedirs(tmp_path / "ckpt_0000000002")
        got, meta = mgr.restore_latest()
        assert meta["step"] == 1

    def test_restart_resumes_training_exactly(self, tmp_path):
        """Kill-and-restart reproduces the uninterrupted run bit-for-bit:
        the checkpoint carries optimizer state + data position."""
        from repro.core import api as A
        from repro.launch import steps as ST
        from repro.models import build_model

        cfg = get_config("smollm-135m", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        policy = A.QuantPolicy()
        spec = DP.spec_for(cfg, ShapeSpec("t", "train", 32, 4))
        qp = A.init_qparams(model, params, policy)
        calib = ST.make_calibrate_step(model, cfg, policy)
        for b in DP.calibration_batches(spec, 2):
            qp = calib(params, qp, b)
        qp = A.finalize_calibration(qp, policy)
        step_fn = jax.jit(ST.make_fat_train_step(model, cfg, policy))

        # uninterrupted: 4 steps
        qp_a, opt_a = qp, adam_init(qp)
        for s in range(4):
            qp_a, opt_a, _ = step_fn(params, qp_a, opt_a, DP.make_batch(spec, s))

        # interrupted at step 2 + restart from checkpoint
        mgr = CheckpointManager(str(tmp_path))
        qp_b, opt_b = qp, adam_init(qp)
        for s in range(2):
            qp_b, opt_b, _ = step_fn(params, qp_b, opt_b, DP.make_batch(spec, s))
        mgr.save(2, {"qparams": qp_b,
                     "opt": {"step": opt_b.step, "mu": opt_b.mu,
                             "nu": opt_b.nu}})
        tree, meta = mgr.restore_latest()
        from repro.optim.adam import AdamState
        qp_c = jax.tree.map(jnp.asarray, tree["qparams"])
        opt_c = AdamState(step=jnp.asarray(tree["opt"]["step"]),
                          mu=jax.tree.map(jnp.asarray, tree["opt"]["mu"]),
                          nu=jax.tree.map(jnp.asarray, tree["opt"]["nu"]))
        for s in range(meta["step"], 4):
            qp_c, opt_c, _ = step_fn(params, qp_c, opt_c, DP.make_batch(spec, s))

        for la, lc in zip(jax.tree.leaves(qp_a), jax.tree.leaves(qp_c)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lc),
                                       rtol=1e-6, atol=1e-7)


class TestOptimizer:
    def test_cosine_restarts_shape(self):
        lr0 = float(cosine_restarts(jnp.asarray(0), 1e-3, 100))
        lr50 = float(cosine_restarts(jnp.asarray(50), 1e-3, 100))
        lr100 = float(cosine_restarts(jnp.asarray(100), 1e-3, 100))
        assert lr0 == pytest.approx(1e-3)
        assert lr50 == pytest.approx(5e-4, rel=1e-3)
        assert lr100 == pytest.approx(1e-3)  # restart

    def test_restart_boundary_and_moment_reset(self):
        assert restart_boundary(100, 100)
        assert not restart_boundary(50, 100)
        opt = adam_init({"x": jnp.ones(3)})
        g = {"x": jnp.ones(3)}
        _, opt = adam_update(g, opt, {"x": jnp.ones(3)}, 1e-3)
        assert float(jnp.sum(jnp.abs(opt.mu["x"]))) > 0
        opt2 = reset_moments(opt)
        assert float(jnp.sum(jnp.abs(opt2.mu["x"]))) == 0

    def test_mask_freezes_leaves(self):
        params = {"train": jnp.ones(3), "frozen": jnp.ones(3)}
        grads = {"train": jnp.ones(3), "frozen": jnp.ones(3)}
        mask = {"train": True, "frozen": False}
        opt = adam_init(params)
        new_p, _ = adam_update(grads, opt, params, 1e-2, mask=mask)
        assert not np.allclose(new_p["train"], params["train"])
        np.testing.assert_array_equal(new_p["frozen"], params["frozen"])


class TestCompressedCollective:
    def test_compressed_psum_close_to_exact(self):
        """int8 gradient compression: mean-reduced grads within one
        quantization step of the exact reduction."""
        from repro.dist.collectives import compressed_psum
        from repro.dist.compat import make_mesh, shard_map

        devs = jax.devices()
        if len(devs) < 1:
            pytest.skip("no devices")
        mesh = make_mesh((1,), ("d",))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(16,)),
                        jnp.float32)

        f = shard_map(
            lambda x: compressed_psum(x, "d"), mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),),
            out_specs=jax.sharding.PartitionSpec(),
        )

        got = f(x)
        step = float(jnp.max(jnp.abs(x))) / 127
        np.testing.assert_allclose(np.asarray(got), np.asarray(x),
                                   atol=step / 2 + 1e-7)


class TestCompressedPsumParity:
    """compressed_psum vs plain jax.lax.psum across shard counts.

    The {2, 4}-way cases need a multi-device host
    (XLA_FLAGS=--xla_force_host_platform_device_count=4 — the CI
    ``sharded`` lane); on a single-device run they skip rather than
    fake the mesh.
    """

    def _reduce(self, fn, n, x):
        from repro.dist.compat import make_mesh, shard_map

        mesh = make_mesh((n,), ("d",))
        P = jax.sharding.PartitionSpec
        return shard_map(fn, mesh=mesh, in_specs=(P("d"),),
                         out_specs=P())(x)

    @pytest.mark.parametrize("n", [1, 2, 4])
    def test_float_sum_parity_vs_plain_psum(self, n):
        """Quantized-wire sum within n * step/2 of the exact psum: each
        shard contributes at most half a quantization step of error, and
        the shared-threshold pmax guarantees every shard uses the SAME
        step (so the bound is additive, not multiplicative)."""
        from repro.dist.collectives import compressed_psum

        if jax.device_count() < n:
            pytest.skip(f"needs {n} devices")
        x = jnp.asarray(np.random.default_rng(n).normal(size=(n, 64)),
                        jnp.float32)
        exact = self._reduce(lambda x: jax.lax.psum(x, "d"), n, x)
        got = self._reduce(
            lambda x: compressed_psum(x, "d", mean=False), n, x)
        step = float(jnp.max(jnp.abs(x))) / 127
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   atol=n * step / 2 + 1e-7)

    @pytest.mark.parametrize("n", [1, 2])
    def test_zero_payload_reduces_to_exact_zero(self, n):
        """All-zero input hits the 1e-8 threshold floor: every quantized
        payload is 0 and the output is EXACTLY zero (no floor leakage)."""
        from repro.dist.collectives import compressed_psum

        if jax.device_count() < n:
            pytest.skip(f"needs {n} devices")
        x = jnp.zeros((n, 8), jnp.float32)
        got = self._reduce(
            lambda x: compressed_psum(x, "d", mean=False), n, x)
        assert np.all(np.asarray(got) == 0.0)

    def test_nan_shard_cannot_poison_the_reduction(self):
        """One shard's NaN payload quantizes as 0 and its NaNs stay out
        of the shared-threshold pmax: the reduction returns the OTHER
        shard's contribution, finite, within one quantization step."""
        from repro.dist.collectives import compressed_psum

        if jax.device_count() < 2:
            pytest.skip("needs 2 devices")
        good = np.random.default_rng(7).normal(size=(1, 16))
        x = jnp.asarray(np.concatenate(
            [good, np.full((1, 16), np.nan)]), jnp.float32)
        got = np.asarray(self._reduce(
            lambda x: compressed_psum(x, "d", mean=False), 2, x))
        assert np.all(np.isfinite(got))
        step = float(np.max(np.abs(good))) / 127
        np.testing.assert_allclose(got, good, atol=step + 1e-7)

    def test_integer_fast_path_is_bit_exact(self):
        """int32 accumulators ride the wire as-is: the reduce is integer
        addition, bit-identical to the unsharded sum."""
        from repro.dist.collectives import compressed_psum

        n = min(2, jax.device_count())
        x = jnp.asarray(np.random.default_rng(3).integers(
            -(2**20), 2**20, size=(n, 32)), jnp.int32)
        got = self._reduce(
            lambda x: compressed_psum(x, "d", mean=False), n, x)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(x).sum(0, keepdims=True))
        assert got.dtype == jnp.int32

    def test_integer_mean_rejected(self):
        """mean=True over an integer payload would truncate — refused."""
        from repro.dist.collectives import compressed_psum

        with pytest.raises(ValueError, match="integer payloads"):
            compressed_psum(jnp.zeros((4,), jnp.int32), "d", mean=True)


class TestShardingSpecs:
    """cache_specs 'seq' layout + divisibility guards (pure spec-tree
    logic: no mesh, no devices)."""

    def _cache(self, S):
        return {"k": jnp.zeros((2, S, 2, 8), jnp.int8),
                "v": jnp.zeros((2, S, 2, 8), jnp.int8),
                "k_scale": jnp.zeros((2,), jnp.float32),
                "pos": jnp.zeros((2,), jnp.int32)}

    def test_seq_layout_shards_kv_sequence_axis(self):
        from repro.dist.sharding import P, ShardingRules, cache_specs

        rules = ShardingRules(kv_cache_layout="seq", model_axis_size=4)
        specs = cache_specs(self._cache(32), rules, 4)
        assert specs["k"] == P(None, "model", None, None)
        assert specs["v"] == P(None, "model", None, None)
        assert specs["k_scale"] == P()
        assert specs["pos"] == P()

    def test_seq_layout_indivisible_falls_back_to_batch(self):
        """S % model_size != 0: the 'seq' knob degrades to the batch
        layout instead of emitting an unshardable spec."""
        from repro.dist.sharding import P, ShardingRules, cache_specs

        rules = ShardingRules(kv_cache_layout="seq", model_axis_size=4)
        specs = cache_specs(self._cache(30), rules, 4)
        assert specs["k"] == P("data", None, None, None)

    def test_sp_cache_specs_rejects_indivisible_sequence(self):
        """The serving wrapper REFUSES indivisible S outright — a silent
        batch-layout fallback would break the SP attention contract."""
        from repro.dist.sharding import sp_cache_specs

        with pytest.raises(ValueError, match="not divisible by sp"):
            sp_cache_specs(self._cache(30), sp=4)

    def test_sp_cache_specs_seq_layout(self):
        from repro.dist.sharding import P, sp_cache_specs

        specs = sp_cache_specs(self._cache(32), sp=4)
        assert specs["k"] == P(None, "model", None, None)
        assert specs["k_scale"] == P()

    def test_multipod_batch_axis_and_divisibility(self):
        """multipod(): batch rides ('pod', 'data'); the model-axis
        divisibility guard still replicates indivisible params."""
        from repro.dist.sharding import (P, ShardingRules, batch_specs,
                                         multipod, param_specs)

        rules = multipod(ShardingRules(model_axis_size=16))
        assert rules.act_batch == ("pod", "data")
        bspec = batch_specs({"tokens": jnp.zeros((4, 8), jnp.int32)}, rules)
        assert bspec["tokens"] == P(("pod", "data"), None)
        pspec = param_specs(None, {"w": jnp.zeros((8, 32)),
                                   "odd": jnp.zeros((8, 30))}, rules)
        assert pspec["w"] == P(None, "model")
        assert pspec["odd"] == P()   # 30 % 16 != 0 -> replicate


class TestConstrainActivation:
    """constrain_activation must be a no-op — never a raise — in traced
    contexts without installed rules and inside shard_map bodies."""

    def test_traced_without_rules_is_identity(self):
        from repro.dist import constraints

        prev = constraints.installed()
        constraints.install(None)
        try:
            @jax.jit
            def f(x):
                return constraints.constrain_activation(x, carry=True)

            x = jnp.ones((2, 4, 8), jnp.float32)
            np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))
        finally:
            constraints.install(prev)

    def test_inside_shard_map_stands_down(self):
        """Rules installed + manual mesh axes: the constraint detects the
        shard_map body and passes through instead of erroring on
        already-manual axes."""
        from repro.dist import constraints
        from repro.dist.compat import make_mesh, shard_map
        from repro.dist.sharding import ShardingRules

        prev = constraints.installed()
        constraints.install(ShardingRules(act_batch="d", act_seq="d",
                                          tensor="d", model_axis_size=1))
        try:
            mesh = make_mesh((1,), ("d",))
            P = jax.sharding.PartitionSpec

            def body(x):
                return constraints.constrain_activation(x, carry=True)

            f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),),
                                  out_specs=P()))
            x = jnp.ones((2, 4, 8), jnp.float32)
            np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))
        finally:
            constraints.install(prev)


class TestCompileCache:
    """The entry points' persistent compile cache: the environment's
    directory when set (JAX reads it itself), else a fixed directory in
    the checkout — never a per-run path, which would never hit."""

    def _enable(self, monkeypatch, env):
        from repro.launch import compile_cache

        updates = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        if env is None:
            monkeypatch.delenv(compile_cache.ENV, raising=False)
        else:
            monkeypatch.setenv(compile_cache.ENV, env)
        return compile_cache, compile_cache.enable(), updates

    def test_environment_directory_is_left_to_jax(self, monkeypatch,
                                                  tmp_path):
        _, path, updates = self._enable(monkeypatch, str(tmp_path))
        assert path == str(tmp_path) and updates == {}

    def test_default_is_fixed_checkout_directory(self, monkeypatch):
        cc, path, updates = self._enable(monkeypatch, None)
        assert path == str(cc.CHECKOUT / ".jax_cache")
        assert (cc.CHECKOUT / "chip_smoke.py").exists()
        assert updates == {"jax_compilation_cache_dir": path}
        assert self._enable(monkeypatch, None)[1] == path
