"""Sharded serving parity suite (ISSUE 10 acceptance).

Tensor-parallel (tp=2) and sequence-parallel (sp=2) engines must produce
TOKEN-IDENTICAL greedy output to the unsharded engine across every
serving path — one-shot prefill, chunked/scheduler prefill, decode,
preemption resume, speculative decode, journal-replay recovery — and the
post-optimization HLO of the sharded executables must move only integer
all-reduce payloads (the int8-on-the-wire contract,
launch/hlo_analysis.py::check_integer_all_reduces).

Multi-device tests need ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(the CI ``sharded`` lane sets it); on a single-device host they skip.
The partial-softmax kernel/merge tests run everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config

S, GEN = 16, 8
NDEV = jax.device_count()

needs2 = pytest.mark.skipif(
    NDEV < 2, reason="needs 2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _cfg():
    # shard-divisible head grid (the smoke preset's 3 heads can't split)
    cfg = get_config("smollm-135m", smoke=True)
    return cfg.replace(n_heads=4, n_kv_heads=2, head_dim=cfg.head_dim)


def _toks(cfg, b=3):
    return jax.random.randint(jax.random.PRNGKey(1), (b, S), 0, cfg.vocab)


def _requests(toks):
    from repro.launch.scheduler import Request

    return [Request(rid=r, tokens=np.asarray(toks[r % toks.shape[0], :n]),
                    max_gen=GEN) for r, n in enumerate([S, S - 5, 9])]


def _by_rid(completions):
    return {c.rid: (c.status, tuple(int(t) for t in c.tokens))
            for c in completions}


@pytest.fixture(scope="module")
def engines():
    """(unsharded, tp=2, sp=2) engines over identical weights/thresholds
    — from_checkpoint is seed-deterministic, so the three builds share
    params bit-for-bit."""
    if NDEV < 2:
        pytest.skip("needs 2 devices")
    from repro.launch.engine import Engine
    from repro.shard.engine import ShardedEngine

    kw = dict(cfg=_cfg(), smoke=True, cache_layout="dense",
              use_pallas=False)
    return (Engine.from_checkpoint("smollm-135m", **kw),
            ShardedEngine.from_checkpoint("smollm-135m", tp=2, **kw),
            ShardedEngine.from_checkpoint("smollm-135m", sp=2, **kw))


class TestTokenParity:
    @needs2
    @pytest.mark.parametrize("which", ["tp", "sp"])
    def test_one_shot_prefill_decode(self, engines, which):
        base, tp2, sp2 = engines
        sharded = tp2 if which == "tp" else sp2
        batch = {"tokens": _toks(base.cfg)}
        want = base.generate_batch(batch, GEN, prompt_len=S).tokens
        got = sharded.generate_batch(batch, GEN, prompt_len=S).tokens
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @needs2
    def test_one_shot_prefill_decode_kernels(self):
        """The fused-kernel path (the TPU default): sp=2 prefill attends
        the prompt through the same prefill kernel as one device, and the
        decode partials merge back to the unsharded kernel's tokens."""
        from repro.launch.engine import Engine
        from repro.shard.engine import ShardedEngine

        kw = dict(cfg=_cfg(), smoke=True, cache_layout="dense",
                  use_pallas=True)
        base = Engine.from_checkpoint("smollm-135m", **kw)
        sp2 = ShardedEngine.from_checkpoint("smollm-135m", sp=2, **kw)
        batch = {"tokens": _toks(base.cfg)}
        want = base.generate_batch(batch, GEN, prompt_len=S).tokens
        got = sp2.generate_batch(batch, GEN, prompt_len=S).tokens
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @needs2
    @pytest.mark.parametrize("which", ["tp", "sp"])
    def test_scheduler_continuous_batching(self, engines, which):
        """Ragged admission through 2 slots: chunked prefill + slot
        decode blocks, all under shard_map, token-identical."""
        base, tp2, sp2 = engines
        sharded = tp2 if which == "tp" else sp2
        toks = _toks(base.cfg)
        want = _by_rid(base.generate(_requests(toks), max_slots=2,
                                     block_steps=3))
        got = _by_rid(sharded.generate(_requests(toks), max_slots=2,
                                       block_steps=3))
        assert got == want
        # the no-retrace contract survives sharding
        counts = sharded.make_scheduler(
            max_slots=2, prompt_cap=S, gen_cap=GEN,
            block_steps=3).executable_counts()
        assert all(v <= 1 for v in counts.values()), counts

    @needs2
    @pytest.mark.parametrize("which", ["tp", "sp"])
    def test_preemption_resume(self, engines, which, tmp_path):
        """A forced preemption re-admits through the resume prefill —
        the re-prefill of prompt+generated must reproduce the exact
        unsharded continuation."""
        from repro.launch.faults import FaultPlan
        from repro.launch.scheduler import SlotScheduler

        base, tp2, sp2 = engines
        sharded = tp2 if which == "tp" else sp2
        toks = _toks(base.cfg)
        plan = FaultPlan(preempt=((1, 0),))
        out = {}
        for key, eng in (("base", base), ("sharded", sharded)):
            sched = SlotScheduler(
                eng.model, eng.cfg, eng.policy, eng.serve_params,
                eng.qparams, mode=eng.mode, max_slots=2, prompt_cap=S,
                gen_cap=GEN, prefill_chunk=8, block_steps=3,
                fault_plan=plan)
            out[key] = _by_rid(sched.run(_requests(toks)))
        assert out["sharded"] == out["base"]

    @needs2
    @pytest.mark.parametrize("which", ["tp", "sp"])
    def test_speculative_decode(self, engines, which):
        """Prompt-lookup speculative decoding (draft + batched verify)
        under shard_map — still bit-identical to greedy."""
        from repro.launch.scheduler import SlotScheduler

        base, tp2, sp2 = engines
        sharded = tp2 if which == "tp" else sp2
        toks = _toks(base.cfg)
        out = {}
        for key, eng in (("base", base), ("sharded", sharded)):
            sched = SlotScheduler(
                eng.model, eng.cfg, eng.policy, eng.serve_params,
                eng.qparams, mode=eng.mode, max_slots=2, prompt_cap=S,
                gen_cap=GEN, prefill_chunk=8, block_steps=3,
                strategy="speculative", spec_k=3)
            out[key] = _by_rid(sched.run(_requests(toks)))
        assert out["sharded"] == out["base"]

    @needs2
    @pytest.mark.parametrize("which", ["tp", "sp"])
    def test_journal_crash_recovery(self, engines, which, tmp_path):
        """Crash mid-run, journal-replay on a FRESH sharded scheduler:
        the durability story holds under shard_map (thresholds frozen,
        so the sharded int8 cache recomputes from journaled tokens)."""
        from repro.launch.faults import FaultPlan, SimulatedCrash
        from repro.launch.scheduler import SlotScheduler

        base, tp2, sp2 = engines
        sharded = tp2 if which == "tp" else sp2
        toks = _toks(base.cfg)
        clean = _by_rid(base.generate(_requests(toks), max_slots=2,
                                      block_steps=3))

        def sched(**kw):
            return SlotScheduler(
                sharded.model, sharded.cfg, sharded.policy,
                sharded.serve_params, sharded.qparams, mode=sharded.mode,
                max_slots=2, prompt_cap=S, gen_cap=GEN, prefill_chunk=8,
                block_steps=3, **kw)

        jp = str(tmp_path / f"{which}.jsonl")
        with pytest.raises(SimulatedCrash):
            sched(journal=jp,
                  fault_plan=FaultPlan(crash=(2,))).run(_requests(toks))
        assert _by_rid(sched(journal=jp).recover()) == clean


class TestInterconnectContract:
    @needs2
    def test_tp_all_reduces_are_integer(self, engines):
        """The acceptance HLO assertion: every all-reduce in the tp=2
        prefill AND decode executables carries integer payload bytes
        (s32 row-epilogue accumulators; compressed_psum's integer fast
        path never even emits the scalar f32 pmax)."""
        base, tp2, sp2 = engines
        report = tp2.dry_run_report(batch=2, prompt_len=S)
        assert report["int8_all_reduces_ok"], report
        payloads = [p for ex in report["executables"].values()
                    for p in ex["all_reduce_payloads"]]
        assert payloads, "tp=2 executables must contain all-reduces"
        assert all(dt.startswith(("s", "u", "pred"))
                   for dt, _ in payloads), payloads

    @needs2
    def test_sp_has_no_all_reduce_at_all(self, engines):
        """Sequence parallelism merges flash partials with gathers, not
        reductions — the strict integer-all-reduce assertion is vacuous
        there BY CONSTRUCTION, and prefill moves zero collective bytes
        (each shard owns its rows outright)."""
        base, tp2, sp2 = engines
        report = sp2.dry_run_report(batch=2, prompt_len=S)
        assert report["int8_all_reduces_ok"], report
        for ex in report["executables"].values():
            assert ex["all_reduce_payloads"] == []
        assert report["executables"]["prefill"]["collective_bytes"] == 0

    @needs2
    def test_sharded_trace_passes_drift_check(self, engines):
        """dtype_drift over the REAL sharded jaxprs: the only float
        collective anywhere is the allowlisted sp_partial_combine
        gather."""
        from repro.analysis import dtype_drift as DD
        from repro.launch import steps as ST

        base, tp2, sp2 = engines
        for eng in (tp2, sp2):
            cache = eng.init_cache(2, 32)
            step = ST.make_prefill_step(eng.model, eng.cfg, eng.policy,
                                        eng.mode)
            jaxpr = jax.make_jaxpr(step)(
                eng.serve_params, eng.qparams,
                {"tokens": jnp.zeros((2, S), jnp.int32)}, cache)
            assert DD.check_dtype_drift(jaxpr) == []


class TestShardedCacheDurability:
    @needs2
    @pytest.mark.parametrize("which", ["tp", "sp"])
    def test_state_dict_roundtrip_mid_generation(self, engines, which):
        """Snapshot the cache after a sharded prefill, rebuild it from
        the state_dict, decode on both — bit-identical logits.  The
        cache pytree stays GLOBAL outside shard_map, so the unsharded
        state_dict machinery round-trips it untouched."""
        from repro.cache.base import KVCache
        from repro.launch import steps as ST

        base, tp2, sp2 = engines
        eng = tp2 if which == "tp" else sp2
        cache = eng.init_cache(2, 32)
        toks = _toks(eng.cfg, b=2)
        prefill = ST.make_prefill_step(eng.model, eng.cfg, eng.policy,
                                       eng.mode)
        decode = ST.make_serve_step(eng.model, eng.cfg, eng.policy,
                                    eng.mode)
        _, cache = prefill(eng.serve_params, eng.qparams,
                           {"tokens": toks}, cache)
        restored = jax.tree.map(
            lambda c: KVCache.from_state_dict(c.state_dict()), cache,
            is_leaf=lambda c: isinstance(c, KVCache))
        tok1 = jnp.zeros((2, 1), jnp.int32)
        want, _, _ = decode(eng.serve_params, eng.qparams, tok1, cache,
                            jnp.int32(S))
        got, _, _ = decode(eng.serve_params, eng.qparams, tok1, restored,
                           jnp.int32(S))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestPartialSoftmax:
    """Kernel + merge algebra — single-device, runs everywhere."""

    def _setup(self, b=2, s=32, kv=2, g=2, d=16, pos=20):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, kv, g, d)), jnp.float32)
        k = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)),
                        jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=(b, s, kv, d)),
                        jnp.int8)
        ks = jnp.full((kv,), 0.02, jnp.float32)
        vs = jnp.full((kv,), 0.03, jnp.float32)
        cur = jnp.full((b,), pos, jnp.int32)
        return q, k, v, ks, vs, cur

    def test_partials_normalize_to_full_attention(self):
        from repro.kernels import decode_attention as DA

        q, k, v, ks, vs, cur = self._setup()
        full = DA.decode_attention_int8(q, k, v, ks, vs, cur,
                                        interpret=True)
        acc, m, l = DA.decode_attention_partials(q, k, v, ks, vs, cur,
                                                 interpret=True)
        got = acc / np.maximum(np.asarray(l)[..., None], 1e-30)
        got = got.reshape(np.asarray(full).shape)
        np.testing.assert_allclose(got, np.asarray(full), atol=1e-5)

    def test_two_shard_merge_is_exact(self):
        """Split S in half, run partials per half at shard-local
        positions, merge with the online-softmax identity — matches the
        full attention to f32 roundoff."""
        from repro.kernels import decode_attention as DA

        q, k, v, ks, vs, cur = self._setup(s=32, pos=20)
        full = np.asarray(DA.decode_attention_int8(q, k, v, ks, vs, cur,
                                                   interpret=True))
        parts = []
        for lo in (0, 16):
            valid = np.clip(np.asarray(cur) - lo, 0, 16)
            acc, m, l = DA.decode_attention_partials(
                q, k[:, lo:lo + 16], v[:, lo:lo + 16], ks, vs,
                jnp.asarray(valid, jnp.int32), interpret=True)
            parts.append((np.asarray(acc, np.float64),
                          np.asarray(m, np.float64),
                          np.asarray(l, np.float64)))
        (a0, m0, l0), (a1, m1, l1) = parts
        mg = np.maximum(m0, m1)
        w0, w1 = np.exp(m0 - mg), np.exp(m1 - mg)
        l = l0 * w0 + l1 * w1
        acc = a0 * w0[..., None] + a1 * w1[..., None]
        got = (acc / np.maximum(l, 1e-30)[..., None]).reshape(full.shape)
        np.testing.assert_allclose(got, full, atol=1e-5)

    def test_empty_shard_is_merge_identity(self):
        """A shard with zero valid rows emits (m=-inf-ish, l=0, acc=0):
        merging it in changes nothing."""
        from repro.kernels import decode_attention as DA

        q, k, v, ks, vs, _ = self._setup()
        acc, m, l = DA.decode_attention_partials(
            q, k, v, ks, vs, jnp.zeros((2,), jnp.int32), interpret=True)
        assert np.all(np.asarray(l) == 0.0)
        assert np.all(np.asarray(acc) == 0.0)
        assert np.all(np.asarray(m) <= -1e29)


class TestValidation:
    def test_tp_and_sp_together_rejected(self):
        from repro.shard.context import ShardContext

        with pytest.raises(ValueError, match="share the one"):
            ShardContext(axis="model", tp=2, sp=2)

    @needs2
    def test_tp_requires_int8_mode(self):
        from repro.shard.engine import ShardedEngine

        with pytest.raises(ValueError, match="mode='int8'"):
            ShardedEngine.from_checkpoint("smollm-135m", cfg=_cfg(),
                                          smoke=True, tp=2, fp=True)

    @needs2
    def test_sp_rejects_paged_layout(self):
        from repro.shard.engine import ShardedEngine

        with pytest.raises(ValueError, match="paged"):
            ShardedEngine.from_checkpoint("smollm-135m", cfg=_cfg(),
                                          smoke=True, sp=2,
                                          cache_layout="paged")

    @needs2
    def test_tp_head_divisibility_enforced(self):
        from repro.launch.mesh import make_serving_mesh
        from repro.models import build_model
        from repro.shard.model import ShardedModel

        cfg = get_config("smollm-135m", smoke=True)  # 3 heads
        with pytest.raises(ValueError, match="not divisible by tp"):
            ShardedModel(build_model(cfg), cfg, make_serving_mesh(2),
                         tp=2)
