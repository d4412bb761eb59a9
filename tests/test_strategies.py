"""DecodeStrategy protocol (ISSUE 5): strategy/loop parity and the
no-retrace contract.

Acceptance:

  * ``GreedyStrategy`` / ``SamplingStrategy`` through the new strategy
    loops are BIT-IDENTICAL to the pre-redesign scan loops (same seed,
    same tokens) — pinned against verbatim copies of the old loop bodies
    kept in this file as the oracle;
  * ``SpeculativeStrategy`` is bit-identical to greedy under the
    deterministic accept rule, on the real model (whatever the
    acceptance rate) and on a cyclic stub where acceptance is provably
    > 0 (windows emit multiple tokens);
  * closure-side trace counters (a counter bumped inside the to-be-jitted
    Python body runs only on jit-cache miss) prove ONE compiled loop
    executable across draft lengths, match patterns, and admission
    patterns.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import api as A
from repro.launch import steps as ST
from repro.launch import strategies as SG
from repro.launch.scheduler import Request, SlotScheduler
from repro.models import build_model

B, S, GEN = 2, 32, 6
CHUNK = 8


def _calibrated(arch="smollm-135m", kv_int8=True, **pol):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    policy = A.QuantPolicy(kv_int8=kv_int8, **pol)
    qp = A.init_qparams(model, params, policy)
    qp = ST.make_calibrate_step(model, cfg, policy)(params, qp,
                                                    {"tokens": toks})
    qp = A.finalize_calibration(qp, policy)
    return cfg, model, params, qp, policy, toks


# -- the pre-redesign loop bodies, verbatim (the parity oracle) -------------

def _legacy_decode_loop(model, cfg, policy, mode="int8", n_steps=16,
                        temperature=0.0, top_p=1.0):
    step = ST.make_serve_step(model, cfg, policy, mode=mode)
    sampled = temperature > 0.0

    def decode_loop(serve_params, qparams, tok0, cache, pos0, key=None):
        if key is None:
            key = jax.random.PRNGKey(0)

        def body(carry, _):
            tok, cache, pos, key = carry
            nxt, logits, cache = step(serve_params, qparams, tok[:, None],
                                      cache, pos)
            if sampled:
                key, sub = jax.random.split(key)
                nxt = ST.sample_tokens(logits[:, -1, :], sub,
                                       temperature=temperature, top_p=top_p)
            return (nxt, cache, pos + 1, key), nxt

        carry0 = (tok0, cache, jnp.asarray(pos0, jnp.int32), key)
        (_, cache, _, _), toks = jax.lax.scan(body, carry0, None,
                                              length=n_steps - 1)
        toks = jnp.concatenate([tok0[:, None], jnp.moveaxis(toks, 0, 1)],
                               axis=1)
        return toks, cache

    return decode_loop


def _legacy_slot_decode_loop(model, cfg, policy, mode="int8", n_steps=8,
                             temperature=0.0, top_p=1.0, eos_id=-1):
    step = ST.make_serve_step(model, cfg, policy, mode=mode)
    sampled = temperature > 0.0

    def slot_decode_loop(serve_params, qparams, tok0, cache, pos0, active0,
                         key=None):
        if key is None:
            key = jax.random.PRNGKey(0)
        cache_len = SG._attn_cache_len(cache)

        def body(carry, _):
            tok, cache, pos, active, key = carry
            if cache_len is not None:
                active = active & (pos < cache_len)
            nxt, logits, cache = step(serve_params, qparams, tok[:, None],
                                      cache, pos, active)
            if sampled:
                key, sub = jax.random.split(key)
                nxt = ST.sample_tokens(logits[:, -1, :], sub,
                                       temperature=temperature, top_p=top_p)
            nxt = jnp.where(active, nxt, tok)
            emitted = active
            if eos_id >= 0:
                active = active & (nxt != eos_id)
            pos = jnp.where(emitted, pos + 1, pos)
            return (nxt, cache, pos, active, key), (nxt, emitted)

        pos0 = jnp.asarray(pos0, jnp.int32)
        active0 = jnp.asarray(active0, bool)
        carry0 = (jnp.asarray(tok0, jnp.int32), cache, pos0, active0, key)
        (tok, cache, pos, active, key), (toks, emitted) = jax.lax.scan(
            body, carry0, None, length=n_steps)
        return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(emitted, 0, 1),
                cache, pos, active, key)

    return slot_decode_loop


class TestLegacyLoopParity:
    """The tentpole's bit-exactness contract: the strategy-backed loops
    reproduce the pre-redesign loops token for token, emission for
    emission — greedy AND sampled (same seed, same key schedule)."""

    @pytest.mark.parametrize("temperature,top_p", [(0.0, 1.0), (1.3, 0.9)])
    def test_single_stream_bit_identical(self, temperature, top_p):
        cfg, model, params, qp, policy, toks = _calibrated()
        pre = jax.jit(ST.make_prefill_step(model, cfg, policy, mode="none"))
        new = jax.jit(ST.make_decode_loop(
            model, cfg, policy, mode="none", n_steps=GEN,
            temperature=temperature, top_p=top_p))
        old = jax.jit(_legacy_decode_loop(
            model, cfg, policy, mode="none", n_steps=GEN,
            temperature=temperature, top_p=top_p))
        outs = []
        for loop in (new, old):
            cache = model.init_cache(B, S + GEN, cfg.dtype, kv_int8=True)
            lg, cache = pre(params, qp, {"tokens": toks}, cache)
            tok0 = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
            out, _ = loop(params, qp, tok0, cache, S,
                          jax.random.PRNGKey(42))
            outs.append(np.asarray(out))
        np.testing.assert_array_equal(outs[0], outs[1])

    @pytest.mark.parametrize("temperature", [0.0, 1.1])
    def test_slot_loop_bit_identical(self, temperature, eos_id=-1):
        cfg, model, params, qp, policy, toks = _calibrated()
        pre = jax.jit(ST.make_prefill_step(model, cfg, policy, mode="none"))
        new = jax.jit(ST.make_slot_decode_loop(
            model, cfg, policy, mode="none", n_steps=4,
            temperature=temperature, eos_id=eos_id))
        old = jax.jit(_legacy_slot_decode_loop(
            model, cfg, policy, mode="none", n_steps=4,
            temperature=temperature, eos_id=eos_id))
        res = []
        for loop in (new, old):
            cache = model.init_cache(B, S + GEN, cfg.dtype, kv_int8=True)
            lg, cache = pre(params, qp, {"tokens": toks}, cache)
            tok0 = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
            out = loop(params, qp, tok0, cache,
                       jnp.full((B,), S, jnp.int32),
                       jnp.asarray([True, False]), jax.random.PRNGKey(3))
            res.append(out)
        for a, b in zip(res[0][:2] + res[0][3:5], res[1][:2] + res[1][3:5]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # caches bit-identical too (inactive slot neutrality included)
        for a, b in zip(jax.tree.leaves(res[0][2]),
                        jax.tree.leaves(res[1][2])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSpeculativeParity:
    """Speculative == greedy, bit for bit, under deterministic accept."""

    def test_single_stream_matches_greedy(self):
        cfg, model, params, qp, policy, toks = _calibrated()
        pre = jax.jit(ST.make_prefill_step(model, cfg, policy, mode="none"))
        greedy = jax.jit(ST.make_decode_loop(model, cfg, policy,
                                             mode="none", n_steps=GEN))
        strat = SG.SpeculativeStrategy(model, cfg, policy, mode="none",
                                       draft_k=3, ngram=2)
        spec = jax.jit(SG.make_strategy_decode_loop(
            model, cfg, policy, strat, mode="none", n_steps=GEN))
        cache_len = S + GEN + strat.draft_k
        outs = {}
        for name in ("greedy", "spec"):
            cache = model.init_cache(B, cache_len, cfg.dtype, kv_int8=True)
            lg, cache = pre(params, qp, {"tokens": toks}, cache)
            tok0 = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
            if name == "greedy":
                out, _ = greedy(params, qp, tok0, cache, S)
            else:
                hist = jnp.zeros((B, cache_len), jnp.int32)
                hist = hist.at[:, :S].set(toks).at[:, S].set(tok0)
                out, _ = spec(params, qp, tok0, cache,
                              jnp.full((B,), S, jnp.int32),
                              jax.random.PRNGKey(0), hist)
            outs[name] = np.asarray(out)
        np.testing.assert_array_equal(outs["spec"], outs["greedy"])

    def test_scheduler_matches_greedy_and_counts_stay_one(self):
        """Two admission patterns through a SPECULATIVE scheduler: tokens
        equal the greedy scheduler's, and the closure trace counters stay
        at one executable per piece — draft lengths, match patterns, and
        admission patterns are all data."""
        cfg, model, params, qp, policy, toks = _calibrated()

        def sched(**kw):
            return SlotScheduler(model, cfg, policy, params, qp,
                                 mode="none", max_slots=2, prompt_cap=S,
                                 gen_cap=GEN + 2, prefill_chunk=CHUNK,
                                 block_steps=3, **kw)

        # repetitive prompt -> lookup hits; random prompt -> misses
        rep = np.tile(np.asarray(toks[0, :4]), 8)[:S].astype(np.int32)
        patterns = [
            [Request(rid=0, tokens=rep, max_gen=GEN),
             Request(rid=1, tokens=np.asarray(toks[1, :20]), max_gen=GEN)],
            [Request(rid=0, tokens=np.asarray(toks[0, :9]), max_gen=GEN)],
        ]
        g, s = sched(), sched(strategy="speculative", spec_k=3,
                              spec_ngram=2)
        for reqs in patterns:
            want = {c.rid: c.tokens for c in g.run(list(reqs))}
            got = {c.rid: c.tokens for c in s.run(list(reqs))}
            assert got == want
        counts = s.executable_counts()
        assert counts == {"prefill": 1, "decode": 1, "insert": 1,
                          "resume": 0}, counts
        assert s.spec_stats()["verify_windows"] > 0


class _CyclicStub:
    """decode/verify emit one-hot logits for (token + 1) % cycle: greedy
    text is perfectly periodic, so prompt-lookup drafts are provably
    accepted once the cycle has repeated in the history."""

    def __init__(self, vocab, cycle=8):
        self.vocab, self.cycle = vocab, cycle

    def _logits(self, tokens):
        nxt = (tokens + 1) % self.cycle
        return jax.nn.one_hot(nxt, self.vocab) * 10.0

    def decode_step(self, params, tokens, cache, cur_pos, ctx=None, *,
                    slot_mask=None):
        return self._logits(tokens), cache

    def verify_step(self, params, tokens, cache, cur_pos, ctx=None, *,
                    slot_mask=None):
        return self._logits(tokens), cache


class TestSpeculativeStub:
    CYCLE = 8

    def _loop(self, n_steps=3, k=4, eos_id=-1, cache_len=64):
        model = _CyclicStub(16, self.CYCLE)
        cfg = get_config("smollm-135m", smoke=True)
        strat = SG.SpeculativeStrategy(model, cfg, A.QuantPolicy(),
                                       mode="none", draft_k=k, ngram=2)
        loop = SG.make_strategy_slot_loop(model, cfg, A.QuantPolicy(), strat,
                                          mode="none", n_steps=n_steps,
                                          eos_id=eos_id)
        cache = {"attn": {"k": jnp.zeros((2, cache_len, 1, 1))}}
        # history = the periodic greedy text, pending token at pos 10
        hist = jnp.tile((jnp.arange(cache_len) + 1) % self.CYCLE,
                        (2, 1)).astype(jnp.int32)
        hist = hist.at[:, 11:].set(0)
        pos0 = jnp.asarray([10, 10], jnp.int32)
        return loop(None, {}, jnp.asarray([3, 3], jnp.int32), cache, pos0,
                    jnp.ones((2,), bool), None, hist)

    def test_full_acceptance_windows(self):
        """With a periodic history every draft matches: each window emits
        draft_k + 1 tokens — the speculation payoff — and the emitted
        stream is exactly the greedy continuation 4,5,6,7,0,1,..."""
        toks, emitted, _, pos, active, _, hist, _, _ = self._loop(
            n_steps=2, k=4)
        toks, emitted = np.asarray(toks), np.asarray(emitted)
        assert emitted.all()                    # every lane accepted
        want = [(4 + i) % self.CYCLE for i in range(10)]
        assert toks[0].tolist() == want
        assert np.asarray(pos).tolist() == [20, 20]   # 2 windows * 5 tokens
        # history recorded the emissions at their absolute positions
        assert np.asarray(hist)[0, 11:21].tolist() == want

    def test_eos_mid_window_cuts_tail_and_freezes(self):
        """EOS inside an accepted window: the EOS lane is emitted, later
        lanes in the window are cut, the slot freezes, and its position
        only advances past what was emitted."""
        toks, emitted, _, pos, active, _, _, _, _ = self._loop(
            n_steps=2, k=4, eos_id=6)
        toks, emitted = np.asarray(toks), np.asarray(emitted)
        # window 1 would emit 4,5,6,7,0 -> cut after the EOS (6)
        assert toks[0, :3].tolist() == [4, 5, 6]
        assert emitted[0].tolist() == [True, True, True] + [False] * 7
        assert not np.asarray(active)[0]
        assert np.asarray(pos)[0] == 13
    def test_capacity_guard_freezes_before_partial_window(self):
        """A slot without room for a WHOLE window freezes rather than
        clamp-writing a partial one."""
        toks, emitted, _, pos, active, _, _, _, _ = self._loop(
            n_steps=2, k=4, cache_len=17)
        # pos 10 + window 5 <= 17 fits once; a second window would need 20
        assert np.asarray(emitted)[0].tolist() == [True] * 5 + [False] * 5
        assert np.asarray(pos)[0] == 15
        assert not np.asarray(active)[0]

    def test_one_loop_executable_across_match_patterns(self):
        """Closure-side trace counter: histories with full matches, no
        matches, and mixed matches reuse ONE compiled loop — draft
        length is data, never shape."""
        model = _CyclicStub(16, self.CYCLE)
        cfg = get_config("smollm-135m", smoke=True)
        strat = SG.SpeculativeStrategy(model, cfg, A.QuantPolicy(),
                                       mode="none", draft_k=4, ngram=2)
        traces = {"n": 0}
        inner = SG.make_strategy_slot_loop(model, cfg, A.QuantPolicy(),
                                           strat, mode="none", n_steps=2)

        def counted(*args):
            traces["n"] += 1
            return inner(*args)

        loop = jax.jit(counted)
        cache = {"attn": {"k": jnp.zeros((2, 64, 1, 1))}}
        hists = [
            jnp.tile((jnp.arange(64) + 1) % self.CYCLE, (2, 1)),  # hits
            jnp.zeros((2, 64)),                                   # misses
            jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 16),
        ]
        for h in hists:
            loop(None, {}, jnp.asarray([3, 3], jnp.int32), cache,
                 jnp.asarray([10, 10], jnp.int32), jnp.ones((2,), bool),
                 jax.random.PRNGKey(0), h.astype(jnp.int32))
        assert traces["n"] == 1


class TestEngineSinglePrompt:
    def test_generate_one_is_generate_batch_at_b1(self):
        """The single-prompt path delegates to generate_batch (B == 1) —
        same executables, so it cannot drift from the batched one."""
        from repro.launch.engine import Engine

        cfg, model, params, qp, policy, toks = _calibrated()
        engine = Engine(model, cfg, policy, params, qp, mode="none")
        one = engine.generate_one(np.asarray(toks[0]), GEN)
        batch = engine.generate_batch({"tokens": toks[:1]}, GEN)
        np.testing.assert_array_equal(np.asarray(one.tokens),
                                      np.asarray(batch.tokens))
        assert one.tokens.shape == (1, GEN)
        with pytest.raises(ValueError, match="1-D prompt"):
            engine.generate_one(np.asarray(toks), GEN)


class TestVerifyPath:
    """model.verify_step — the speculative verify pass — against decode."""

    def test_s1_window_bit_matches_per_slot_decode(self):
        """A 1-token verify window IS per-slot decode: same rope, same
        append, same mask, same contraction order — bit-identical logits
        and cache (the anchor that makes speculative == greedy exact)."""
        cfg, model, params, qp, policy, toks = _calibrated()
        pre = jax.jit(ST.make_prefill_step(model, cfg, policy, mode="none"))
        ctx = SG._serve_ctx("none", policy, qp)
        pos = jnp.full((B,), S, jnp.int32)
        mask = jnp.asarray([True, False])

        def run(fn):
            cache = model.init_cache(B, S + GEN, cfg.dtype, kv_int8=True)
            lg, cache = pre(params, qp, {"tokens": toks}, cache)
            tok0 = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
            return jax.jit(fn)(tok0[:, None], cache)

        lg_d, cache_d = run(lambda t, c: model.decode_step(
            params, t, c, pos, ctx, slot_mask=mask))
        lg_v, cache_v = run(lambda t, c: model.verify_step(
            params, t, c, pos, ctx, slot_mask=mask))
        np.testing.assert_array_equal(np.asarray(lg_d, np.float32),
                                      np.asarray(lg_v, np.float32))
        for a, b in zip(jax.tree.leaves(cache_d), jax.tree.leaves(cache_v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fused_verify_matches_jnp_verify(self):
        """policy.use_pallas routes the verify window through the
        flash-prefill kernel's per-request q_start vector; logits must
        stay within the usual kernel-parity budget of the jnp path."""
        cfg, model, params, qp, policy, toks = _calibrated()
        pol_p = A.QuantPolicy(kv_int8=True, use_pallas=True)
        pre = jax.jit(ST.make_prefill_step(model, cfg, policy, mode="none"))
        window = jax.random.randint(jax.random.PRNGKey(9), (B, 4), 0,
                                    cfg.vocab)
        pos = jnp.asarray([S, S - 7], jnp.int32)
        outs = []
        for pol in (policy, pol_p):
            cache = model.init_cache(B, S + GEN + 4, cfg.dtype,
                                     kv_int8=True)
            lg, cache = pre(params, qp, {"tokens": toks}, cache)
            ctx = SG._serve_ctx("none", pol, qp)
            lgv, _ = jax.jit(lambda t, c, ctx=ctx: model.verify_step(
                params, t, c, pos, ctx))(window, cache)
            outs.append(np.asarray(lgv, np.float32))
        np.testing.assert_allclose(outs[1], outs[0], atol=0.1)
