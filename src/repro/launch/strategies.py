"""``DecodeStrategy`` — one decode-loop API, many decoding schemes.

PRs 1 and 3 each grew a bespoke scan loop (single-stream and slotted),
and the carry, the sampling rule, and the EOS/slot bookkeeping were
hardwired twice — any new decoding scheme meant forking a third loop.
This module makes the decode loop a PROTOCOL, the same "one API, many
implementations" move PR 4 made for cache layouts:

  * the **carry** is an explicit ``DecodeState`` pytree — (token, cache,
    per-slot position, active mask, PRNG key, history buffer);
  * a **strategy** supplies four hooks over that carry:

      - ``propose(tok, pos, hist)``   -> draft tokens (B, W-1)
      - ``verify(params, qp, tok, drafts, cache, pos, active)``
                                      -> (logits (B, W, V), cache)
      - ``accept(tok, drafts, logits, active, key)``
                                      -> (next tok, toks (B, W),
                                          emitted (B, W), key)
      - ``update_hist(hist, pos, toks, emitted)`` -> hist

    where W = ``emit_width`` is the (static) number of tokens a step can
    emit;
  * the **loops** (``make_strategy_decode_loop`` single-stream,
    ``make_strategy_slot_loop`` slotted) own the scan, the capacity
    guard, EOS freezing, and position accounting — once, for every
    strategy.

Three strategies ship:

  * ``GreedyStrategy`` / ``SamplingStrategy`` — bit-exact ports of the
    pre-redesign loops (W == 1; verify IS the fused one-token decode
    step, accept is argmax / ``sample_tokens`` with the same per-step
    key split).
  * ``SpeculativeStrategy`` — prompt-lookup speculative decoding
    (draft-model-free): ``propose`` drafts ``k`` tokens by matching the
    trailing ``ngram`` of the token history against earlier history and
    copying what followed; ``verify`` runs the pending token + drafts as
    ONE batched window through ``model.verify_step`` — exactly a short
    per-slot chunked prefill over the int8 cache, reusing the Pallas
    flash-prefill kernel via its per-request ``q_start`` vector (the
    int8 cache halves precisely the bytes this pass streams, which is
    where speculation pays); ``accept`` keeps the longest matching draft
    prefix plus the model's own next token (1..k+1 tokens per step).
    Under this deterministic accept rule every emitted token equals what
    greedy would emit — a WRONG draft costs only wasted verify work,
    never a wrong token — so speculative output is bit-identical to
    ``GreedyStrategy``.  Rejected drafts leave dead cache entries beyond
    the accepted position; ``KVCache.rollback`` documents why no
    physical erase is needed (masks never read them, the next window
    overwrites them) and how the paged layout protects shared prefix
    pages.

Shape discipline (the no-retrace contract): ``k``/``ngram`` are static
— draft CONTENT, match positions, and acceptance counts are data, so
one compiled executable per loop serves every draft length and
admission pattern (pinned by the trace-counter tests in
tests/test_strategies.py).
"""
from __future__ import annotations

import abc
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import api as A
from repro.models.moe import routing_stats


class DecodeState(NamedTuple):
    """The decode-loop carry, made explicit (the protocol's data half).

    ``tok`` is the pending token — sampled/accepted but not yet cached;
    it enters the model at position ``pos`` on the next step.  ``pos``
    counts valid cache entries per slot.  ``hist`` is the strategy's
    state (token history for prompt lookup; a (B, 0) placeholder for
    stateless strategies)."""
    tok: jax.Array       # (B,) int32 pending token
    cache: object        # KVCache pytree
    pos: jax.Array       # (B,) int32 valid cache entries per slot
    active: jax.Array    # (B,) bool live slots
    key: jax.Array       # PRNG key
    hist: jax.Array      # (B, H) int32 strategy state


def _serve_ctx(mode: str, policy: A.QuantPolicy, qparams):
    """Serving ctx.  A ctx is built even for mode='none' when the policy
    quantizes the KV cache or enables the Pallas kernels (Dense layers
    still run full precision — enabled() is False): the
    int8-KV-over-bf16-weights ablation needs the KV thresholds in qparams
    to reach attention, and the fused bf16-KV attention kernels (unit
    scales) need the policy flag to reach it."""
    if mode == "none" and not (policy.kv_int8 or policy.use_pallas):
        return None
    return A.make_ctx(mode, policy, qparams)


def _attn_cache_len(cache):
    """Logical sequence capacity of the first attention cache in a cache
    pytree — a ``repro.cache.KVCache`` object (any layout, stacked or
    per-layer; paged capacity is blocks * page_size) or, for stub caches
    in tests, a plain dict with a (..., S, KV, D) "k" leaf."""
    from repro.cache import KVCache

    if isinstance(cache, KVCache):
        return cache.capacity
    if isinstance(cache, dict):
        if "attn" in cache and isinstance(cache["attn"], dict) \
                and "k" in cache["attn"]:
            return cache["attn"]["k"].shape[-3]
        for sub in cache.values():
            n = _attn_cache_len(sub)
            if n is not None:
                return n
    return None


def _rollback(cache, pos):
    """Thread ``KVCache.rollback`` through a cache pytree (identity for
    non-KVCache test stubs).  In-loop this is the LOGICAL rewind — every
    shipped layout makes it free (see the protocol docstring); the call
    keeps the contract explicit and gives future layouts the hook."""
    from repro.cache import KVCache

    return jax.tree.map(
        lambda c: c.rollback(pos) if isinstance(c, KVCache) else c,
        cache, is_leaf=lambda x: isinstance(x, KVCache))


def sample_tokens(logits, key, *, temperature: float = 1.0,
                  top_p: float = 1.0):
    """Temperature / nucleus (top-p) sampling over (B, V) logits.

    ``temperature <= 0`` is greedy argmax.  ``top_p < 1`` keeps the
    smallest prefix of probability-sorted tokens whose mass reaches
    top_p (always at least the argmax) and renormalizes over it.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_p < 1.0:
        sorted_l = jnp.sort(l, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        # exclusive cumulative mass: a token stays while the mass BEFORE
        # it is < top_p, so the argmax always survives
        cum = jnp.cumsum(probs, axis=-1) - probs
        keep = cum < top_p
        thresh = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                         keepdims=True)
        l = jnp.where(l >= thresh, l, -jnp.inf)
    return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)


def _check_attn_only(cfg, what: str):
    kinds = {cfg.layer_kind(i) for i in range(cfg.n_layers)}
    if kinds - {"attn", "attn_local"} or cfg.modality != "text":
        raise ValueError(
            f"{what} covers attention-only text stacks: SSM state "
            "stepping has no per-slot freeze/rewind yet "
            f"(got kinds={sorted(kinds)}, modality={cfg.modality})")


class DecodeStrategy(abc.ABC):
    """One decoding scheme behind the propose/verify/accept hooks.

    ``emit_width`` (static) is the number of token lanes a step emits
    into — 1 for one-token-at-a-time schemes, draft_k + 1 for
    speculative windows.  ``stateful`` marks strategies that carry a
    history buffer through the loop.
    """

    emit_width: int = 1
    stateful: bool = False

    def __init__(self, model, cfg, policy: A.QuantPolicy,
                 mode: str = "int8"):
        self.model, self.cfg = model, cfg
        self.policy, self.mode = policy, mode

    # -- hooks -------------------------------------------------------------
    def propose(self, tok, pos, hist):
        """Draft tokens (B, emit_width - 1) to verify this step.  Draft
        content never affects WHICH tokens are emitted (verify/accept
        correct wrong drafts) — only how many per step."""
        return jnp.zeros((tok.shape[0], 0), jnp.int32)

    @abc.abstractmethod
    def verify(self, serve_params, qparams, tok, drafts, cache, pos,
               active):
        """Run the model over the pending token (+ drafts) and return
        (logits (B, emit_width, V), new cache).  ``active`` is a (B,)
        slot mask or None (single-stream, scalar ``pos``)."""

    @abc.abstractmethod
    def accept(self, tok, drafts, logits, active, key):
        """Turn verify logits into emissions: (next pending token (B,),
        toks (B, W), emitted (B, W) bool, new key).  ``emitted[b, j]``
        marks real tokens; positions advance by the emitted count."""

    def update_hist(self, hist, pos, toks, emitted):
        return hist


class GreedyStrategy(DecodeStrategy):
    """Argmax decoding — a bit-exact port of the pre-redesign loops: the
    verify pass IS the fused one-token decode step (flash-decode kernel
    under policy.use_pallas), accept is its argmax."""

    emit_width = 1

    def verify(self, serve_params, qparams, tok, drafts, cache, pos,
               active):
        ctx = _serve_ctx(self.mode, self.policy, qparams)
        logits, cache = self.model.decode_step(
            serve_params, tok[:, None], cache, pos, ctx, slot_mask=active)
        return logits, cache

    def accept(self, tok, drafts, logits, active, key):
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        if active is None:
            emitted = jnp.ones(nxt.shape + (1,), bool)
        else:
            emitted = active[:, None]
        return nxt, nxt[:, None], emitted, key


class SamplingStrategy(GreedyStrategy):
    """Temperature / nucleus sampling (absorbs ``sample_tokens``).

    Two key schedules, dispatched (at trace time) on the carried key's
    shape:

      * scalar key — split once per step, shared across slots: the
        pre-redesign schedule, so same seed -> same tokens for the
        legacy loops and the steps-module wrapper;
      * per-slot keys, a (B, 2) uint32 matrix — each slot splits its OWN
        key, and a slot's key advances only on its active steps.  A
        request's sample stream then depends only on its admission key
        and how many tokens it has emitted — not on arrival order, slot
        placement, batch composition, or preemption — which is what lets
        the scheduler promise same-seed reproducibility across admission
        patterns (tests/test_resilience.py pins it).
    """

    def __init__(self, model, cfg, policy, mode: str = "int8", *,
                 temperature: float = 1.0, top_p: float = 1.0):
        super().__init__(model, cfg, policy, mode)
        self.temperature, self.top_p = temperature, top_p

    def accept(self, tok, drafts, logits, active, key):
        last = logits[:, -1, :]
        if key.ndim == 2:
            ks = jax.vmap(jax.random.split)(key)     # (B, 2, 2)
            sub, nxt_key = ks[:, 0], ks[:, 1]
            nxt = jax.vmap(
                lambda l, k: sample_tokens(l[None], k,
                                           temperature=self.temperature,
                                           top_p=self.top_p)[0])(last, sub)
            if active is None:
                emitted = jnp.ones(nxt.shape + (1,), bool)
                key = nxt_key
            else:
                emitted = active[:, None]
                # frozen slots hold their key: the stream position stays
                # a pure function of tokens emitted
                key = jnp.where(active[:, None], nxt_key, key)
            return nxt, nxt[:, None], emitted, key
        key, sub = jax.random.split(key)
        nxt = sample_tokens(last, sub,
                            temperature=self.temperature, top_p=self.top_p)
        if active is None:
            emitted = jnp.ones(nxt.shape + (1,), bool)
        else:
            emitted = active[:, None]
        return nxt, nxt[:, None], emitted, key


class SpeculativeStrategy(DecodeStrategy):
    """Prompt-lookup speculative decoding (no second model).

    ``propose`` matches the trailing ``ngram`` tokens of the history
    (prompt + everything emitted, including the pending token) against
    earlier history; the most recent match's continuation becomes the
    ``k`` drafts (the pending token repeated when nothing matches — a
    always-rejected placebo, so a lookup miss degrades to greedy rate,
    never to wrong output).  ``verify`` runs [pending, drafts] as one
    (B, k+1) window through ``model.verify_step``; ``accept`` keeps the
    longest prefix of drafts matching the model's own argmax plus the
    model's next token after it.  Deterministic accept == greedy accept,
    token for token.

    The history buffer ``hist`` maps absolute position -> token (the
    caller sizes it to the cache capacity and seeds it with the prompt
    at admission); all matching is fixed-shape: the n-gram, window
    starts, and draft gathers are data-indexed into (B, H), so one
    compiled loop serves every match/acceptance pattern.
    """

    stateful = True

    def __init__(self, model, cfg, policy, mode: str = "int8", *,
                 draft_k: int = 4, ngram: int = 2):
        super().__init__(model, cfg, policy, mode)
        _check_attn_only(cfg, "speculative decoding")
        if draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {draft_k}")
        if ngram < 1:
            raise ValueError(f"ngram must be >= 1, got {ngram}")
        self.draft_k, self.ngram = draft_k, ngram
        self.emit_width = draft_k + 1

    def propose(self, tok, pos, hist):
        b, h = hist.shape
        g, k = self.ngram, self.draft_k
        if h < g + 1:
            raise ValueError(
                f"history buffer ({h}) shorter than ngram+1 ({g + 1})")
        pos = jnp.asarray(pos, jnp.int32).reshape(-1)
        j = jnp.arange(g, dtype=jnp.int32)
        # the trailing n-gram ends at the pending token (hist[pos])
        gram_idx = jnp.clip(pos[:, None] - (g - 1) + j[None], 0, h - 1)
        gram = jnp.take_along_axis(hist, gram_idx, axis=1)       # (B, g)
        starts = jnp.arange(h - g + 1, dtype=jnp.int32)
        wins = hist[:, starts[:, None] + j[None]]                # (B, n, g)
        hit = (wins == gram[:, None, :]).all(-1)
        # a usable match must END before the trailing gram starts, so its
        # continuation (the draft source) is known history
        usable = hit & (starts[None, :] <= pos[:, None] - g)
        best = jnp.max(jnp.where(usable, starts[None, :], -1), axis=1)
        found = best >= 0
        src = jnp.maximum(best, 0) + g                           # (B,)
        didx = src[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
        # clamp draft reads to known history (a short continuation pads
        # with the newest tokens — correctness never depends on drafts)
        didx = jnp.clip(jnp.minimum(didx, pos[:, None]), 0, h - 1)
        drafts = jnp.take_along_axis(hist, didx, axis=1)         # (B, k)
        return jnp.where(found[:, None], drafts, tok[:, None])

    def verify(self, serve_params, qparams, tok, drafts, cache, pos,
               active):
        ctx = _serve_ctx(self.mode, self.policy, qparams)
        window = jnp.concatenate([tok[:, None], drafts], axis=1)
        logits, cache = self.model.verify_step(
            serve_params, window, cache, pos, ctx, slot_mask=active)
        return logits, cache

    def accept(self, tok, drafts, logits, active, key):
        w = self.emit_width
        pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B, W)
        # accept the longest draft prefix the model agrees with; accepted
        # drafts EQUAL the model's argmax at their position, so the
        # emitted tokens are simply pred[:n_match + 1] — drafts only
        # decide how far one verify pass advances
        match = (drafts == pred[:, :-1]).astype(jnp.int32)
        n_match = jnp.sum(jnp.cumprod(match, axis=1), axis=1)    # (B,)
        lanes = jnp.arange(w, dtype=jnp.int32)[None]
        emitted = lanes <= n_match[:, None]
        if active is not None:
            emitted = emitted & active[:, None]
        nxt = jnp.take_along_axis(
            pred, jnp.clip(n_match, 0, w - 1)[:, None], axis=1)[:, 0]
        return nxt, pred, emitted, key

    def update_hist(self, hist, pos, toks, emitted):
        """Record the step's emissions at their absolute positions
        (``pos`` is the PRE-step position; emitted lane j landed at
        ``pos + 1 + j``)."""
        b, h = hist.shape
        idx = pos[:, None] + 1 + jnp.arange(toks.shape[1],
                                            dtype=jnp.int32)[None]
        idx = jnp.where(emitted, idx, h)    # out-of-range -> dropped
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        return hist.at[rows, idx].set(toks, mode="drop")


STRATEGIES = ("greedy", "sample", "speculative")


def make_strategy(name: str, model, cfg, policy: A.QuantPolicy,
                  mode: str = "int8", *, temperature: float = 0.0,
                  top_p: float = 1.0, spec_k: int = 4,
                  spec_ngram: int = 2) -> DecodeStrategy:
    """Build a strategy by name; ``name=None`` auto-picks from the
    sampling knobs (sample when temperature > 0, else greedy) — the
    pre-redesign behavior."""
    if name is None:
        name = "sample" if temperature > 0.0 else "greedy"
    if name == "greedy":
        if temperature > 0.0:
            raise ValueError(
                "greedy decoding ignores temperature — drop the "
                "temperature or use strategy='sample'")
        return GreedyStrategy(model, cfg, policy, mode)
    if name == "sample":
        return SamplingStrategy(model, cfg, policy, mode,
                                temperature=temperature, top_p=top_p)
    if name == "speculative":
        if temperature > 0.0:
            raise ValueError(
                "speculative decoding uses the deterministic (greedy) "
                "accept rule; temperature must be 0")
        return SpeculativeStrategy(model, cfg, policy, mode,
                                   draft_k=spec_k, ngram=spec_ngram)
    raise ValueError(f"unknown decode strategy {name!r} (use one of "
                     f"{STRATEGIES})")


# -- the loops (own the scan; strategies own the scheme) ---------------------

def make_strategy_decode_loop(model, cfg, policy: A.QuantPolicy,
                              strategy: DecodeStrategy, mode: str = "int8",
                              n_steps: int = 16):
    """Single-stream whole-generation decode as ONE compiled call.

    ``emit_width == 1`` strategies keep the pre-redesign carry exactly —
    (token, cache, scalar position, key), n_steps - 1 scanned steps,
    tokens[:, 0] == tok0 — so greedy/sampling are bit-identical to the
    old ``make_decode_loop``.  Windowed strategies (speculative) carry
    per-slot positions, a write cursor, and an output buffer instead:
    each step scatters its accepted tokens at the cursor, rows freeze
    when their budget fills, and the loop still returns a dense
    (B, n_steps) token matrix.  Callers jit with ``donate_argnums=(3,)``
    either way (serve/engine do).
    """
    w = strategy.emit_width

    if w == 1:
        def decode_loop(serve_params, qparams, tok0, cache, pos0, key=None):
            if key is None:
                key = jax.random.PRNGKey(0)
            no_drafts = jnp.zeros((tok0.shape[0], 0), jnp.int32)

            def body(carry, _):
                tok, cache, pos, key = carry
                logits, cache = strategy.verify(
                    serve_params, qparams, tok, no_drafts, cache, pos, None)
                nxt, _, _, key = strategy.accept(tok, no_drafts, logits,
                                                 None, key)
                return (nxt, cache, pos + 1, key), nxt

            carry0 = (tok0, cache, jnp.asarray(pos0, jnp.int32), key)
            (_, cache, _, _), toks = jax.lax.scan(body, carry0, None,
                                                  length=n_steps - 1)
            toks = jnp.concatenate(
                [tok0[:, None], jnp.moveaxis(toks, 0, 1)], axis=1)
            return toks, cache

        return decode_loop

    def decode_loop(serve_params, qparams, tok0, cache, pos0, key=None,
                    hist=None):
        if key is None:
            key = jax.random.PRNGKey(0)
        b = tok0.shape[0]
        if hist is None:
            raise ValueError(
                "a stateful strategy needs its history buffer (seed it "
                "with the prompt tokens + tok0; see Engine.generate_batch)")
        cache_len = _attn_cache_len(cache)
        pos_v = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32).reshape(-1),
                                 (b,))
        out0 = jnp.zeros((b, n_steps), jnp.int32).at[:, 0].set(tok0)
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]

        def body(carry, _):
            tok, cache, pos, n_out, out, key, hist = carry
            active = n_out < n_steps
            if cache_len is not None:
                active = active & (pos + w <= cache_len)
            drafts = strategy.propose(tok, pos, hist)
            logits, cache = strategy.verify(serve_params, qparams, tok,
                                            drafts, cache, pos, active)
            nxt, toks, emitted, key = strategy.accept(tok, drafts, logits,
                                                      active, key)
            nxt = jnp.where(active, nxt, tok)
            toks = jnp.where(emitted, toks, tok[:, None])
            # compact this step's emissions at each row's write cursor;
            # lanes past the budget scatter out of range and drop
            off = jnp.cumsum(emitted, axis=1) - emitted
            idx = jnp.where(emitted, n_out[:, None] + off, n_steps)
            out = out.at[rows, idx].set(toks, mode="drop")
            n_acc = jnp.sum(emitted, axis=1)
            hist = strategy.update_hist(hist, pos, toks, emitted)
            pos = pos + n_acc
            cache = _rollback(cache, pos)
            return (nxt, cache, pos, n_out + n_acc, out, key, hist), None

        carry0 = (tok0, cache, pos_v, jnp.ones((b,), jnp.int32), out0, key,
                  hist)
        # worst case one token per step: n_steps - 1 windows always fill
        # the budget; high acceptance just freezes the tail steps early
        (_, cache, _, _, out, _, _), _ = jax.lax.scan(
            body, carry0, None, length=n_steps - 1)
        return out, cache

    return decode_loop


def make_strategy_slot_loop(model, cfg, policy: A.QuantPolicy,
                            strategy: DecodeStrategy, mode: str = "int8",
                            n_steps: int = 8, eos_id: int = -1):
    """One continuous-batching decode BLOCK under any strategy.

    The carry is a ``DecodeState``: per-slot (tok, cache, pos, active,
    key, hist).  Each of the ``n_steps`` scanned steps runs
    propose -> verify -> accept and then the loop-owned bookkeeping:

      * capacity guard BEFORE the write — a slot without room for a full
        ``emit_width`` window freezes instead of clamp-writing;
      * EOS (``eos_id >= 0``): the EOS lane itself is emitted, every
        later lane in the window is cut, and the slot freezes — mid-scan,
        without touching the rest of the batch;
      * positions advance by each slot's emitted count (slots DRAIN AT
        DIFFERENT RATES under speculation — that raggedness is data);
      * ``KVCache.rollback`` records the logical rewind of rejected
        draft entries;
      * NON-FINITE LOGITS freeze only the slot that produced them: the
        slot emits nothing from that step on and comes back flagged in
        ``bad``, so the scheduler can retire just that request as
        ``failed`` while the rest of the batch decodes on.  The optional
        ``nan_step`` vector ((B,) int32, -1 = never) forces a slot's
        logits non-finite at a chosen scan step — the fault-injection
        hook (``launch/faults.py``); it is DATA, so faulted and clean
        runs share one executable.

    Returns ``(toks (B, n_steps * W), emitted (B, n_steps * W), cache,
    pos, active, key, hist, bad, routing)`` with W = ``emit_width``; lane
    j of step i sits at column i * W + j.  ``routing`` holds the block's
    routing counters (``repro.models.moe.routing_stats``:
    ``expert_rows``, ``expert_rows_max``, ``experts_live``), each summed
    over the steps and the layers, for an unrolled stack with MoE layers;
    it is empty for any other stack.  Under speculation emissions are
    ragged WITHIN a window, so consumers skip un-emitted lanes rather
    than stopping at the first (the scheduler does).  All shapes are
    fixed by (max_slots, cache_len, n_steps, W): one compiled executable
    serves every admission pattern, every draft/acceptance pattern, and
    every fault plan.  Callers jit with ``donate_argnums=(3,)``.
    """
    _check_attn_only(cfg, "slot decode")
    w = strategy.emit_width
    # a scanned stack traces its layers inside an inner scan, out of the
    # counters' reach
    counted = (not cfg.scan_layers
               and any(cfg.ffn_kind(i) == "moe" for i in range(cfg.n_layers)))

    def slot_loop(serve_params, qparams, tok0, cache, pos0, active0,
                  key=None, hist=None, nan_step=None):
        if key is None:
            key = jax.random.PRNGKey(0)
        cache_len = _attn_cache_len(cache)
        if strategy.stateful and hist is None:
            raise ValueError(
                "a stateful strategy needs its history buffer (the "
                "scheduler seeds it with each prompt at admission)")
        pos0 = jnp.asarray(pos0, jnp.int32)
        if nan_step is None:
            nan_step = jnp.full(pos0.reshape(-1).shape, -1, jnp.int32)

        def body(carry, step_i):
            st = DecodeState(*carry[0])
            bad_acc = carry[1]
            tok, cache, pos, active, key, hist = st
            # capacity guard BEFORE the write: a slot without room for a
            # whole window freezes instead of clamping over valid entries
            if cache_len is not None:
                active = active & (pos + w <= cache_len)
            drafts = strategy.propose(tok, pos, hist)
            with routing_stats() as sink:
                logits, cache = strategy.verify(serve_params, qparams, tok,
                                                drafts, cache, pos, active)
            stats = ({k: sum(d[k] for d in sink) for k in sink[0]}
                     if counted else {})
            # injected fault: scheduled slots' logits turn NaN here, then
            # flow through the same detection as a real model fault
            hit = (nan_step == step_i) & active
            logits = jnp.where(hit[:, None, None],
                               jnp.asarray(jnp.nan, logits.dtype), logits)
            nxt, toks, emitted, key = strategy.accept(tok, drafts, logits,
                                                      active, key)
            nxt = jnp.where(active, nxt, tok)      # frozen slots hold
            toks = jnp.where(emitted, toks, tok[:, None])
            # fault isolation: a slot with non-finite verify logits emits
            # nothing this step and freezes; the batch decodes on
            finite = jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                             axis=(1, 2))
            bad = active & ~finite
            emitted = emitted & ~bad[:, None]
            nxt = jnp.where(bad, tok, nxt)
            active = active & ~bad
            bad_acc = bad_acc | bad
            if eos_id >= 0:
                # the EOS lane itself is emitted; later lanes in the
                # window are cut and the slot freezes after
                iseos = (toks == eos_id) & emitted
                before = (jnp.cumsum(iseos.astype(jnp.int32), axis=1)
                          - iseos.astype(jnp.int32))
                emitted = emitted & (before == 0)
                eos_hit = jnp.any(iseos & emitted, axis=1)
                active = active & ~eos_hit
                if w > 1:
                    # the held token of a frozen slot is its last
                    # emission (the EOS), matching the one-token loops
                    last = jnp.clip(jnp.sum(emitted, axis=1) - 1, 0, w - 1)
                    held = jnp.take_along_axis(toks, last[:, None],
                                               axis=1)[:, 0]
                    nxt = jnp.where(eos_hit, held, nxt)
            n_acc = jnp.sum(emitted, axis=1)
            hist = strategy.update_hist(hist, pos, toks, emitted)
            pos = pos + n_acc
            cache = _rollback(cache, pos)
            return ((nxt, cache, pos, active, key, hist), bad_acc), \
                (toks, emitted, stats)

        active0 = jnp.asarray(active0, bool)
        if hist is None:
            hist = jnp.zeros((pos0.shape[0], 0), jnp.int32)
        carry0 = ((jnp.asarray(tok0, jnp.int32), cache, pos0, active0, key,
                   hist), jnp.zeros(active0.shape, bool))
        ((tok, cache, pos, active, key, hist), bad), (toks, emitted, stats) \
            = jax.lax.scan(body, carry0, jnp.arange(n_steps, dtype=jnp.int32))
        # (n_steps, B, W) -> (B, n_steps * W): lane j of step i at i*W+j
        b = pos.shape[0]
        toks = jnp.moveaxis(toks, 0, 1).reshape(b, n_steps * w)
        emitted = jnp.moveaxis(emitted, 0, 1).reshape(b, n_steps * w)
        routing = {k: jnp.sum(v) for k, v in stats.items()}
        return toks, emitted, cache, pos, active, key, hist, bad, routing

    return slot_loop
