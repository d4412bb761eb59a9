"""Step functions — the units the launcher jits/lowers/compiles.

  * fat_train_step   — the paper's contribution: distillation training of
                       quantization thresholds on unlabeled data (§3.2):
                       FP teacher forward + fake-quant student forward,
                       RMSE on pre-softmax logits (eq. 24-25 with
                       alpha=beta=0), Adam masked to the threshold scales,
                       cosine-annealed LR (§4.1.2).
  * calibrate_step   — observer pass (§2 calibration).
  * pretrain_step    — standard next-token CE on all params (substrate
                       proof: the framework trains, not just fine-tunes).
  * prefill_step     — int8 serving prefill; ``prefill_chunk`` switches to
                       the chunked ragged variant (lax.scan over fixed
                       prompt chunks + a per-request length vector: one
                       executable for every prompt length).
  * serve_step / decode_loop — one-token decode and the scanned
                       whole-generation loop; ``temperature``/``top_p``
                       sample from the carried PRNG key (greedy default).
  * slot_decode_loop — the continuous-batching decode block: per-slot
                       position/active vectors in the scan carry, EOS
                       freezing one slot mid-scan without touching the
                       rest, fixed (max_slots, cache_len, n_steps) shapes
                       so one executable serves every admission pattern
                       (driven by launch/scheduler.py).

The decode loops are thin compatibility wrappers now: the scan bodies
live in ``launch/strategies.py`` behind the ``DecodeStrategy`` protocol
(propose/verify/accept over an explicit ``DecodeState`` carry), with
``GreedyStrategy``/``SamplingStrategy`` as bit-exact ports of the old
bodies and ``SpeculativeStrategy`` (prompt-lookup drafting + batched
verify) as the first new scheme.  These wrappers keep the historical
signatures — (temperature, top_p) knobs in, (toks, ...) shapes out.

Masking semantics shared by the serving steps: a request's raggedness is
always DATA (length vectors, per-slot positions, active masks), never
SHAPE — that is what keeps each step a single compiled executable.  A
masked row (padded prompt tail, inactive slot) attends over zero keys and
produces exact-zero attention output; cache writes for masked rows are
bit-exact no-ops.  VMEM expectations live with the kernels
(repro.kernels.prefill_attention / decode_attention): the steps only
pick grid-friendly shapes (chunk multiples, 128-tiled cache lengths).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import api as A
from repro.core.distill import chunked_ce_loss, chunked_sq_err
from repro.launch.strategies import (  # noqa: F401  (re-exports: the
    DecodeState, DecodeStrategy, GreedyStrategy,  # pre-redesign public
    SamplingStrategy, SpeculativeStrategy,        # home of sample_tokens
    _attn_cache_len, _serve_ctx, make_strategy,
    make_strategy_decode_loop, make_strategy_slot_loop, sample_tokens)
from repro.optim.adam import AdamState, adam_init, adam_update, cosine_restarts


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    base_lr: float = 1e-3
    anneal_period: int = 100   # cosine restart period (steps)
    weight_decay: float = 0.0
    aux_weight: float = 0.01   # MoE load-balance weight (pretrain mode)


def make_calibrate_step(model, cfg, policy: A.QuantPolicy):
    def calibrate_step(params, qparams, batch):
        ctx = A.make_ctx("calibrate", policy, qparams)
        h, _ = model.hidden(params, batch, ctx, remat=False)
        # an untied readout is a quantized Dense: it observes the final
        # hidden states like every other layer its input
        model.readout_fn(params, ctx)(h)
        merged = dict(qparams)
        for path, obs in ctx.updates.items():
            if A.is_kv_path(path):  # KV observer entry: replace wholesale
                merged[path] = obs
            else:
                merged[path] = {**merged[path], "act": obs}
        return merged

    return calibrate_step


def make_fat_train_step(model, cfg, policy: A.QuantPolicy,
                        hp: TrainHParams = TrainHParams(),
                        n_micro: int = 1):
    """The FAT QAT step.  Gradients are taken w.r.t. the full qparams tree
    but the Adam update is masked to the trained leaves (alpha scales and
    optional pointwise scales) — §3.1.3: "All network parameters except
    quantization thresholds are fixed".

    ``n_micro`` > 1 runs microbatched gradient accumulation: activations
    peak at one microbatch's footprint while the accumulated state is just
    the qparams-shaped gradient (a few KB of alphas + thresholds) — the
    cheapest gradient-accumulation in existence, courtesy of FAT's tiny
    trainable set.
    """

    def loss_for(qp, params, batch):
        # weights are frozen in FAT (§3.1.3) — without this stop_gradient
        # the layer-scan VJP materializes a stacked (L, ...) f32 cotangent
        # for every weight tensor it scans over (GBs that are immediately
        # discarded)
        params = jax.lax.stop_gradient(params)
        # teacher: full precision, frozen
        h_t, _ = model.hidden(params, batch, None, remat=cfg.remat)
        h_t = jax.lax.stop_gradient(h_t)
        # student: fake-quantized with trained thresholds
        ctx = A.make_ctx("fake", policy, qp)
        h_s, _ = model.hidden(params, batch, ctx, remat=cfg.remat)
        ro_t = model.readout_fn(params, None)
        ro_s = model.readout_fn(params, ctx)
        sq, n = chunked_sq_err(h_t, h_s, ro_t, ro_s, chunk=cfg.loss_chunk)
        return jnp.sqrt(sq / n)  # eq. 25

    def train_step(params, qparams, opt_state: AdamState, batch):
        if n_micro == 1:
            loss, grads = jax.value_and_grad(loss_for)(qparams, params, batch)
        else:
            from repro.dist.constraints import constrain_activation

            micro = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                    + x.shape[1:]),
                batch,
            )

            def body(acc, mb):
                mb = jax.tree.map(constrain_activation, mb)
                l, g = jax.value_and_grad(loss_for)(qparams, params, mb)
                acc_l, acc_g = acc
                return (acc_l + l,
                        jax.tree.map(jnp.add, acc_g, g)), None

            zero_g = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), qparams)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_g), micro)
            loss = loss / n_micro
            grads = jax.tree.map(lambda g: g / n_micro, grads)
        lr = cosine_restarts(opt_state.step, hp.base_lr, hp.anneal_period)
        mask = A.trainable_mask(qparams)
        new_qp, new_opt = adam_update(grads, opt_state, qparams, lr, mask=mask)
        return new_qp, new_opt, {"loss": loss, "lr": lr}

    return train_step


def finetune_thresholds(model, cfg, policy: A.QuantPolicy, params, qparams,
                        batches, *, epochs: int = 4,
                        hp: TrainHParams = TrainHParams()):
    """Train the quantization thresholds by distillation (paper §3 + TQT).

    Runs the existing FAT QAT step — fp teacher vs fake-quant student,
    RMSE on pre-softmax logits, Adam masked to the trainable qparams
    leaves — over ``epochs`` passes of a small calibration set.  With
    ``finalize_calibration(..., train_thresholds=True)`` qparams the
    trainable set includes the per-head KV ``log2_t`` thresholds (the
    attention fake-mode hook quantizes the KV stream through them), so a
    handful of epochs repairs max-abs thresholds that an outlier in the
    calibration data blew up — exactly the paper's pitch, applied to the
    int4 cache where the 7-level grid makes threshold quality decisive.

    ``epochs`` is capped at 8: the paper's method converges within a few
    epochs on a small unlabeled set, and the cap keeps serving bring-up
    (engine --finetune-thresholds) bounded.  Returns
    ``(qparams, losses)`` — per-step distill losses, first entry the
    pre-training loss (tests pin strict decrease against it).
    """
    if not 1 <= epochs <= 8:
        raise ValueError(f"epochs must be in [1, 8], got {epochs}")
    batches = list(batches)
    if not batches:
        raise ValueError("finetune_thresholds needs >= 1 calibration batch")
    train_step = jax.jit(make_fat_train_step(model, cfg, policy, hp))
    opt = adam_init(qparams)
    losses = []
    for _ in range(epochs):
        for batch in batches:
            qparams, opt, metrics = train_step(params, qparams, opt, batch)
            losses.append(float(metrics["loss"]))
    return qparams, losses


def make_pretrain_step(model, cfg, hp: TrainHParams = TrainHParams()):
    def pretrain_step(params, opt_state: AdamState, batch):
        def loss_fn(params):
            h, aux = model.hidden(params, batch, None, remat=cfg.remat)
            if cfg.modality == "vlm":
                h = h[:, cfg.mm_patches:, :]
            ce = chunked_ce_loss(h, batch["labels"], model.readout_fn(params),
                                 chunk=cfg.loss_chunk)
            return ce + hp.aux_weight * aux

        loss, grads = jax.value_and_grad(loss_fn)(params)
        lr = cosine_restarts(opt_state.step, hp.base_lr, hp.anneal_period)
        new_params, new_opt = adam_update(grads, opt_state, params, lr,
                                          weight_decay=hp.weight_decay)
        return new_params, new_opt, {"loss": loss, "lr": lr}

    return pretrain_step


def pad_for_chunked_prefill(tokens, chunk: int, lengths=None):
    """Pad (B, S) tokens up to a ``chunk`` multiple and build the
    per-request length vector the chunked prefill step consumes
    (defaults to the unpadded S for every request)."""
    b, s = tokens.shape
    s_pad = -(-s // chunk) * chunk
    if s_pad != s:
        tokens = jnp.pad(tokens, [(0, 0), (0, s_pad - s)])
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    return tokens, jnp.asarray(lengths, jnp.int32)


def make_prefill_step(model, cfg, policy: A.QuantPolicy, mode: str = "int8",
                      prefill_chunk: int | None = None):
    """Prefill step.  With ``prefill_chunk`` set, returns the CHUNKED
    variant ``prefill_step(params, qparams, batch, cache, lengths)``: a
    ``lax.scan`` over fixed-size prompt chunks with a per-request length
    vector, so ONE compiled executable serves ragged prompt lengths
    (tokens padded to a chunk multiple) instead of recompiling per shape.
    Each chunk appends its K/V at absolute cache slots and attends over
    the growing cache; the carry keeps each request's last valid hidden
    state so the readout runs once on (B, 1, d), never on full logits.
    """
    if prefill_chunk is None:
        def prefill_step(serve_params, qparams, batch, cache):
            ctx = _serve_ctx(mode, policy, qparams)
            logits, new_cache = model.prefill(serve_params, batch, cache, ctx)
            return logits, new_cache

        return prefill_step

    kinds = {cfg.layer_kind(i) for i in range(cfg.n_layers)}
    if kinds - {"attn", "attn_local"} or cfg.modality != "text":
        raise ValueError(
            "chunked prefill covers attention-only text stacks: SSM state "
            "folding has no per-request length masking yet "
            f"(got kinds={sorted(kinds)}, modality={cfg.modality})")

    def prefill_step(serve_params, qparams, batch, cache, lengths):
        ctx = _serve_ctx(mode, policy, qparams)
        tokens = batch["tokens"]
        b, s_max = tokens.shape
        if s_max % prefill_chunk:
            raise ValueError(
                f"tokens length {s_max} must pad to a multiple of "
                f"prefill_chunk={prefill_chunk} "
                "(steps.pad_for_chunked_prefill)")
        cache_len = _attn_cache_len(cache)
        if cache_len is not None and s_max > cache_len:
            # dynamic_update_slice would silently CLAMP the out-of-range
            # chunk write, shifting keys into wrong slots — reject instead
            raise ValueError(
                f"padded prompt {s_max} exceeds the cache length "
                f"{cache_len}; size the cache to at least the padded "
                "prompt (prompt_len rounded up to prefill_chunk) + gen")
        lengths = jnp.asarray(lengths, jnp.int32)
        n_chunks = s_max // prefill_chunk

        def body(carry, ci):
            cache, h_last = carry
            tok_c = jax.lax.dynamic_slice_in_dim(
                tokens, ci * prefill_chunk, prefill_chunk, axis=1)
            h, cache = model.prefill_chunk(
                serve_params, tok_c, cache, ci * prefill_chunk, ctx,
                lengths=lengths, kv_limit=s_max)
            # carry the last VALID hidden of each request (requests whose
            # final token lives in this chunk update; others keep theirs)
            last = lengths - 1
            here = (last >= ci * prefill_chunk) & (
                last < (ci + 1) * prefill_chunk)
            idx = jnp.clip(last - ci * prefill_chunk, 0, prefill_chunk - 1)
            h_sel = jnp.take_along_axis(h, idx[:, None, None], axis=1)
            h_last = jnp.where(here[:, None, None], h_sel.astype(h_last.dtype),
                               h_last)
            return (cache, h_last), None

        h0 = jnp.zeros((b, 1, cfg.d_model), cfg.dtype)
        (cache, h_last), _ = jax.lax.scan(body, (cache, h0),
                                          jnp.arange(n_chunks))
        logits = model.readout_fn(serve_params, ctx)(h_last)
        return logits, cache

    return prefill_step


def make_serve_step(model, cfg, policy: A.QuantPolicy, mode: str = "int8"):
    def serve_step(serve_params, qparams, tokens, cache, cur_pos,
                   slot_mask=None):
        ctx = _serve_ctx(mode, policy, qparams)
        logits, new_cache = model.decode_step(serve_params, tokens, cache,
                                              cur_pos, ctx,
                                              slot_mask=slot_mask)
        # greedy next token; make_decode_loop overrides with sample_tokens
        # when a temperature is set
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, logits, new_cache

    return serve_step


def make_decode_loop(model, cfg, policy: A.QuantPolicy, mode: str = "int8",
                     n_steps: int = 16, temperature: float = 0.0,
                     top_p: float = 1.0):
    """Whole-generation decode as ONE compiled call (the serving fast path).

    Compatibility wrapper over ``strategies.make_strategy_decode_loop``:
    ``temperature`` picks ``GreedyStrategy`` (0.0, the default — bit-
    identical greedy decoding) or ``SamplingStrategy`` (per-step key
    split, optional nucleus ``top_p``).  The scan carries (token, cache,
    position, PRNG key); N tokens cost one dispatch and XLA keeps the
    cache resident across steps.  Callers should jit with
    ``donate_argnums=(3,)`` so the input cache buffer is reused for the
    scan carry instead of doubling resident cache HBM (serve.py does).

    Returns (tokens (B, n_steps), final cache); tokens[:, 0] is ``tok0``
    (the caller's prefill argmax/sample), the rest come from the scan.
    For speculative decoding build the loop directly via
    ``strategies.make_strategy_decode_loop(...,  SpeculativeStrategy)``
    (Engine.generate_batch does).
    """
    strategy = make_strategy(None, model, cfg, policy, mode,
                             temperature=temperature, top_p=top_p)
    return make_strategy_decode_loop(model, cfg, policy, strategy,
                                     mode=mode, n_steps=n_steps)


def make_slot_decode_loop(model, cfg, policy: A.QuantPolicy,
                          mode: str = "int8", n_steps: int = 8,
                          temperature: float = 0.0, top_p: float = 1.0,
                          eos_id: int = -1):
    """One continuous-batching decode BLOCK: ``n_steps`` scanned steps over
    a slot batch where every slot sits at its own position.

    Compatibility wrapper over ``strategies.make_strategy_slot_loop``
    with the one-token strategies (greedy / sampled by ``temperature``),
    keeping the historical signature and shapes:

    Returns ``(toks (B, n_steps), emitted (B, n_steps) bool, cache,
    pos, active, key)``: ``emitted[b, i]`` marks real tokens (the EOS
    itself is emitted; everything after is padding).  The scheduler
    (launch/scheduler.py) runs strategy loops directly (so it can thread
    speculative windows and the history carry); this wrapper serves the
    tests and tooling that pin the one-token block semantics.

    ``eos_id < 0`` disables EOS detection (fixed-budget generation).
    Callers should jit with ``donate_argnums=(3,)`` like the
    single-stream loop.
    """
    strategy = make_strategy(None, model, cfg, policy, mode,
                             temperature=temperature, top_p=top_p)
    inner = make_strategy_slot_loop(model, cfg, policy, strategy,
                                    mode=mode, n_steps=n_steps,
                                    eos_id=eos_id)

    def slot_decode_loop(serve_params, qparams, tok0, cache, pos0, active0,
                         key=None):
        out = inner(serve_params, qparams, tok0, cache, pos0, active0, key)
        return out[:6]

    return slot_decode_loop
