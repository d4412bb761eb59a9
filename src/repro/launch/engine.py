"""``Engine`` — the serving facade over the int8 FAT pipeline.

Before this module, every serving surface (the CLI, the examples, the
benchmark) re-assembled the same stack by hand: config -> model -> random
or checkpointed params -> calibration pass -> int8 conversion -> jitted
step functions -> cache sizing -> prefill/decode driver.  The Engine owns
that assembly once:

    engine = Engine.from_checkpoint(arch="smollm-135m", smoke=True)
    # batched one-shot serving (prefill + scanned decode, AOT-compiled):
    result = engine.generate_batch(batch, gen=16)
    # one prompt == generate_batch at B=1 (same executables, no drift):
    result = engine.generate_one(prompt_tokens, gen=16)
    # continuous batching (slot scheduler; paged layout => prefix sharing):
    completions = engine.generate(requests, max_slots=4)

The decode scheme is an Engine-level knob too (``decode_strategy`` in
{"greedy", "sample", "speculative"} + ``spec_k``/``spec_ngram``): both
serving paths run the same ``DecodeStrategy`` (launch/strategies.py),
so speculative decoding — bit-identical tokens to greedy — is one
constructor argument away on either.

``from_checkpoint`` restores params via repro.checkpoint.manager when a
directory is given (the ``{"params": ...}`` tree train.py writes) and
falls back to seeded random init; either way it runs the paper's §2
calibration pass and (unless ``fp=True``) the int8 weight conversion —
the frozen-threshold artifact every layout of the KV cache relies on.

The cache layout is an Engine-level knob (``cache_layout`` in
{"dense", "ring", "paged"} + ``page_size``), threaded through
``model.init_cache`` and the scheduler — callers never touch layout
internals.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.core import api as A
from repro.data import pipeline as DP
from repro.launch import steps as ST
from repro.models import build_model


def prepare_int8(model, cfg, policy, params, calib_batches, *,
                 convert: bool = True, finetune_epochs: int = 0):
    """Calibration + int8 conversion (the paper's deployment pipeline).

    ``convert=False`` stops after calibration (bf16-weight ablations need
    the thresholds but not a second, immediately-discarded param pytree).
    ``finetune_epochs`` > 0 inserts the paper's §3 threshold training
    between calibration and conversion: finalize emits trainable
    ``log2_t`` KV thresholds, ``steps.finetune_thresholds`` distills
    them against the fp teacher over the same calibration batches, and
    ``freeze_thresholds`` collapses the result back to the static
    ``t_max`` form serving consumes — so everything downstream of this
    function is byte-identical in shape either way.
    """
    qparams = A.init_qparams(model, params, policy)
    calib = jax.jit(ST.make_calibrate_step(model, cfg, policy))
    calib_batches = list(calib_batches)
    for b in calib_batches:
        qparams = calib(params, qparams, b)
    qparams = A.finalize_calibration(
        qparams, policy, train_thresholds=finetune_epochs > 0)
    if finetune_epochs > 0:
        qparams, _losses = ST.finetune_thresholds(
            model, cfg, policy, params, qparams, calib_batches,
            epochs=finetune_epochs)
        qparams = A.freeze_thresholds(qparams)
    serve_params = (A.convert_to_int8(model, params, qparams, policy)
                    if convert else params)
    return serve_params, qparams


@dataclasses.dataclass
class GenerationResult:
    """Output of the single-stream batched path, with the steady-state
    timings the CLI and benchmark report."""
    tokens: jax.Array          # (B, gen) generated token ids
    prefill_s: float           # AOT-compiled prefill wall time
    decode_s: float            # decode wall time (gen - 1 steps)
    prompt_tokens: int
    gen_tokens: int
    # the compiled executables that ran ("prefill", "decode"): their
    # ``as_text()`` says which kernels the step really used
    executables: dict = dataclasses.field(default_factory=dict)


class Engine:
    """One assembled serving stack: model + converted params + finalized
    thresholds + sampling policy + cache layout.  See module docstring."""

    def __init__(self, model, cfg, policy: A.QuantPolicy, serve_params,
                 qparams, *, mode: str = "int8", cache_layout: str = "ring",
                 page_size: int = 64, prefill_chunk: Optional[int] = None,
                 temperature: float = 0.0, top_p: float = 1.0,
                 seed: int = 0, decode_strategy: Optional[str] = None,
                 spec_k: int = 4, spec_ngram: int = 2,
                 queue_cap: Optional[int] = None, shed_policy: str = "shed",
                 fault_plan=None, journal: Optional[str] = None,
                 snapshot_every: int = 0,
                 snapshot_dir: Optional[str] = None):
        """``decode_strategy`` picks the decode-loop scheme
        (strategies.STRATEGIES: "greedy" | "sample" | "speculative");
        None auto-selects from ``temperature`` (the historical behavior).
        ``spec_k``/``spec_ngram`` are the speculative draft-window and
        prompt-lookup n-gram sizes (static — no retrace across draft
        contents).

        Resilience knobs (continuous batching; see launch/scheduler.py):
        ``queue_cap`` bounds the admission queue and ``shed_policy``
        ("shed" | "block") picks the overload behavior; ``fault_plan``
        (a :class:`repro.launch.faults.FaultPlan`, or anything its
        ``parse`` accepts) injects deterministic faults for chaos testing
        and the degraded-traffic benchmark.

        Durability knobs (see launch/journal.py and the scheduler's
        recovery methods): ``journal`` is the write-ahead request
        journal path (enables ``recover()`` after a crash);
        ``snapshot_every`` > 0 writes a full state snapshot every N
        decode-block boundaries through a ``CheckpointManager`` at
        ``snapshot_dir`` (which alone enables on-demand
        ``save_state``/``load_state``)."""
        from repro.cache import LAYOUTS
        from repro.launch import strategies as SG
        from repro.launch.faults import FaultPlan

        if cache_layout not in LAYOUTS:
            raise ValueError(f"cache_layout must be one of {LAYOUTS}, got "
                             f"{cache_layout!r}")
        if shed_policy not in ("shed", "block"):
            raise ValueError(f"shed_policy must be 'shed' or 'block', got "
                             f"{shed_policy!r}")
        if fault_plan is not None:
            fault_plan = FaultPlan.parse(fault_plan)
        if decode_strategy is not None:
            # eager validation through the single authority
            # (strategies.make_strategy): unknown names,
            # strategy/temperature conflicts, and attention-only-config
            # violations all reject at construction, not first generate
            SG.make_strategy(decode_strategy, model, cfg, policy, mode,
                             temperature=temperature, top_p=top_p,
                             spec_k=spec_k, spec_ngram=spec_ngram)
        self.model, self.cfg, self.policy = model, cfg, policy
        self.serve_params, self.qparams = serve_params, qparams
        self.mode = mode
        self.cache_layout = cache_layout
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.temperature, self.top_p, self.seed = temperature, top_p, seed
        self.decode_strategy = decode_strategy
        self.spec_k, self.spec_ngram = spec_k, spec_ngram
        self.queue_cap, self.shed_policy = queue_cap, shed_policy
        self.fault_plan = fault_plan
        if snapshot_every < 0:
            raise ValueError(
                f"snapshot_every must be >= 0, got {snapshot_every}")
        if snapshot_every > 0 and snapshot_dir is None:
            raise ValueError(
                "snapshot_every > 0 needs a snapshot_dir to write to")
        self.journal = journal
        self.snapshot_every = snapshot_every
        self.snapshot_dir = snapshot_dir
        self._scheduler = None
        self._scheduler_key = None

    # -- assembly ----------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, arch: str = "smollm-135m", *,
                        cfg=None,
                        checkpoint_dir: Optional[str] = None,
                        smoke: bool = True, fp: bool = False,
                        kv_int8: bool = True, kv_bits: int = 8,
                        finetune_thresholds: int = 0,
                        use_pallas: Optional[bool] = None,
                        calib_batches: Optional[Sequence] = None,
                        n_calib: int = 2, calib_batch: int = 4,
                        calib_len: int = 32, init_seed: int = 0,
                        **engine_kw) -> "Engine":
        """Build a ready-to-serve Engine.

        ``checkpoint_dir`` restores the newest ``{"params": ...}`` tree
        written by launch/train.py (mesh-agnostic restore); without one,
        params are seeded random init (smoke/bench usage).  ``fp`` serves
        bf16 weights (baseline); ``kv_int8`` quantizes the KV cache and
        ``kv_bits`` picks its width (8, or 4 = packed nibbles — quarter
        of the bf16 cache bytes).  ``finetune_thresholds`` > 0 trains
        the KV thresholds by distillation for that many epochs before
        freezing (paper §3; the knob that makes the 7-level int4 grid
        usable when max-abs calibration over-shoots).  ``calib_batches``
        overrides the default data-pipeline calibration stream
        (``n_calib`` batches of (calib_batch, calib_len) tokens).
        Remaining ``engine_kw`` go to ``Engine.__init__`` (cache_layout,
        page_size, temperature, ...).  ``cfg`` overrides the registry
        lookup with an explicit :class:`ModelConfig` (e.g. a
        ``cfg.replace(...)`` variant with shard-divisible head counts
        for the sharded engine); ``arch``/``smoke`` are ignored then.
        """
        if cfg is None:
            cfg = get_config(arch, smoke=smoke)
        model = build_model(cfg)
        use_pallas = (jax.default_backend() == "tpu" if use_pallas is None
                      else use_pallas)
        policy = A.QuantPolicy(kv_int8=kv_int8, kv_bits=kv_bits,
                               use_pallas=use_pallas)
        params = model.init(jax.random.PRNGKey(init_seed))
        if checkpoint_dir is not None:
            from repro.checkpoint.manager import CheckpointManager

            tree, _meta = CheckpointManager(checkpoint_dir).restore_latest()
            params = tree["params"]

        if calib_batches is None:
            shape = ShapeSpec("engine", "train", calib_len, calib_batch)
            spec = DP.spec_for(cfg, shape)
            calib_batches = DP.calibration_batches(spec, n_calib)
            for b in calib_batches:
                b.pop("labels", None)

        mode = "none" if fp else "int8"
        if fp and not kv_int8:
            serve_params, qparams = params, A.finalize_calibration(
                A.init_qparams(model, params, policy), policy)
        else:
            # int8 weights and/or int8 KV both need the calibration pass;
            # bf16-weight ablations skip the weight conversion
            serve_params, qparams = prepare_int8(
                model, cfg, policy, params, calib_batches, convert=not fp,
                finetune_epochs=finetune_thresholds)
        return cls(model, cfg, policy, serve_params, qparams, mode=mode,
                   **engine_kw)

    # -- introspection -----------------------------------------------------
    def n_int8_weights(self) -> int:
        return sum(1 for l in jax.tree.leaves(self.serve_params)
                   if l.dtype == jnp.int8)

    def analyze(self, *, batch: int = 2, prompt_len: int = 32,
                cache_len: int = 64):
        """Static contract checks over THIS engine's serving graphs.

        Traces the engine's own prefill and decode entry points (its
        params, its policy, its cache layout — not the generic smoke
        assembly in repro.analysis.entrypoints) and runs the jaxpr
        analyzers plus the freeze/donation checks on the live state.
        Returns a list of findings; empty means every contract holds.
        Tracing only — no compiles, so this is cheap enough to run at
        startup or in a deploy gate.
        """
        from repro.analysis import (check_dtype_drift,
                                    check_duplicate_donation,
                                    check_frozen_qparams,
                                    check_no_fake_quant,
                                    check_pallas_jaxpr)
        from repro.kernels import ops as _ops

        expect_interpret = _ops._interpret()
        cache = self.init_cache(batch, cache_len)
        toks = jnp.zeros((batch, prompt_len), jnp.int32)
        entries = {
            "prefill": (ST.make_prefill_step(self.model, self.cfg,
                                             self.policy, self.mode),
                        (self.serve_params, self.qparams,
                         {"tokens": toks}, cache)),
            "decode_loop": (ST.make_decode_loop(self.model, self.cfg,
                                                self.policy, self.mode,
                                                n_steps=4),
                            (self.serve_params, self.qparams,
                             jnp.zeros((batch,), jnp.int32), cache,
                             jnp.int32(prompt_len))),
        }
        findings = []
        for name, (fn, args) in entries.items():
            jx = jax.make_jaxpr(fn)(*args)
            findings += check_dtype_drift(jx, entry_point=name)
            findings += check_pallas_jaxpr(jx, entry_point=name,
                                           expect_interpret=expect_interpret)
            findings += check_no_fake_quant(jx, entry_point=name)
        findings += check_frozen_qparams(self.qparams,
                                         entry_point="qparams")
        findings += check_duplicate_donation(cache, entry_point="cache",
                                             what="donated KV cache")
        return findings

    def init_cache(self, batch: int, max_len: int, **kw):
        """Engine-configured cache: layout/page_size/kv_int8/kv_bits
        applied."""
        kw.setdefault("kv_int8", bool(self.policy.kv_int8))
        kw.setdefault("layout", self.cache_layout)
        kw.setdefault("page_size", self.page_size)
        kw.setdefault("kv_bits", int(self.policy.kv_bits))
        return self.model.init_cache(batch, max_len, self.cfg.dtype, **kw)

    def _cache_len(self, prompt_len: int, gen: int) -> int:
        """Single-stream cache sizing: padded prompt + generation budget,
        rounded for the fused decode kernel (128 tiles) and the paged
        layout (whole pages)."""
        cap = prompt_len
        if self.prefill_chunk:
            # the cache must hold the PADDED prompt: chunked prefill
            # writes whole chunks (garbage tails masked by lengths)
            cap = -(-prompt_len // self.prefill_chunk) * self.prefill_chunk
        max_len = cap + gen + (self.cfg.mm_patches
                               if self.cfg.modality == "vlm" else 0)
        if self.policy.use_pallas:
            max_len = -(-max_len // 128) * 128
        if self.cache_layout == "paged":
            max_len = -(-max_len // self.page_size) * self.page_size
        return max_len

    # -- single-stream batched serving ------------------------------------
    def generate_batch(self, batch: dict, gen: int, *,
                       prompt_len: Optional[int] = None,
                       loop: bool = False) -> GenerationResult:
        """Serve one fixed batch: prefill the prompts, then decode ``gen``
        tokens (the first comes from the prefill logits).  The decode
        default is the single-dispatch scanned loop; ``loop=True`` keeps
        the legacy per-token driver for comparison.  Executables are
        AOT-compiled (lower().compile()) so the reported timings are
        steady-state with no warm-up execution — and the cache buffer is
        donated to decode, so the (possibly huge) cache is never resident
        twice."""
        model, cfg, policy = self.model, self.cfg, self.policy
        mode = self.mode
        speculative = self.decode_strategy == "speculative"
        if speculative and loop:
            raise ValueError("the legacy per-token loop has no "
                             "speculative variant (drop loop=True)")
        tokens = batch["tokens"]
        requests, s = tokens.shape
        if prompt_len is None:
            prompt_len = s
        # a speculative verify window appends spec_k + 1 entries before
        # accepting — reserve draft headroom past the generation budget.
        # Speculation also needs absolute slots: map the ring default to
        # dense (a max_len-sized dense cache serves SWA layers correctly
        # through window masks — the same aliasing the scheduler does)
        max_len = self._cache_len(prompt_len,
                                  gen + (self.spec_k if speculative else 0))
        cache_kw = {}
        if speculative and self.cache_layout == "ring":
            cache_kw["layout"] = "dense"
        cache = self.init_cache(requests, max_len, **cache_kw)

        prefill = jax.jit(
            ST.make_prefill_step(model, cfg, policy, mode=mode,
                                 prefill_chunk=self.prefill_chunk),
            donate_argnums=(3,))
        if self.prefill_chunk:
            # pad prompts to a chunk multiple; the per-request length
            # vector masks the tail, so THIS executable serves any
            # prompt length
            toks, lengths = ST.pad_for_chunked_prefill(tokens,
                                                       self.prefill_chunk)
            prefill_args = (self.serve_params, self.qparams,
                            {**batch, "tokens": toks}, cache, lengths)
        else:
            prefill_args = (self.serve_params, self.qparams, batch, cache)

        prefill_x = prefill.lower(*prefill_args).compile()
        key = jax.random.PRNGKey(self.seed)
        t0 = time.time()
        logits, cache = prefill_x(*prefill_args)
        key, sub = jax.random.split(key)
        next_tok = ST.sample_tokens(logits[:, -1, :], sub,
                                    temperature=self.temperature,
                                    top_p=self.top_p)
        next_tok.block_until_ready()
        prefill_s = time.time() - t0

        pos0 = prompt_len + (cfg.mm_patches if cfg.modality == "vlm" else 0)
        if loop:
            decode = jax.jit(
                ST.make_serve_step(model, cfg, policy, mode=mode),
                donate_argnums=(3,))
            decode_x = decode.lower(self.serve_params, self.qparams,
                                    next_tok[:, None], cache, pos0).compile()
            t0 = time.time()
            toks_out = [next_tok]
            for i in range(gen - 1):
                nxt, lg, cache = decode_x(self.serve_params, self.qparams,
                                          toks_out[-1][:, None], cache,
                                          pos0 + i)
                if self.temperature > 0:
                    key, sub = jax.random.split(key)
                    nxt = ST.sample_tokens(lg[:, -1, :], sub,
                                           temperature=self.temperature,
                                           top_p=self.top_p)
                toks_out.append(nxt)
            out = jnp.stack(toks_out, axis=1)
        elif speculative:
            from repro.launch import strategies as SG

            strat = SG.make_strategy("speculative", model, cfg, policy,
                                     mode, spec_k=self.spec_k,
                                     spec_ngram=self.spec_ngram)
            decode_loop = jax.jit(
                SG.make_strategy_decode_loop(model, cfg, policy, strat,
                                             mode=mode, n_steps=gen),
                donate_argnums=(3,))
            # prompt-lookup history: absolute position -> token, seeded
            # with the prompt and the pending first generated token
            hist = jnp.zeros((requests, max_len), jnp.int32)
            hist = hist.at[:, :prompt_len].set(
                tokens[:, :prompt_len].astype(jnp.int32))
            hist = hist.at[:, prompt_len].set(next_tok)
            pos_v = jnp.full((requests,), pos0, jnp.int32)
            decode_x = decode_loop.lower(self.serve_params, self.qparams,
                                         next_tok, cache, pos_v, key,
                                         hist).compile()
            t0 = time.time()
            out, cache = decode_x(self.serve_params, self.qparams, next_tok,
                                  cache, pos_v, key, hist)
        else:
            decode_loop = jax.jit(
                ST.make_decode_loop(model, cfg, policy, mode=mode,
                                    n_steps=gen,
                                    temperature=self.temperature,
                                    top_p=self.top_p),
                donate_argnums=(3,))
            decode_x = decode_loop.lower(self.serve_params, self.qparams,
                                         next_tok, cache, pos0, key).compile()
            t0 = time.time()
            out, cache = decode_x(self.serve_params, self.qparams, next_tok,
                                  cache, pos0, key)
        out.block_until_ready()
        decode_s = time.time() - t0
        return GenerationResult(
            tokens=out, prefill_s=prefill_s, decode_s=decode_s,
            prompt_tokens=requests * prompt_len,
            gen_tokens=int(out.shape[0]) * int(out.shape[1]),
            executables={"prefill": prefill_x, "decode": decode_x})

    # -- continuous batching -----------------------------------------------
    def make_scheduler(self, *, max_slots: int = 4, prompt_cap: int = 64,
                       gen_cap: int = 32, block_steps: int = 8,
                       eos_id: int = -1, prefix_pages: Optional[int] = None):
        """Build (or reuse) the slot scheduler for this engine's layout.
        The scheduler is cached per parameter set so repeated
        ``generate`` calls keep their compiled executables AND the paged
        layout's prefix store (shared pages persist across calls)."""
        from repro.launch.scheduler import SlotScheduler

        # the key covers every knob the scheduler bakes in — including
        # the engine-level ones — so mutating e.g. engine.prefill_chunk
        # after a generate() call rebuilds instead of serving stale config
        key = (max_slots, prompt_cap, gen_cap, block_steps, eos_id,
               prefix_pages, self.cache_layout, self.page_size,
               self.prefill_chunk, self.temperature, self.top_p, self.seed,
               self.decode_strategy, self.spec_k, self.spec_ngram,
               self.queue_cap, self.shed_policy, self.fault_plan,
               self.journal, self.snapshot_every, self.snapshot_dir)
        if self._scheduler is None or self._scheduler_key != key:
            layout = ("paged" if self.cache_layout == "paged" else "dense")
            self._scheduler = SlotScheduler(
                self.model, self.cfg, self.policy, self.serve_params,
                self.qparams, mode=self.mode, max_slots=max_slots,
                prompt_cap=prompt_cap, gen_cap=gen_cap,
                prefill_chunk=self.prefill_chunk, block_steps=block_steps,
                cache_layout=layout, page_size=self.page_size,
                prefix_pages=prefix_pages, temperature=self.temperature,
                top_p=self.top_p, eos_id=eos_id, seed=self.seed,
                strategy=self.decode_strategy, spec_k=self.spec_k,
                spec_ngram=self.spec_ngram, queue_cap=self.queue_cap,
                shed_policy=self.shed_policy, fault_plan=self.fault_plan,
                journal=self.journal, snapshot_every=self.snapshot_every,
                snapshot_dir=self.snapshot_dir)
            self._scheduler_key = key
        return self._scheduler

    def generate(self, requests: Iterable, *, max_slots: int = 4,
                 prompt_cap: Optional[int] = None,
                 gen_cap: Optional[int] = None, block_steps: int = 8,
                 eos_id: int = -1, max_blocks: Optional[int] = None):
        """Continuous batching: stream ``requests`` (launch.scheduler
        Request objects) through ``max_slots`` cache slots; returns
        Completions in finish order.  With the paged layout, repeated
        prompts admit through the prefix store with zero prefill FLOPs.
        ``prompt_cap``/``gen_cap`` default to the queue's longest prompt
        and largest budget."""
        reqs = list(requests)
        if prompt_cap is None:
            prompt_cap = max((len(r.tokens) for r in reqs), default=64)
        if gen_cap is None:
            gen_cap = max((r.max_gen for r in reqs), default=32)
        sched = self.make_scheduler(
            max_slots=max_slots, prompt_cap=prompt_cap, gen_cap=gen_cap,
            block_steps=block_steps, eos_id=eos_id)
        return sched.run(reqs, max_blocks=max_blocks)

    def health_report(self) -> dict:
        """Engine-level outcome aggregation for the continuous-batching
        path: the scheduler's ``health_stats()`` (terminal statuses,
        retirement causes, preemption/readmit/shed/deadline counters,
        and the durability counters ``recoveries``/``replayed_tokens``),
        accumulated across ``generate`` calls.  Empty before the first
        ``generate``."""
        if self._scheduler is None:
            return {}
        return self._scheduler.health_stats()

    # -- durability (see launch/journal.py and scheduler recovery) ---------
    def save_state(self) -> str:
        """Snapshot the live scheduler's full serving state through the
        ``snapshot_dir`` checkpoint manager; returns the checkpoint
        path.  Requires a scheduler (a prior ``generate``/
        ``make_scheduler``) built with ``snapshot_dir`` set."""
        if self._scheduler is None:
            raise ValueError(
                "no scheduler to snapshot — call generate()/"
                "make_scheduler() first")
        return self._scheduler.save_state()

    def load_state(self, **scheduler_kw) -> int:
        """Restore the newest snapshot into a scheduler built with
        ``scheduler_kw`` (the same ``make_scheduler`` knobs the crashed
        run used — knob mismatches raise).  Returns the restored
        decode-block counter; follow with
        ``make_scheduler(...).resume_run()`` (or just ``resume()``
        below) to drive the run to completion."""
        return self.make_scheduler(**scheduler_kw).load_state()

    def recover(self, *, max_blocks: Optional[int] = None,
                **scheduler_kw) -> list:
        """Journal-replay crash recovery end to end: build the scheduler
        (same knobs as the crashed run; the engine must have been
        constructed with the crashed run's ``journal`` path) and drive
        its :meth:`SlotScheduler.recover` to completion.  Returns ALL
        completions of the logical run."""
        return self.make_scheduler(**scheduler_kw).recover(
            max_blocks=max_blocks)

    def resume(self, *, max_blocks: Optional[int] = None,
               **scheduler_kw) -> list:
        """Snapshot-mode recovery end to end: ``load_state`` then drive
        the restored run to completion."""
        sched = self.make_scheduler(**scheduler_kw)
        sched.load_state()
        return sched.resume_run(max_blocks=max_blocks)

    # -- single prompt -----------------------------------------------------
    def generate_one(self, tokens, gen: int, **kw) -> GenerationResult:
        """Serve ONE prompt — by delegating to ``generate_batch`` at
        B == 1, never by re-deriving its own prefill/decode steps: the
        single-prompt path runs the exact executables the batched path
        runs (same strategy, same cache sizing, same AOT compile), so it
        cannot drift from it.  ``tokens`` is a 1-D prompt (list/array);
        returns the usual :class:`GenerationResult` with ``tokens`` of
        shape (1, gen)."""
        toks = jnp.asarray(tokens, jnp.int32)
        if toks.ndim != 1:
            raise ValueError(
                f"generate_one takes a single 1-D prompt, got shape "
                f"{toks.shape} (use generate_batch for batches)")
        return self.generate_batch({"tokens": toks[None, :]}, gen, **kw)
