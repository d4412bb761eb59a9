"""Slot-based continuous-batching scheduler over the int8 serving engine.

The paper's frozen static thresholds (§2) are what make this possible:
K/V dequant scales never change at serve time, so a request can be
admitted into — or evicted from — a shared int8 KV cache without any
recalibration.  The cache is one fixed-shape (max_slots, cache_len)
region per layer behind the ``repro.cache.KVCache`` protocol (dense by
default, paged with ``cache_layout="paged"``); requests stream through
slots while the COMPILED executables never change:

  * admission runs the batch-1 chunked ragged prefill (one executable for
    every prompt length: tokens pad to ``prompt_cap``, the length vector
    does the ragged masking) and splices the resulting cache region into
    the free slot — a batch-axis dynamic-update-slice for the dense
    layout, a page-pool scatter + block-table row write for paged;
  * decode runs ``steps.make_slot_decode_loop`` blocks: every slot at its
    own position (vector ``cur_pos`` through the fused decode kernel),
    inactive slots masked in attention, sampling, and cache writes;
  * eviction is pure bookkeeping — a finished slot's region is dead data
    that the next admission's prefill overwrites (slots [0, prompt) and
    per-step decode writes cover every position a future mask can see).

Prefix sharing (paged layout)
-----------------------------
Because the int8 scales are frozen and request-independent, a page
written for one request is bit-valid for every other — so the paged
scheduler keeps a host-side :class:`repro.cache.PrefixStore`: after a
prompt prefills, its full pages are snapshotted into the pool's shared
region (device copies, keyed by the prompt token hash) together with the
prompt's last-position logits.  A later request with the SAME prompt
admits with ZERO prefill FLOPs: its block-table row points at the shared
pages, the partial tail page (the one decode will append into) is copied
into the slot's private page, and the first token samples from the
stored logits.  ``prefix_stats()`` / ``call_counts()`` expose the hit
and skipped-prefill counters the acceptance test pins.

Resilience (fault isolation, deadlines, preemption, degradation)
----------------------------------------------------------------
``run()`` never aborts because one request is bad (a fault of the
server itself — a kernel that fails to compile, a device error — does
propagate).  Every request retires with a terminal ``Completion.status``:

    ok         finished normally (``finished_by``: eos | budget | capacity)
    rejected   failed admission validation (never touched the device)
    failed     an injected admission fault, or non-finite prefill/decode
               logits
    timeout    missed its ``deadline_ms`` (resident or still queued)
    preempted  evicted for a higher-priority request and the run ended
               before it could be re-admitted
    shed       dropped by the bounded admission queue under overload

Deadlines are checked at block boundaries against a per-request arrival
time.  A higher-priority waiter preempts the lowest-priority resumable
resident: the victim's host state (tokens generated so far, sampling
key, position) is parked on a re-admit queue and its slot is handed
over; re-admission rebuilds the victim's KV state with ONE ragged
prefill over prompt + generated-so-far tokens (the ``resume``
executable) — cheap and bit-valid precisely because the paper's frozen
thresholds make int8 cache state a pure function of the token sequence.
Under the paged layout the victim's shared-prefix references are
released and its block-table row is reclaimed onto its private pages
before the new resident moves in.

Per-request PRNG keys (``fold_in(seed_key, rid)``, advanced only on a
request's own active steps) make sampled outputs a function of (seed,
rid, tokens emitted) — independent of arrival order, slot placement,
and preemption.

All degraded paths are driveable deterministically through a
:class:`repro.launch.faults.FaultPlan` (see launch/faults.py); injection
is host-driven or data-driven, so faulted and clean runs share the same
compiled executables.  ``health_stats()`` exposes per-status and
per-event counters.

Slot lifecycle (see docs/serving.md for the full diagram)::

    FREE --admit(prefill into slot region | attach shared prefix)--> ACTIVE
    ACTIVE --EOS token / gen budget / cache full / deadline / NaN--> DRAINED
    ACTIVE --preempted (park host state, free the slot)--> PARKED
    PARKED --re-admit (one ``resume`` ragged prefill)--> ACTIVE
    DRAINED --collect output + terminal status--> FREE

Which slots are live, at which positions, with which arrival order —
and, for paged, which pages a slot's table points at — is DATA
(pos/active vectors, block tables), never SHAPE: one compiled executable
per piece serves every admission pattern (verified by the
jit-cache-miss-counting tests in tests/test_scheduler.py and
tests/test_cache.py).

The host loop (``SlotScheduler.run``) interleaves admission and decode
blocks: admit into every free slot, decode ``block_steps`` tokens, retire
finished slots, repeat until the queue drains.  Raggedness across
requests costs masked lanes within a block, not recompiles.
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import (KVCache, PrefixEntry, PrefixStore, copy_pages,
                         set_table_row, splice_dense_into_pages)
from repro.checkpoint.manager import CheckpointManager
from repro.core import api as A
from repro.launch import steps as ST
from repro.launch import strategies as SG
from repro.launch import telemetry as tel
from repro.launch.faults import FaultPlan, InjectedFault, SimulatedCrash
from repro.launch.journal import (RequestJournal, completion_from_dict,
                                  completion_to_dict, request_from_dict,
                                  request_to_dict)


@dataclasses.dataclass
class Request:
    """One generation request: prompt tokens + a generation budget.

    ``priority`` orders admission and picks preemption victims (higher
    wins; residents only yield to strictly higher waiters).
    ``deadline_ms`` is a completion deadline relative to ``arrive_ms``
    (None = none).  ``arrive_ms`` places the request on the run's clock
    (wall ms from run start, or virtual ms under a fault plan's
    ``ms_per_block``); requests are invisible to the scheduler before
    they arrive."""
    rid: int
    tokens: np.ndarray          # (prompt_len,) int32
    max_gen: int = 16           # generated-token budget (incl. first token)
    priority: int = 0
    deadline_ms: Optional[float] = None
    arrive_ms: float = 0.0


@dataclasses.dataclass
class Completion:
    rid: int
    prompt_len: int
    tokens: list                # generated tokens (includes EOS if hit)
    finished_by: str            # 'eos' | 'budget' | 'capacity' when ok,
                                # else mirrors ``status``
    status: str = "ok"          # ok | rejected | timeout | preempted |
                                # shed | failed
    reason: Optional[str] = None    # human-readable failure detail


@dataclasses.dataclass
class _Parked:
    """A preempted resident awaiting re-admission: everything needed to
    rebuild its device state (the tokens) plus the host state that must
    survive verbatim (sampling key carry, decode-step count)."""
    req: Request
    out: list                   # generated so far (incl. pending token)
    key: np.ndarray             # (2,) uint32 per-request key carry
    steps: int                  # decode scan steps consumed so far
    recovered: bool = False     # parked by crash recovery, not preemption


_STATUSES = ("ok", "rejected", "timeout", "preempted", "shed", "failed")
_HEALTH_KEYS = _STATUSES + (
    "eos", "budget", "capacity",            # ok retirement causes
    "preemptions", "readmits", "deadline_misses", "prefix_exhausted",
    "recoveries", "replayed_tokens")        # durability counters


@dataclasses.dataclass
class _RunState:
    """Everything ``run()`` used to keep in closure-local variables,
    hoisted into one object so a decode-block boundary can be snapshotted
    (``save_state``) and a crashed run can be rebuilt (``recover``)
    without the driver loop changing shape."""
    pos: np.ndarray             # (B,) int32 absolute positions
    active: np.ndarray          # (B,) bool
    last_tok: np.ndarray        # (B,) int32 pending token per slot
    slot_req: list              # per-slot Request (None = free)
    slot_out: list              # per-slot generated tokens (incl. pending)
    slot_steps: list            # per-slot decode scan steps consumed
    done: list                  # Completions, finish order
    n_blocks: int               # committed decode-block boundaries
    arrivals: deque             # not-yet-arrived Requests (by arrive_ms)
    pending: deque              # arrived, waiting for a slot
    readmit: deque              # _Parked preemption victims
    vclock: float               # virtual ms when plan.ms_per_block > 0
    t_start: float              # wall-clock run origin
    # rid -> host times (time.monotonic()) of t_ingest, t_admit, t_first,
    # t_last: written into its sched.request span at retirement, and kept
    # off Completion so that completions of a recovered run still compare
    # equal to an uninterrupted run's
    times: dict = dataclasses.field(default_factory=dict)


def _cache_map(fn, *trees):
    """tree.map over cache pytrees with ``KVCache`` objects as leaves."""
    return jax.tree.map(fn, *trees,
                        is_leaf=lambda x: isinstance(x, KVCache))


def _slot_cache_insert(cache, slot_cache, slot):
    """Splice a batch-1 cache pytree into slot ``slot`` of the batch
    cache via each layer's ``KVCache.splice_slot`` (dense layout: one
    dynamic-update-slice along the batch axis per layer; scale leaves
    come from the slot cache — frozen calibration, identical for every
    admission)."""
    slot = jnp.asarray(slot, jnp.int32)
    return _cache_map(lambda big, small: big.splice_slot(small, slot),
                      cache, slot_cache)


class SlotScheduler:
    """Continuous batching: admit/evict requests through a fixed slot batch.

    Parameters
    ----------
    model, cfg, policy, mode : the serving stack (same objects the Engine
        builds); attention-only text configs only — the same restriction
        as chunked prefill, checked at construction.
    serve_params, qparams : converted weights + finalized thresholds.
    max_slots : decode batch size (concurrent requests).
    prompt_cap : maximum prompt length; every prompt pads to this, the
        length vector masks the tail (one prefill executable).  Rounded up
        to a ``prefill_chunk`` multiple.
    gen_cap : per-slot generation headroom reserved in the cache.
    prefill_chunk : chunk size of the admission prefill scan; None picks
        ``max(8, min(16, prompt_cap))`` — the single home of that default
        (serve.py and the on-chip benchmark both inherit it, so the
        benchmark measures the executable the CLI serves).
    block_steps : decode-block length; admission happens at block
        boundaries, so smaller blocks = lower admission latency, larger
        blocks = fewer dispatches.
    cache_layout : "dense" (default) or "paged"; paged turns on
        prompt-prefix sharing through the page pool ("ring" is accepted
        as an alias of dense — the scheduler requires absolute slots).
    page_size : paged-layout page length (tokens per page).
    prefix_pages : size of the pool's shared prefix region, in pages
        (None = room for two full-capacity prompts).
    temperature, top_p, seed : sampling (greedy when temperature == 0).
        Sampled requests draw from per-request key streams
        (``fold_in(PRNGKey(seed), rid)``), so outputs are reproducible
        across arrival orders and preemptions.
    eos_id : generation stops for a slot when it emits this token
        (< 0 disables).
    strategy : decode strategy — a name from ``strategies.STRATEGIES``
        ("greedy" | "sample" | "speculative"), a ``DecodeStrategy``
        instance, or None (auto: sample when temperature > 0, else
        greedy — the pre-redesign behavior).  Speculative slots drain at
        different rates (1..spec_k+1 tokens per verify window); their
        raggedness is data, so the no-retrace contract is unchanged.
    spec_k, spec_ngram : speculative knobs — draft window length and the
        prompt-lookup n-gram size (both static: one compiled decode
        executable serves every draft/acceptance pattern).
    queue_cap : bound on the admission queue (None = unbounded).  When
        full, ``shed_policy`` decides: "shed" retires the newest arrival
        immediately with status 'shed'; "block" leaves arrivals waiting
        upstream until the queue drains.
    shed_policy : "shed" (default) or "block" — see ``queue_cap``.
    fault_plan : a :class:`repro.launch.faults.FaultPlan` injecting
        deterministic faults (and/or the virtual clock); None = no
        faults, wall clock.  ``crash=(k, ...)`` raises
        :class:`repro.launch.faults.SimulatedCrash` after the k-th
        decode-block boundary commits — the recovery tests' crash point.
    journal : a :class:`repro.launch.journal.RequestJournal` (or a path
        string) enabling the write-ahead request journal: admissions,
        per-boundary progress, and retirements are journaled, and
        ``recover()`` on a FRESH scheduler replays the journal to
        continue a crashed run bit-identically (journal-replay mode —
        no device state is ever saved).
    snapshot_every : > 0 writes a full state snapshot (``save_state``)
        every N decode-block boundaries through a
        ``repro.checkpoint.CheckpointManager`` at ``snapshot_dir``
        (full-snapshot mode); requires ``snapshot_dir``.
    snapshot_dir : checkpoint directory for snapshots; setting it alone
        enables on-demand ``save_state()``/``load_state()`` without the
        periodic cadence.
    """

    def __init__(self, model, cfg, policy: A.QuantPolicy, serve_params,
                 qparams, *, mode: str = "int8", max_slots: int = 4,
                 prompt_cap: int = 64, gen_cap: int = 32,
                 prefill_chunk: int | None = None, block_steps: int = 8,
                 cache_layout: str = "dense", page_size: int = 64,
                 prefix_pages: int | None = None,
                 temperature: float = 0.0, top_p: float = 1.0,
                 eos_id: int = -1, seed: int = 0,
                 strategy=None, spec_k: int = 4, spec_ngram: int = 2,
                 queue_cap: int | None = None, shed_policy: str = "shed",
                 fault_plan: FaultPlan | None = None,
                 journal=None, snapshot_every: int = 0,
                 snapshot_dir: str | None = None):
        kinds = {cfg.layer_kind(i) for i in range(cfg.n_layers)}
        wins = {cfg.attn_window(i) for i in range(cfg.n_layers)}
        if kinds - {"attn", "attn_local"} or cfg.modality != "text":
            raise ValueError(
                "slot scheduler covers attention-only text stacks "
                f"(got kinds={sorted(kinds)}, modality={cfg.modality})")
        if wins != {None}:
            raise ValueError(
                "slot scheduler needs dense caches: SWA ring buffers drop "
                f"absolute slots (got windows={sorted(map(str, wins))})")
        if cache_layout == "ring":
            cache_layout = "dense"   # no windows here: ring == dense
        if cache_layout not in ("dense", "paged"):
            raise ValueError(
                f"slot scheduler cache_layout must be dense or paged, got "
                f"{cache_layout!r}")
        if shed_policy not in ("shed", "block"):
            raise ValueError(
                f"shed_policy must be 'shed' or 'block', got "
                f"{shed_policy!r}")
        if queue_cap is not None and queue_cap < 1:
            raise ValueError(f"queue_cap must be >= 1, got {queue_cap}")
        if snapshot_every < 0:
            raise ValueError(
                f"snapshot_every must be >= 0, got {snapshot_every}")
        if snapshot_every > 0 and snapshot_dir is None:
            raise ValueError(
                "snapshot_every > 0 needs a snapshot_dir to write to")
        self.model, self.cfg = model, cfg
        self.policy, self.mode = policy, mode
        self.serve_params, self.qparams = serve_params, qparams
        self.max_slots = max_slots
        if prefill_chunk is None:
            prefill_chunk = max(8, min(16, prompt_cap))
        self.prefill_chunk = prefill_chunk
        self.prompt_cap = -(-prompt_cap // prefill_chunk) * prefill_chunk
        self.block_steps = block_steps
        self.temperature, self.top_p = temperature, top_p
        self.eos_id = eos_id
        self.cache_layout = cache_layout
        self.page_size = page_size
        self.queue_cap = queue_cap
        self.shed_policy = shed_policy
        self._plan = fault_plan if fault_plan is not None else FaultPlan()
        self._seed = int(seed)
        # durability plumbing: write-ahead journal and/or full snapshots
        if isinstance(journal, (str, os.PathLike)):
            journal = RequestJournal(journal)
        self._journal: RequestJournal | None = journal
        self._snapshot_every = int(snapshot_every)
        self._snap_mgr = (CheckpointManager(snapshot_dir, keep=3)
                          if snapshot_dir is not None else None)
        self._snap_step = 0         # monotonic snapshot counter
        self._rs: _RunState | None = None   # live run state (None = idle)
        self._epoch = 0             # journal epoch counter
        if isinstance(strategy, SG.DecodeStrategy):
            self._strategy = strategy
        else:
            self._strategy = SG.make_strategy(
                strategy, model, cfg, policy, mode,
                temperature=temperature, top_p=top_p, spec_k=spec_k,
                spec_ngram=spec_ngram)
        self._emit_w = self._strategy.emit_width
        # a speculative window appends emit_width entries before the
        # accept — reserve headroom so a slot can still fill its whole
        # generation budget (greedy: emit_width == 1, zero extra)
        cache_len = self.prompt_cap + gen_cap + (self._emit_w - 1)
        if policy.use_pallas:
            # tile the cache length for the fused decode kernel — a
            # non-tiling length pad-copies the cache every step
            cache_len = -(-cache_len // 128) * 128
        if cache_layout == "paged":
            # capacity must equal n_blocks * page_size so the dense
            # batch-1 prefill result reshapes into whole pages
            cache_len = -(-cache_len // page_size) * page_size
        self.cache_len = cache_len
        # per-request sampling keys: each admission folds its rid into
        # the seed key, so a request's stream is independent of arrival
        # order and slot placement; the carried halves live per slot
        self._base_key = jax.random.PRNGKey(seed)
        self._slot_keys = np.zeros((max_slots, 2), np.uint32)
        # re-admission ragged prefill covers positions [0, resume_cap):
        # chunked prefill writes whole chunks, so the widest resumable
        # state is the largest chunk multiple that fits the cache
        self._resume_cap = (cache_len // prefill_chunk) * prefill_chunk

        kv_int8 = bool(policy.kv_int8)
        self._kv_int8 = kv_int8
        self._n_blocks = cache_len // page_size if cache_layout == "paged" \
            else 0
        if prefix_pages is None:
            prefix_pages = 2 * self._n_blocks
        self._prefix_pages = prefix_pages if cache_layout == "paged" else 0
        # batch-1 slot cache template for admissions: DENSE regardless of
        # the batch layout — one compiled prefill executable serves every
        # layout, and the splice re-homes the tiles (prefill never donates
        # the template, so one allocation serves every admission)
        self._slot_cache0 = model.init_cache(1, cache_len, cfg.dtype,
                                             kv_int8=kv_int8,
                                             layout="dense",
                                             kv_bits=policy.kv_bits)
        # the resident batch cache lives on the instance so page contents
        # (and the prefix store pointing into them) survive across run()s
        self._cache = model.init_cache(
            max_slots, cache_len, cfg.dtype, kv_int8=kv_int8,
            layout=cache_layout, page_size=page_size,
            extra_pages=self._prefix_pages, kv_bits=policy.kv_bits)

        # paged bookkeeping: slot-private page rows + the shared-region
        # prefix store (host-side; device content lives in the pool)
        if cache_layout == "paged":
            nb = self._n_blocks
            self._private_rows = [
                np.arange(b * nb, (b + 1) * nb, dtype=np.int32)
                for b in range(max_slots)]
            self._prefix = PrefixStore(max_slots * nb, self._prefix_pages,
                                       page_size)
        else:
            self._private_rows = None
            self._prefix = None

        # trace counting: the counter bumps inside the to-be-jitted Python
        # body, which only runs when the jit cache misses — so the count
        # IS the number of compiled variants, measured on public jit
        # behavior (and per instance: each wrapper is a fresh closure).
        # call counts tick on every invocation (host-side): the prefix-
        # sharing acceptance pins prefill CALLS, not just traces.
        pieces = ["prefill", "decode", "insert", "resume"]
        if cache_layout == "paged":
            pieces += ["set_row", "copy_page"]
        self._trace_counts = {p: 0 for p in pieces}
        self._call_counts = {p: 0 for p in pieces}
        self._health = {k: 0 for k in _HEALTH_KEYS}

        def counted(name, fn):
            def wrapper(*args):
                self._trace_counts[name] += 1
                return fn(*args)
            # jit names its program after the function: the profiler's
            # module names read jit_sched_<piece>
            wrapper.__name__ = wrapper.__qualname__ = f"sched_{name}"
            return wrapper

        self._prefill_fn = jax.jit(counted("prefill", ST.make_prefill_step(
            model, cfg, policy, mode=mode, prefill_chunk=prefill_chunk)))
        # the re-admission prefill is the same maker at the resume buffer
        # width — its own jitted piece so a preempting run still leaves
        # "prefill" at one trace (widths are shape)
        self._resume_fn = jax.jit(counted("resume", ST.make_prefill_step(
            model, cfg, policy, mode=mode, prefill_chunk=prefill_chunk)))
        self._decode_fn = jax.jit(counted("decode", SG.make_strategy_slot_loop(
            model, cfg, policy, self._strategy, mode=mode,
            n_steps=block_steps, eos_id=eos_id)),
            donate_argnums=(3,))
        # strategy state: absolute-position -> token history for prompt
        # lookup (seeded per slot at admission); empty for stateless
        # strategies.  Host-resident between blocks, device during.
        hist_w = cache_len if self._strategy.stateful else 0
        self._hist = np.zeros((max_slots, hist_w), np.int32)
        # speculative observability: emitted tokens per verify window
        self._spec_emitted = 0
        self._spec_windows = 0
        if cache_layout == "paged":
            self._insert_fn = jax.jit(
                counted("insert", lambda c, sc, row: _cache_map(
                    lambda big, small: splice_dense_into_pages(big, small,
                                                               row),
                    c, sc)),
                donate_argnums=(0,))
            self._set_row_fn = jax.jit(
                counted("set_row", lambda c, slot, row: _cache_map(
                    lambda big: set_table_row(big, slot, row), c)),
                donate_argnums=(0,))
            self._copy_page_fn = jax.jit(
                counted("copy_page", lambda c, src, dst: _cache_map(
                    lambda big: copy_pages(big, src, dst), c)),
                donate_argnums=(0,))
        else:
            self._insert_fn = jax.jit(counted("insert", _slot_cache_insert),
                                      donate_argnums=(0,))

    # -- observability ----------------------------------------------------
    def executable_counts(self) -> dict:
        """Number of times each jitted piece was TRACED (== number of
        compiled variants) — the no-retrace contract says each stays at 1
        across every admission pattern AND every fault plan (including
        shared-prefix admissions and preemption re-admissions: block-table
        rows, masks, and nan-step vectors are data).  ``resume`` stays 0
        until a preemption actually re-admits."""
        return dict(self._trace_counts)

    def call_counts(self) -> dict:
        """Host-side invocation counts per piece.  ``prefill`` is the
        number of admissions that actually ran the model — a prefix-store
        hit admits without bumping it (the zero-prefill-FLOPs counter);
        ``resume`` counts preemption re-admissions."""
        return dict(self._call_counts)

    def check_budgets(self):
        """The no-retrace contract as findings: this scheduler's live
        trace counts against the declared per-piece budgets
        (repro.analysis.budgets.SCHEDULER_BUDGETS).  Empty list == within
        budget; the analysis CI lane runs this after a real mixed-
        admission session, and operators can call it on a production
        scheduler at any point."""
        from repro.analysis.budgets import check_executable_budgets
        return check_executable_budgets(self.executable_counts(),
                                        entry_point="scheduler")

    def prefix_stats(self) -> dict:
        """Prefix-sharing counters (paged layout; empty dict for dense)."""
        return self._prefix.stats() if self._prefix is not None else {}

    def health_stats(self) -> dict:
        """Resilience counters: terminal statuses (``ok``/``rejected``/
        ``timeout``/``preempted``/``shed``/``failed``), ok retirement
        causes (``eos``/``budget``/``capacity``), events (``preemptions``,
        ``readmits``, ``deadline_misses``, ``prefix_exhausted`` — prefix
        registrations skipped because the shared pool had no evictable
        pages), and durability counters (``recoveries`` — completed
        ``recover()`` calls; ``replayed_tokens`` — prompt+generated
        tokens re-prefilled through the ``resume`` executable during
        journal-replay recovery).

        Semantics are CUMULATIVE over the scheduler's lifetime: counters
        accumulate across every ``run()``/``recover()`` on this instance
        and are never reset implicitly (pinned by
        tests/test_recovery.py).  Call :meth:`reset_health` for a
        per-window view; snapshot restore (``load_state``) REPLACES the
        counters with the snapshot's, journal recovery re-derives
        terminal-status counts from the replayed retirements."""
        return dict(self._health)

    def reset_health(self):
        """Zero the cumulative ``health_stats`` counters (explicit reset
        is the only reset — see ``health_stats`` semantics)."""
        self._health = {k: 0 for k in _HEALTH_KEYS}

    def spec_stats(self) -> dict:
        """Speculative-decoding counters (empty dict for one-token
        strategies).  ``acceptance_rate`` is accepted drafts per drafted
        token: a verify window emits 1 + accepted tokens, so the rate is
        (emitted/windows - 1) / spec_k in [0, 1]."""
        if self._emit_w == 1:
            return {}
        k = self._emit_w - 1
        wins = max(self._spec_windows, 1)
        return {
            "emitted_tokens": int(self._spec_emitted),
            "verify_windows": int(self._spec_windows),
            "draft_k": k,
            "tokens_per_window": self._spec_emitted / wins,
            "acceptance_rate": max(self._spec_emitted / wins - 1.0, 0.0) / k,
        }

    # -- counted invocation helpers ---------------------------------------
    def _prefill(self, *args):
        self._call_counts["prefill"] += 1
        return self._prefill_fn(*args)

    def _resume(self, *args):
        self._call_counts["resume"] += 1
        return self._resume_fn(*args)

    def _decode(self, *args):
        self._call_counts["decode"] += 1
        return self._decode_fn(*args)

    # -- one serving session ----------------------------------------------
    def _fresh_rs(self, requests: Iterable[Request]) -> _RunState:
        B = self.max_slots
        return _RunState(
            pos=np.zeros((B,), np.int32), active=np.zeros((B,), bool),
            last_tok=np.zeros((B,), np.int32), slot_req=[None] * B,
            slot_out=[[] for _ in range(B)], slot_steps=[0] * B, done=[],
            n_blocks=0,
            arrivals=deque(sorted(requests, key=lambda r: r.arrive_ms)),
            pending=deque(), readmit=deque(), vclock=0.0,
            t_start=time.monotonic())

    def _knobs(self) -> dict:
        """The scheduler knobs a recovered run must match for replay to
        be bit-valid (recorded in journal ``begin`` records and snapshot
        metadata; checked by ``recover``/``load_state``)."""
        return {
            "max_slots": self.max_slots, "prompt_cap": self.prompt_cap,
            "block_steps": self.block_steps,
            "cache_layout": self.cache_layout,
            "page_size": (self.page_size if self.cache_layout == "paged"
                          else None),
            "cache_len": self.cache_len,
            "prefill_chunk": self.prefill_chunk, "mode": self.mode,
            "temperature": self.temperature, "top_p": self.top_p,
            "seed": self._seed, "eos_id": self.eos_id,
            "emit_width": self._emit_w,
        }

    def _check_knobs(self, knobs: dict):
        want = self._knobs()
        got = {k: knobs.get(k) for k in want}
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        if bad:
            raise ValueError(
                "recovery scheduler knobs do not match the crashed run's "
                "(replay would not be bit-valid): " +
                ", ".join(f"{k}: saved={s!r} vs live={l!r}"
                          for k, (s, l) in sorted(bad.items())))

    def run(self, requests: Iterable[Request],
            max_blocks: Optional[int] = None) -> list[Completion]:
        """Serve ``requests`` to completion through the slot batch.

        Admission is streaming: requests become visible at their
        ``arrive_ms``, wait in a (optionally bounded) pending queue, and
        enter whenever a slot frees — highest priority first, FIFO within
        a priority, parked re-admissions preferred on ties.  The number
        of concurrent residents never exceeds ``max_slots`` while
        raggedness (arrival time, prompt length, budget) stays data.

        Every request retires with a terminal ``status`` (see the module
        docstring's taxonomy); a bad request never aborts the run.
        Returns completions in finish order.  ``max_blocks`` bounds the
        decode blocks (None = drain fully); parked preemption victims
        still waiting at the cut retire as 'preempted'.

        With a ``journal``, the run is write-ahead journaled (a new epoch
        per run); a ``FaultPlan.crash`` boundary raises
        :class:`~repro.launch.faults.SimulatedCrash` out of this method —
        recover on a FRESH scheduler via :meth:`recover` (journal replay)
        or :meth:`load_state` + :meth:`resume_run` (snapshot).
        """
        rs = self._fresh_rs(requests)
        self._rs = rs
        if self._journal is not None:
            self._epoch = max(self._epoch,
                              self._journal.last_epoch()) + 1
            self._journal.begin(self._epoch, self._knobs())
            for req in rs.arrivals:
                self._journal.enqueue(req)
        return self._drive(max_blocks, "run", t0=rs.t_start)

    def resume_run(self,
                   max_blocks: Optional[int] = None) -> list[Completion]:
        """Continue driving a restored run state (``load_state`` or a
        prior interrupted drive) to completion.  Returns ALL completions
        of the logical run — the pre-crash ones restored with the state
        plus everything finished after.  ``max_blocks`` counts TOTAL
        decode blocks of the logical run (it compares against the
        restored block counter)."""
        if self._rs is None:
            raise ValueError(
                "no run state to resume (call load_state(), recover(), "
                "or run() first)")
        return self._drive(max_blocks, "resume")

    def _drive(self, max_blocks: Optional[int], entry: str,
               t0: Optional[float] = None) -> list[Completion]:
        """The scheduler host loop over ``self._rs`` (see ``run``), inside
        one ``sched.run`` span (``t0``: the run's origin, for ``run()``).

        Spans (``launch/telemetry.py``; docs/serving.md "Spans"): the
        leaves tile the loop, so from the run's start to its end every
        instant lies in exactly one of ``sched.loop`` (the iteration's
        bookkeeping: deadlines, arrivals, preemption, choosing a waiter,
        retirements), ``sched.wait`` (asleep until the next arrival), the
        children of ``sched.admit``/``sched.readmit``, and the children of
        ``sched.decode``: ``decode.transfer`` (host arrays to the device),
        ``decode.call`` (the jitted block, until it returns),
        ``decode.fetch`` (its outputs on the host), ``decode.collect``
        (emissions and retirements) and ``decode.commit``
        (``_boundary_commit``).  The decode spans sit here, around the
        call site, not inside ``_decode``: a caller that replaces
        ``_decode`` with a wrapper that waits for the block (a benchmark
        harness) moves the device time into ``decode.call``, and
        ``decode.fetch`` is then a pure copy; unwrapped, ``decode.call``
        is the dispatch and ``decode.fetch`` holds the wait.  Each
        retirement writes a ``sched.request`` span from the request's due
        time to its retirement, with its host times as attributes."""
        with tel.span("sched.run", t0=t0, slots=self.max_slots,
                      mode=self.mode, entry=entry):
            return self._loop(max_blocks)

    def _loop(self, max_blocks: Optional[int]) -> list[Completion]:
        plan = self._plan
        B = self.max_slots
        rs = self._rs

        def now_ms() -> float:
            if plan.ms_per_block > 0:
                return rs.vclock
            return (time.monotonic() - rs.t_start) * 1e3

        def finish(req: Request, out: list, why: str, status: str = "ok",
                   reason: Optional[str] = None):
            c = Completion(req.rid, len(req.tokens), out, why,
                           status=status, reason=reason)
            rs.done.append(c)
            self._health[status] += 1
            if status == "ok":
                self._health[why] += 1
            if self._journal is not None:
                self._journal.retire(c)
            t1 = time.monotonic()
            times = rs.times.pop(req.rid, {})
            # due on the host clock; under a virtual clock arrivals follow
            # virtual ms, so the request is due when it was noticed
            due = (rs.t_start + req.arrive_ms * 1e-3
                   if plan.ms_per_block <= 0 else times.get("t_ingest", t1))
            tel.record("sched.request", due, t1, rid=req.rid, status=status,
                       n_tokens=len(out), **{
                           k: times.get(k) for k in
                           ("t_ingest", "t_admit", "t_first", "t_last")})

        def retire(slot: int, why: str, status: str = "ok",
                   reason: Optional[str] = None):
            req = rs.slot_req[slot]
            finish(req, rs.slot_out[slot], why, status, reason)
            rs.slot_req[slot] = None
            rs.slot_out[slot] = []
            rs.active[slot] = False
            if self._prefix is not None:
                self._prefix.release(slot)

        def overdue(req: Request) -> bool:
            return (req.deadline_ms is not None
                    and now_ms() - req.arrive_ms >= req.deadline_ms)

        def resumable(slot: int) -> bool:
            # the parked state (prompt + generated minus the pending
            # token) must fit the resume prefill's buffer
            return int(rs.pos[slot]) <= self._resume_cap

        def preempt(slot: int):
            req = rs.slot_req[slot]
            rs.readmit.append(_Parked(req=req, out=rs.slot_out[slot],
                                      key=self._slot_keys[slot].copy(),
                                      steps=rs.slot_steps[slot]))
            self._health["preemptions"] += 1
            rs.slot_req[slot] = None
            rs.slot_out[slot] = []
            rs.active[slot] = False
            if self._prefix is not None:
                # drop shared-page references and reclaim the table row
                # onto the slot's private pages before a new resident
                # moves in
                self._prefix.release(slot)
                self._set_row(slot, self._private_rows[slot])

        def reap_deadlines():
            for slot in range(B):
                req = rs.slot_req[slot]
                if req is not None and overdue(req):
                    self._health["deadline_misses"] += 1
                    retire(slot, "timeout", status="timeout",
                           reason=f"deadline {req.deadline_ms:g} ms "
                                  "exceeded while decoding")
            for q in (rs.pending, rs.readmit):
                kept = []
                for item in q:
                    req = item.req if isinstance(item, _Parked) else item
                    if overdue(req):
                        self._health["deadline_misses"] += 1
                        out = item.out if isinstance(item, _Parked) else []
                        finish(req, out, "timeout", status="timeout",
                               reason=f"deadline {req.deadline_ms:g} ms "
                                      "exceeded while queued")
                    else:
                        kept.append(item)
                q.clear()
                q.extend(kept)

        def ingest():
            while rs.arrivals and rs.arrivals[0].arrive_ms <= now_ms():
                rs.times.setdefault(rs.arrivals[0].rid, {}).setdefault(
                    "t_ingest", time.monotonic())
                if (self.queue_cap is not None
                        and len(rs.pending) >= self.queue_cap):
                    if self.shed_policy == "shed":
                        req = rs.arrivals.popleft()
                        finish(req, [], "shed", status="shed",
                               reason=f"admission queue full "
                                      f"(queue_cap={self.queue_cap})")
                        continue
                    break   # "block": arrivals wait upstream
                rs.pending.append(rs.arrivals.popleft())

        def next_waiter():
            """Highest-priority waiter; FIFO within a priority, parked
            re-admissions preferred on ties (their device work is already
            partly spent).  Plain FIFO when every priority is equal —
            the pre-resilience admission order."""
            best = None     # (source, index, priority)
            for i, p in enumerate(rs.readmit):
                if best is None or p.req.priority > best[2]:
                    best = ("readmit", i, p.req.priority)
            for i, r in enumerate(rs.pending):
                if best is None or r.priority > best[2]:
                    best = ("pending", i, r.priority)
            if best is None:
                return None
            src, i, _ = best
            q = rs.readmit if src == "readmit" else rs.pending
            item = q[i]
            del q[i]
            return item

        def force_preempts():
            for rid in plan.preempts_at(rs.n_blocks):
                for slot in range(B):
                    req = rs.slot_req[slot]
                    if (req is not None and req.rid == rid
                            and resumable(slot)):
                        preempt(slot)

        def priority_preempt():
            """One preemption per boundary: when no slot is free and a
            waiter strictly outranks the lowest-priority resumable
            resident, evict that resident."""
            if not (rs.pending or rs.readmit):
                return
            if any(rs.slot_req[s] is None for s in range(B)):
                return
            waiter_pri = max(
                [p.req.priority for p in rs.readmit]
                + [r.priority for r in rs.pending])
            victims = [s for s in range(B)
                       if rs.slot_req[s] is not None and resumable(s)]
            if not victims:
                return
            s = min(victims, key=lambda s: (rs.slot_req[s].priority, s))
            if rs.slot_req[s].priority < waiter_pri:
                preempt(s)

        def seed_host_state(slot: int, req: Request, out: list,
                            key: np.ndarray, steps: int):
            L = len(req.tokens)
            if self._strategy.stateful:
                seq = list(np.asarray(req.tokens, np.int32)) + list(out)
                self._hist[slot] = 0
                self._hist[slot, :len(seq)] = np.asarray(seq, np.int32)
            rs.slot_req[slot] = req
            rs.slot_out[slot] = out
            rs.pos[slot] = L + len(out) - 1
            rs.last_tok[slot] = int(out[-1])
            rs.active[slot] = True
            rs.slot_steps[slot] = steps
            self._slot_keys[slot] = key

        def admit_free_slots(loop: tel.Span):
            # each admission pauses the sched.loop span around it
            for slot in range(B):
                if rs.slot_req[slot] is not None:
                    continue
                while True:
                    item = next_waiter()
                    if item is None:
                        return
                    if isinstance(item, _Parked):
                        with loop.paused():
                            self._readmit(slot, item.req, item.out,
                                          recovered=item.recovered)
                        seed_host_state(slot, item.req, item.out,
                                        item.key, item.steps)
                        break
                    req = item
                    err = self._check(req)
                    if err is not None:
                        finish(req, [], "rejected", status="rejected",
                               reason=err)
                        continue
                    try:
                        with loop.paused():
                            t0, key = self._admit(slot, req)
                    except (InjectedFault, FloatingPointError) as e:
                        # isolation: a fault of THIS request (injected,
                        # or non-finite prefill logits) retires it and
                        # the run keeps serving.  Anything else — a
                        # kernel that fails to lower or compile, a
                        # device error — is the server's fault, not the
                        # request's, and propagates
                        finish(req, [], "failed", status="failed",
                               reason=f"{type(e).__name__}: {e}")
                        continue
                    seed_host_state(slot, req, [int(t0)], key, steps=0)
                    if self.eos_id >= 0 and int(t0) == self.eos_id:
                        retire(slot, "eos")
                    elif req.max_gen <= 1:
                        retire(slot, "budget")
                    break

        while rs.arrivals or rs.pending or rs.readmit or rs.active.any():
            with tel.span("sched.loop") as loop:
                reap_deadlines()
                ingest()
                force_preempts()
                priority_preempt()
                admit_free_slots(loop)
                n_active = int(rs.active.sum())
                idle = n_active == 0
                wait = idle and bool(rs.arrivals) and not rs.pending \
                    and not rs.readmit
                if wait and plan.ms_per_block > 0:
                    # nothing runnable until the next arrival: advance
                    # the clock to it instead of spinning
                    rs.vclock = max(rs.vclock, rs.arrivals[0].arrive_ms)
            if idle:
                if wait and plan.ms_per_block <= 0:
                    # nothing runnable, nothing to reap: sleep to the next
                    # arrival
                    with tel.span("sched.wait"):
                        time.sleep(max(0.0, (rs.arrivals[0].arrive_ms
                                             - now_ms()) * 1e-3))
                continue

            # -- one decode block over the slot batch ----------------------
            with tel.span("sched.decode", block=rs.n_blocks,
                          active=n_active) as block:
                with tel.span("decode.transfer"):
                    # nan_step: per-slot in-block scan step at which a
                    # scheduled decode fault fires (-1 = none) — data, not
                    # shape
                    nan_step = np.full((B,), -1, np.int32)
                    for slot in range(B):
                        req = rs.slot_req[slot]
                        if req is None or not rs.active[slot]:
                            continue
                        step = plan.nan_decode_step(req.rid)
                        if step is not None:
                            rel = step - rs.slot_steps[slot]
                            if 0 <= rel < self.block_steps:
                                nan_step[slot] = rel
                    ran = rs.active.copy()
                    args = (self.serve_params, self.qparams,
                            jnp.asarray(rs.last_tok), self._cache,
                            jnp.asarray(rs.pos), jnp.asarray(rs.active),
                            jnp.asarray(self._slot_keys),
                            jnp.asarray(self._hist), jnp.asarray(nan_step))
                with tel.span("decode.call"):
                    toks, emitted, self._cache, pos_d, active_d, keys_d, \
                        hist, bad_d = self._decode(*args)
                    del args        # the cache argument is donated
                with tel.span("decode.fetch") as fetch:
                    toks = np.asarray(toks)
                    emitted = np.asarray(emitted)
                    pos_new = np.asarray(pos_d)
                    active_new = np.asarray(active_d)
                    bad = np.asarray(bad_d)
                    # host copies: admission mutates rows in place
                    # (np.asarray of a device buffer is read-only)
                    self._hist = np.array(hist)
                    self._slot_keys = np.array(keys_d)
                with tel.span("decode.collect"):
                    block.attrs["kept"] = self._collect(
                        rs, ran, toks, emitted, pos_new, active_new, bad,
                        fetch.t1, retire)
                    rs.n_blocks += 1
                    if plan.ms_per_block > 0:
                        rs.vclock += plan.ms_per_block
                # -- boundary commit: WAL flush, snapshot cadence, crash --
                with tel.span("decode.commit"):
                    self._boundary_commit(rs, now_ms())
            if max_blocks is not None and rs.n_blocks >= max_blocks:
                break
        with tel.span("sched.loop"):
            # parked victims the run never got back to are terminal too —
            # with their generated-so-far tokens, so nothing is silently
            # lost
            while rs.readmit:
                p = rs.readmit.popleft()
                finish(p.req, p.out, "preempted", status="preempted",
                       reason="preempted; run ended before re-admission")
            # no resident remains (or the run was cut): drop any prefix-
            # store references this run's slots held so unused entries
            # stay evictable
            if self._prefix is not None:
                for slot in range(B):
                    self._prefix.release(slot)
        return rs.done

    def _collect(self, rs: _RunState, ran, toks, emitted, pos_new,
                 active_new, bad, t_fetch: float, retire) -> int:
        """Take one decode block's emissions into the run state and retire
        the slots it finished; returns the tokens kept.  ``t_fetch``: when
        the block's outputs reached the host (each request's t_last)."""
        B = self.max_slots
        for slot in range(B):
            if ran[slot]:
                rs.slot_steps[slot] += self.block_steps
        if self._emit_w > 1:
            # a window with any emission ran a live verify pass
            win = emitted.reshape(B, self.block_steps, self._emit_w)
            self._spec_windows += int(win.any(-1).sum())
            self._spec_emitted += int(emitted.sum())

        # emission lanes are RAGGED within a speculative window (a partial
        # accept leaves un-emitted tail lanes, then the next window emits
        # again) — skip gaps instead of stopping at one
        kept = 0
        for slot in range(B):
            req = rs.slot_req[slot]
            if req is None or not rs.active[slot]:
                continue
            had = len(rs.slot_out[slot])
            for i in range(self.block_steps * self._emit_w):
                if len(rs.slot_out[slot]) >= req.max_gen:
                    break
                if not emitted[slot, i]:
                    continue
                rs.slot_out[slot].append(int(toks[slot, i]))
            if len(rs.slot_out[slot]) > had:
                kept += len(rs.slot_out[slot]) - had
                rs.times.setdefault(req.rid, {})["t_last"] = t_fetch
            rs.pos[slot] = pos_new[slot]
            rs.last_tok[slot] = (rs.slot_out[slot][-1]
                                 if rs.slot_out[slot]
                                 else rs.last_tok[slot])
            # finish reason from what was actually COLLECTED: an EOS beyond
            # the budget cut was never part of the output, so that request
            # finished by budget, not eos — and a device-side freeze
            # without a collected EOS and with budget to spare can only be
            # the NaN guard (flagged in ``bad``) or the capacity guard
            hit_eos = (self.eos_id >= 0 and bool(rs.slot_out[slot])
                       and rs.slot_out[slot][-1] == self.eos_id)
            budget_done = len(rs.slot_out[slot]) >= req.max_gen
            if hit_eos:
                retire(slot, "eos")
            elif budget_done:
                retire(slot, "budget")
            elif bad[slot]:
                retire(slot, "failed", status="failed",
                       reason="non-finite logits during decode")
            elif not active_new[slot]:
                retire(slot, "capacity")
            else:
                rs.active[slot] = active_new[slot]
        return kept

    def _boundary_commit(self, rs: _RunState, clock_ms: float):
        """Everything that makes a decode-block boundary DURABLE, in the
        WAL order the journal module documents: retire records were
        already flushed as they happened, so write progress (absolute
        host state per in-flight request — residents then parked), then
        the ``block`` marker, then the optional periodic snapshot, and
        only THEN fire a scheduled simulated crash — a crash can never
        observe a boundary whose records are not durable."""
        if self._journal is not None:
            for slot in range(self.max_slots):
                req = rs.slot_req[slot]
                if req is not None:
                    self._journal.progress(
                        req.rid, rs.slot_out[slot],
                        self._slot_keys[slot], rs.slot_steps[slot])
            for p in rs.readmit:
                self._journal.progress(p.req.rid, p.out, p.key, p.steps)
            self._journal.block(rs.n_blocks, clock_ms)
        if (self._snap_mgr is not None and self._snapshot_every > 0
                and rs.n_blocks % self._snapshot_every == 0):
            self.save_state()
        if self._plan.crash_at(rs.n_blocks):
            raise SimulatedCrash(
                f"simulated crash at decode-block boundary {rs.n_blocks} "
                "(recover on a fresh scheduler: recover() replays the "
                "journal, load_state() restores the last snapshot)")

    # -- crash recovery ----------------------------------------------------
    def recover(self,
                max_blocks: Optional[int] = None) -> list[Completion]:
        """Journal-replay crash recovery, on a FRESH scheduler pointed at
        the crashed run's journal: no device state is read back at all.
        The journal's last epoch classifies every request — retired
        completions are re-emitted verbatim, in-flight requests
        (resident or parked at the crash) park on the re-admit queue and
        rebuild their int8 KV state with one ``resume`` ragged prefill
        over prompt + generated-so-far tokens (bit-valid because the
        paper's frozen §2 thresholds make cache state a pure function of
        the token sequence), and never-admitted requests re-enter the
        arrival queue.  The surviving state is re-written as a fresh
        journal epoch first, so repeated crash/recover cycles stay
        replayable.  Greedy completions are bit-identical to an
        uninterrupted run (tests/test_recovery.py pins it); sampled ones
        too, because each request's carried PRNG key rides the journal.

        Returns ALL completions of the logical run (pre-crash retirees
        included).  ``max_blocks`` counts total decode blocks (the block
        counter resumes from the crash boundary)."""
        if self._journal is None:
            raise ValueError(
                "recover() needs a journal: construct the scheduler with "
                "journal=<path of the crashed run's journal>")
        rp = self._journal.replay()
        self._check_knobs(rp.knobs)
        rs = self._fresh_rs([])
        rs.n_blocks = rp.n_blocks
        # resume the run clock where the crash left it: virtual clocks
        # restore exactly; wall clocks restart offset by the journaled
        # elapsed ms (deadline fidelity across recovery needs the
        # virtual clock — wall time lost to the outage is invisible)
        if self._plan.ms_per_block > 0:
            rs.vclock = rp.vclock
        rs.t_start = time.monotonic() - rp.vclock * 1e-3
        for d in rp.done:
            c = completion_from_dict(d)
            rs.done.append(c)
            # re-derive the terminal-status counters the crash erased
            self._health[c.status] += 1
            if c.status == "ok":
                self._health[c.finished_by] += 1
        for item in rp.inflight:
            req = request_from_dict(item["req"])
            out = [int(t) for t in item["out"]]
            if len(req.tokens) + len(out) - 1 <= self._resume_cap:
                rs.readmit.append(_Parked(
                    req=req, out=out,
                    key=np.asarray(item["key"], np.uint32),
                    steps=int(item["steps"]), recovered=True))
            else:
                # parked state too wide for the resume executable (its
                # buffer covers whole prefill chunks only — the same
                # bound ``resumable()`` enforces before preempting, but
                # a crash cannot refuse): re-serve from scratch.  Still
                # bit-identical — greedy tokens are a pure function of
                # the prompt, and the sampling stream restarts from the
                # same fold_in(seed, rid) key — at the cost of
                # re-decoding what was already generated
                self._health["replayed_tokens"] += (len(req.tokens)
                                                    + len(out))
                rs.pending.append(req)
        rs.arrivals = deque(sorted(
            (request_from_dict(d) for d in rp.queued),
            key=lambda r: r.arrive_ms))
        self._health["recoveries"] += 1
        self._rs = rs
        self._epoch = max(self._epoch, rp.epoch)
        self._rewrite_epoch(rs)
        return self._drive(max_blocks, "recover")

    def _rewrite_epoch(self, rs: _RunState):
        """Start a fresh journal epoch that re-states the surviving run
        state (retirees, in-flight progress, queued requests), so replay
        after a SECOND crash still sees one complete epoch."""
        j = self._journal
        if j is None:
            return
        self._epoch = max(self._epoch, j.last_epoch()) + 1
        j.begin(self._epoch, self._knobs(), recovered=True)
        for c in rs.done:
            j.retire(c)
        for slot in range(self.max_slots):
            req = rs.slot_req[slot]
            if req is not None:
                j.enqueue(req)
                j.progress(req.rid, rs.slot_out[slot],
                           self._slot_keys[slot], rs.slot_steps[slot])
        for p in rs.readmit:
            j.enqueue(p.req)
            j.progress(p.req.rid, p.out, p.key, p.steps)
        for req in list(rs.pending) + list(rs.arrivals):
            j.enqueue(req)
        j.block(rs.n_blocks, rs.vclock)

    # -- full-state snapshot (save/load through CheckpointManager) ---------
    def save_state(self) -> str:
        """Write a full snapshot of the serving state through the
        checkpoint manager at ``snapshot_dir``: every cache layer's
        ``state_dict()`` (int8 pages/slots + frozen scales), the host
        decode vectors (positions, active mask, pending tokens, per-slot
        PRNG keys, strategy history), the run bookkeeping (residents,
        queues, parked victims, completions, block counter, clock), the
        prefix store (entries, refcounts, stored logits), and the health
        counters.  Atomic (temp dir + rename) and keep-N via
        ``CheckpointManager`` — the same fault-tolerance contract
        training checkpoints get.  Returns the checkpoint path."""
        if self._snap_mgr is None:
            raise ValueError(
                "save_state() needs a snapshot_dir (construct the "
                "scheduler with snapshot_dir=...)")
        rs = self._rs if self._rs is not None else self._fresh_rs([])
        leaves = [c for c in jax.tree.leaves(
            self._cache, is_leaf=lambda x: isinstance(x, KVCache))]
        tree = {
            "cache": {str(i): c.state_dict()
                      for i, c in enumerate(leaves)},
            "host": {"pos": rs.pos, "active": rs.active,
                     "last_tok": rs.last_tok,
                     "slot_keys": self._slot_keys, "hist": self._hist},
        }
        clock_ms = (rs.vclock if self._plan.ms_per_block > 0
                    else (time.monotonic() - rs.t_start) * 1e3)

        def parked_d(p: _Parked) -> dict:
            return {"req": request_to_dict(p.req),
                    "out": [int(t) for t in p.out],
                    "key": [int(k) for k in p.key],
                    "steps": int(p.steps), "recovered": bool(p.recovered)}

        state = {
            "knobs": self._knobs(),
            "slot_req": [None if r is None else request_to_dict(r)
                         for r in rs.slot_req],
            "slot_out": [[int(t) for t in out] for out in rs.slot_out],
            "slot_steps": [int(s) for s in rs.slot_steps],
            "done": [completion_to_dict(c) for c in rs.done],
            "arrivals": [request_to_dict(r) for r in rs.arrivals],
            "pending": [request_to_dict(r) for r in rs.pending],
            "readmit": [parked_d(p) for p in rs.readmit],
            "n_blocks": int(rs.n_blocks), "clock_ms": float(clock_ms),
            "health": {k: int(v) for k, v in self._health.items()},
            "epoch": int(self._epoch),
        }
        if self._prefix is not None:
            psd = self._prefix.state_dict()
            # logits are arrays — route them through the npz tree, keep
            # the rest JSON (entry i's logits live at prefix_logits[i])
            tree["prefix_logits"] = {
                str(i): e.pop("logits")
                for i, e in enumerate(psd["entries"])}
            state["prefix"] = psd
        self._snap_step = max([self._snap_step]
                              + self._snap_mgr.list_steps()) + 1
        return self._snap_mgr.save(self._snap_step, tree,
                                   metadata={"state": state})

    def load_state(self) -> int:
        """Restore the newest committed snapshot from ``snapshot_dir``
        into THIS scheduler (typically a fresh instance standing in for
        a crashed process), rebuilding the device cache bit-exactly from
        the saved int8 arrays — full-snapshot recovery, the
        gigabytes-back alternative to journal replay.  Knobs must match
        the saving scheduler's.  Follow with :meth:`resume_run` to drive
        the restored run to completion; decode continues at the
        snapshot's block boundary, so completions are bit-identical to
        an uninterrupted run.  Returns the restored block counter."""
        if self._snap_mgr is None:
            raise ValueError(
                "load_state() needs a snapshot_dir (construct the "
                "scheduler with snapshot_dir=...)")
        tree, meta = self._snap_mgr.restore_latest()
        if tree is None:
            raise FileNotFoundError(
                f"no committed snapshot under {self._snap_mgr.dir}")
        st = meta["state"]
        self._check_knobs(st["knobs"])
        tmpl, treedef = jax.tree.flatten(
            self._cache, is_leaf=lambda x: isinstance(x, KVCache))
        saved = tree["cache"]
        if len(saved) != len(tmpl):
            raise ValueError(
                f"snapshot has {len(saved)} cache layers, scheduler has "
                f"{len(tmpl)} (wrong snapshot for this config?)")
        leaves = []
        for i, t in enumerate(tmpl):
            c = KVCache.from_state_dict(saved[str(i)])
            for n in type(t)._child_names():
                a, b = getattr(c, n), getattr(t, n)
                if a.shape != b.shape or a.dtype != b.dtype:
                    raise ValueError(
                        f"snapshot cache layer {i} child {n!r} is "
                        f"{a.shape}/{a.dtype}, scheduler expects "
                        f"{b.shape}/{b.dtype}")
            leaves.append(c)
        self._cache = jax.tree.unflatten(treedef, leaves)
        host = tree["host"]
        self._slot_keys = np.array(host["slot_keys"], np.uint32)
        self._hist = np.array(host["hist"], np.int32)
        rs = self._fresh_rs([])
        rs.pos = np.array(host["pos"], np.int32)
        rs.active = np.array(host["active"], bool)
        rs.last_tok = np.array(host["last_tok"], np.int32)
        rs.slot_req = [None if d is None else request_from_dict(d)
                       for d in st["slot_req"]]
        rs.slot_out = [[int(t) for t in out] for out in st["slot_out"]]
        rs.slot_steps = [int(s) for s in st["slot_steps"]]
        rs.done = [completion_from_dict(d) for d in st["done"]]
        rs.arrivals = deque(request_from_dict(d) for d in st["arrivals"])
        rs.pending = deque(request_from_dict(d) for d in st["pending"])
        rs.readmit = deque(
            _Parked(req=request_from_dict(p["req"]),
                    out=[int(t) for t in p["out"]],
                    key=np.asarray(p["key"], np.uint32),
                    steps=int(p["steps"]),
                    recovered=bool(p.get("recovered", False)))
            for p in st["readmit"])
        rs.n_blocks = int(st["n_blocks"])
        clock_ms = float(st["clock_ms"])
        if self._plan.ms_per_block > 0:
            rs.vclock = clock_ms
        rs.t_start = time.monotonic() - clock_ms * 1e-3
        self._health = {k: int(st["health"].get(k, 0))
                        for k in _HEALTH_KEYS}
        self._health["recoveries"] += 1
        self._epoch = int(st["epoch"])
        if self._prefix is not None and "prefix" in st:
            psd = dict(st["prefix"])
            logits = tree.get("prefix_logits", {})
            psd["entries"] = [
                {**e, "logits": np.asarray(logits[str(i)])}
                for i, e in enumerate(psd["entries"])]
            self._prefix.load_state_dict(psd)
        self._rs = rs
        # restored state supersedes whatever epoch the journal holds
        self._rewrite_epoch(rs)
        return rs.n_blocks

    # -- admission ---------------------------------------------------------
    def _check(self, req: Request) -> Optional[str]:
        """Validate a request; returns a rejection reason or None.  Bad
        requests retire with status 'rejected' instead of aborting the
        run — per-request fault isolation."""
        L = int(len(req.tokens))
        if L > self.prompt_cap:
            return (f"prompt length {L} exceeds prompt_cap "
                    f"{self.prompt_cap}")
        if L < 1:
            return "empty prompt"
        if req.max_gen < 1:
            # admission always yields the prefill's first token, so a
            # 0-token budget cannot be honored
            return ("max_gen must be >= 1 (the first token is sampled "
                    "at admission)")
        return None

    def _request_keys(self, rid: int):
        """Per-request key pair: (first-token sample key, carried slot
        key).  Folding the rid into the seed key makes the request's
        entire sample stream independent of arrival order and slot
        placement."""
        k = jax.random.fold_in(self._base_key, int(rid))
        ks = jax.random.split(k)
        return ks[0], np.asarray(ks[1], np.uint32)

    def _sample_t0(self, logits, key) -> int:
        t0 = ST.sample_tokens(jnp.asarray(logits)[:, -1, :], key,
                              temperature=self.temperature, top_p=self.top_p)
        return int(t0[0])

    def _admit(self, slot: int, req: Request):
        """Admit ``req`` into ``slot``; returns (first generated token,
        carried per-request key).  Dense: chunked-prefill the prompt into
        the batch-1 template and splice it into the slot's region.
        Paged: try the prefix store first — a full-prompt hit attaches
        the shared pages (block-table row write + one tail-page copy) and
        samples from the stored logits, running ZERO prefill FLOPs; a
        miss prefills, scatters into the slot's private pages, and
        registers the prompt for future sharers.  Raises on injected
        admission faults and non-finite prefill logits — BEFORE anything
        is spliced into the resident cache, so a failed admission leaves
        no trace.

        One ``sched.admit`` span, with a child per device round trip:
        ``admit.keys`` (the request's PRNG keys, its prefix-store
        lookup), ``admit.prefill`` (through the logits on the host),
        ``admit.insert`` (the splice, or a hit's page attach) and
        ``admit.first_token`` (through the token on the host)."""
        L = int(len(req.tokens))
        with tel.span("sched.admit", rid=req.rid, slot=slot,
                      prompt_len=L) as admit:
            times = self._rs.times.setdefault(req.rid, {})
            times["t_admit"] = admit.t0
            if self._plan.rejects(req.rid):
                raise InjectedFault(
                    f"request {req.rid}: injected admission failure")
            with tel.span("admit.keys", rid=req.rid):
                k_t0, k_carry = self._request_keys(req.rid)
                key = tuple(int(t) for t in np.asarray(req.tokens))
                entry = (self._prefix.lookup(key, slot)
                         if self._prefix is not None else None)
            admit.attrs["prefix_hit"] = entry is not None
            if entry is not None:
                admit.attrs["chunks"] = 0
                with tel.span("admit.insert", rid=req.rid):
                    self._attach_prefix(slot, entry)
                logits = entry.logits
            else:
                admit.attrs["chunks"] = self.prompt_cap // self.prefill_chunk
                with tel.span("admit.prefill", rid=req.rid):
                    toks = np.zeros((1, self.prompt_cap), np.int32)
                    toks[0, :L] = np.asarray(req.tokens, np.int32)
                    lengths = jnp.asarray([L], jnp.int32)
                    logits, slot_cache = self._prefill(
                        self.serve_params, self.qparams,
                        {"tokens": jnp.asarray(toks)}, self._slot_cache0,
                        lengths)
                    last_row = np.asarray(logits)[:, -1, :]
                    if self._plan.nans_prefill(req.rid):
                        last_row = np.full_like(last_row, np.nan)
                    if not np.isfinite(last_row).all():
                        raise FloatingPointError(
                            f"request {req.rid}: non-finite prefill logits")
                with tel.span("admit.insert", rid=req.rid):
                    self._insert(slot, slot_cache)
                    if self._prefix is not None:
                        self._register_prefix(key, L,
                                               self._private_rows[slot],
                                               logits)
                    # release the batch-1 cache inside the admission's
                    # spans, so its cost is timed as the admission's
                    del slot_cache
            with tel.span("admit.first_token", rid=req.rid) as first:
                t0 = self._sample_t0(logits, k_t0)
                del logits, k_t0
            times["t_first"] = times["t_last"] = first.t1
            return t0, k_carry

    def _readmit(self, slot: int, req: Request, out: list,
                 recovered: bool = False):
        """Rebuild a preempted request's device state in ``slot``: one
        ragged prefill (the ``resume`` executable) over prompt +
        generated-so-far tokens minus the pending one — FAT's frozen
        scales make the recomputed int8 cache bit-valid, so decode
        continues exactly where it left off.  The slot's private pages
        receive the state; prefix pages are not consulted (the sequence
        includes generated tokens no other request shares).  Crash
        recovery rides the same executable (``recovered=True``) but
        counts re-prefilled tokens as ``replayed_tokens`` instead of a
        preemption re-admission.  One ``sched.readmit`` span, with
        children ``readmit.resume`` and ``readmit.insert``."""
        L = len(req.tokens)
        resume = L + len(out) - 1   # pending token is NOT yet in cache
        with tel.span("sched.readmit", rid=req.rid, slot=slot,
                      prompt_len=L, resume_len=resume, recovered=recovered):
            with tel.span("readmit.resume", rid=req.rid):
                toks = np.zeros((1, self._resume_cap), np.int32)
                seq = list(np.asarray(req.tokens, np.int32)) + [
                    int(t) for t in out[:-1]]
                toks[0, :resume] = np.asarray(seq, np.int32)
                lengths = jnp.asarray([resume], jnp.int32)
                _, slot_cache = self._resume(
                    self.serve_params, self.qparams,
                    {"tokens": jnp.asarray(toks)}, self._slot_cache0,
                    lengths)
            with tel.span("readmit.insert", rid=req.rid):
                self._insert(slot, slot_cache)
                del slot_cache
        if recovered:
            self._health["replayed_tokens"] += resume
        else:
            self._health["readmits"] += 1

    def _insert(self, slot: int, slot_cache):
        """Splice a batch-1 prefill result into ``slot``: its dense region,
        or (paged) its private pages and their block-table row."""
        self._call_counts["insert"] += 1
        if self._prefix is None:
            self._cache = self._insert_fn(self._cache, slot_cache,
                                          jnp.asarray(slot, jnp.int32))
        else:
            row = self._private_rows[slot]
            self._cache = self._insert_fn(self._cache, slot_cache,
                                          jnp.asarray(row))
            self._set_row(slot, row)

    # -- paged plumbing ----------------------------------------------------
    def _set_row(self, slot: int, row: np.ndarray):
        self._call_counts["set_row"] += 1
        self._cache = self._set_row_fn(self._cache,
                                       jnp.asarray(slot, jnp.int32),
                                       jnp.asarray(row, jnp.int32))

    def _copy_pages(self, pairs: Sequence[tuple]):
        """One fixed-shape copy dispatch for up to n_blocks (src, dst)
        page pairs: unused entries repeat the first pair (duplicate dst
        with identical src — a deterministic re-write), so every
        registration/attach shares ONE compiled executable regardless of
        how many pages the prompt spans."""
        if not pairs:
            return
        self._call_counts["copy_page"] += 1
        padded = list(pairs) + [pairs[0]] * (self._n_blocks - len(pairs))
        src = jnp.asarray([p[0] for p in padded], jnp.int32)
        dst = jnp.asarray([p[1] for p in padded], jnp.int32)
        self._cache = self._copy_page_fn(self._cache, src, dst)

    def _register_prefix(self, key, L, private_row, logits):
        """Snapshot the freshly-prefilled prompt pages into the shared
        region (device page copies — no model FLOPs) and store the
        last-position logits so a future identical prompt skips prefill
        entirely.  Opportunistic: skipped — and counted in
        ``health_stats()['prefix_exhausted']`` — when the shared region
        is full of in-use entries (or a fault plan forces exhaustion);
        the admission itself already lives in private pages, so serving
        degrades to no-sharing instead of failing."""
        alloc = (None if self._plan.exhaust_prefix
                 else self._prefix.reserve(key, L))
        if alloc is None:
            self._health["prefix_exhausted"] += 1
            return
        pages, tail = alloc
        n_full = len(pages)
        pairs = [(int(private_row[j]), int(dst))
                 for j, dst in enumerate(pages)]
        if tail is not None:
            pairs.append((int(private_row[n_full]), int(tail)))
        self._copy_pages(pairs)
        self._prefix.register(key, PrefixEntry(
            pages=pages, tail_page=tail, length=L,
            logits=np.asarray(logits)))

    def _attach_prefix(self, slot: int, entry: PrefixEntry):
        """Full-prompt hit: point the slot's table row at the shared
        pages; the partial tail page (decode's first append target) is
        copied into the slot's private page so shared pages stay
        immutable.  No prefill executable runs."""
        row = self._private_rows[slot].copy()
        n_full = len(entry.pages)
        row[:n_full] = entry.pages
        self._set_row(slot, row)
        if entry.tail_page is not None:
            self._copy_pages([(int(entry.tail_page),
                               int(self._private_rows[slot][n_full]))])
