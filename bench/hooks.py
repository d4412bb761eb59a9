"""Every place where the benchmark touches the program's own names.

The rest of ``bench/`` knows the program only through this file and the
architecture modules (``bench/arch/<arch>.py``: the program's
``ModelConfig`` and parameter tree for a configuration).  Here: how to
assemble the serving engine the way ``Engine.from_checkpoint`` does, and
how to watch ``SlotScheduler`` from outside.  A rename in the program
breaks this file or an architecture module, and their tests
(``tests/bench/test_bench_hooks.py``, ``test_bench_arch.py``), nothing
else.

What is watched: ``SlotScheduler._admit`` (one admission: the chunked
prefill, the splice into the slot cache, and the first token on the host)
and ``SlotScheduler._decode`` (one decode block of ``block_steps`` steps
over every slot).  Each call gets a host span, written into a profiler
trace too when one is on (``jax.profiler.TraceAnnotation``), and the
block waits for its outputs so that its span ends with its tokens on the
host, as the scheduler's own next line would.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time
import traceback
from pathlib import Path

from bench import arch

CHECKOUT = Path(__file__).resolve().parents[1]
SRC = CHECKOUT / "src"


def import_program() -> None:
    """Put the program on ``sys.path``; fail if the checkout lacks it."""
    if not (SRC / "repro" / "launch" / "scheduler.py").is_file():
        raise SystemExit(f"bench: the program is missing ({SRC}/repro)")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def enable_compile_cache() -> str:
    """The program's own persistent compile cache: ``$JAX_COMPILATION_
    CACHE_DIR`` if set, else the fixed ``<checkout>/.jax_cache``."""
    import_program()
    from repro.launch import compile_cache
    return compile_cache.enable()


def set_norm_eps(model, eps: float) -> int:
    """Give every RMSNorm of the model the configuration's eps.  The
    program's ``ModelConfig`` has no key for it (its ``RMSNorm`` defaults
    to 1e-6), so the benchmark sets it on the built modules, before any
    of them is traced.  Returns how many norms it set."""
    import_program()
    from repro.models.layers import RMSNorm

    seen, todo, n = set(), [model], 0
    while todo:
        obj = todo.pop()
        if id(obj) in seen:
            continue
        seen.add(id(obj))
        if isinstance(obj, RMSNorm):
            obj.eps = float(eps)
            n += 1
        elif isinstance(obj, (list, tuple)):
            todo.extend(obj)
        elif type(obj).__module__.startswith("repro."):
            todo.extend(vars(obj).values())
    return n


def build_engine(cfg: dict, weights: dict):
    """The serving engine on the given weights, assembled as
    ``Engine.from_checkpoint`` assembles it: the §2 calibration pass on
    the program's own calibration batches, then the int8 conversion.
    ``cfg["weight_bits"]``/``cfg["kv_bits"]`` pick the precision (8 and 8
    as the configurations state; 4 is the control's)."""
    import jax

    import_program()
    from repro.configs.shapes import ShapeSpec
    from repro.core import api as A
    from repro.data import pipeline as DP
    from repro.launch.engine import Engine, prepare_int8
    from repro.models import build_model

    mod = arch.of(cfg)
    pcfg = mod.program_config(cfg)
    model = build_model(pcfg)
    if set_norm_eps(model, cfg["rms_norm_eps"]) < mod.norm_count(cfg):
        raise ValueError("the program's norms have moved; update "
                         "bench/hooks.py:set_norm_eps")
    params = mod.program_params(weights)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if (jax.tree.structure(want) != jax.tree.structure(params) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(params)))):
        raise ValueError("the program's parameter tree has changed; "
                         f"update bench/arch/{cfg['arch']}.py:"
                         "program_params")
    policy = A.QuantPolicy(bits=cfg["weight_bits"], kv_int8=True,
                           kv_bits=cfg["kv_bits"],
                           use_pallas=jax.default_backend() == "tpu")
    spec = DP.spec_for(pcfg, ShapeSpec("engine", "train", 32, 4))
    calib = DP.calibration_batches(spec, 2)
    for b in calib:
        b.pop("labels", None)
    serve_params, qparams = prepare_int8(model, pcfg, policy, params, calib)
    return Engine(model, pcfg, policy, serve_params, qparams, mode="int8",
                  cache_layout="dense")


def make_scheduler(engine, server: dict):
    return engine.make_scheduler(
        max_slots=server["max_slots"], prompt_cap=server["prompt_cap"],
        gen_cap=server["gen_cap"], block_steps=server["block_steps"])


def make_request(rid: int, tokens, max_gen: int, arrive_ms: float):
    import_program()
    from repro.launch.scheduler import Request
    return Request(rid=rid, tokens=tokens, max_gen=max_gen,
                   arrive_ms=arrive_ms)


def executable_counts(sched) -> dict:
    return sched.executable_counts()


def cache_len(sched) -> int:
    return sched.cache_len


def prefill_chunk(sched) -> int:
    return sched.prefill_chunk


def padded_prompt_cap(sched) -> int:
    return sched.prompt_cap


STALL_S = 1.0   # a span this long is a stall: where it waited is recorded


class WindowClosed(Exception):
    """Raised from a hook once a cut window has closed: the scheduler
    stops there, and what was still in flight counts neither way."""


@dataclasses.dataclass
class Admit:
    seq: int            # the span's number in the trace (``bench.admit.N``)
    rid: int
    prompt_len: int
    t0: float           # host clock (``time.monotonic``), seconds
    t1: float           # the first token is on the host


@dataclasses.dataclass
class Block:
    seq: int            # the span's number in the trace (``bench.decode.N``)
    t0: float
    t1: float           # the block's tokens are on the host
    # (rid, tokens it had before the block, its budget, position of its
    # pending token) for every slot that decoded in this block
    slots: list
    emitted: dict       # rid -> tokens the block emitted for it


@dataclasses.dataclass
class Stall:
    kind: str           # "admit" or "decode"
    t0: float
    seconds: float
    stack: list         # the main thread's innermost frames, STALL_S in
    process_s: float    # CPU seconds of the whole process over the span


@dataclasses.dataclass
class Outcome:
    rid: int
    status: str
    tokens: list


class Recorder:
    """Host spans of one scheduler run, taken from outside.

    ``cut_s``: stop the run at the first admission or block that
    would start more than ``cut_s`` seconds after the run began
    (``None``: serve every request to its end).  ``trace_dir`` with
    ``trace_from_s``/``trace_s``: profile that part of the run.
    """

    def __init__(self, sched, *, cut_s=None, trace_dir=None,
                 trace_from_s=0.0, trace_s=0.0):
        self.sched = sched
        self.cut_s = cut_s
        self.trace_dir = trace_dir
        self.trace_from_s, self.trace_s = trace_from_s, trace_s
        self.admits: list[Admit] = []
        self.blocks: list[Block] = []
        self.t_start = None
        self.trace_window = None        # (t0, t1) host clock, if traced
        # (start, seconds, generation) of every garbage collection in the
        # run: the look for host stalls (``bench/run.py:host_stalls``)
        self.gc_pauses: list[tuple] = []
        self._gc_t0 = None
        # spans of STALL_S or more, with where the main thread waited
        self.stalls: list[Stall] = []
        self._open_span = None          # (kind, t0, process CPU seconds)
        self._stack = None              # (that tuple, stack at STALL_S)
        self._watch_stop = threading.Event()
        # the watcher's longest sleep (seconds, host time it began): far
        # past its 50 ms tick, no thread of the process ran
        self.watch_gap = (0.0, 0.0)
        self._tracing = False
        self._seq = 0

    # -- clock and profiler -------------------------------------------------
    def _tick(self) -> float:
        now = time.monotonic()
        if self.t_start is None:
            self.t_start = self.sched._rs.t_start
        since = now - self.t_start
        if self.trace_dir is not None:
            if (not self._tracing and self.trace_window is None
                    and since >= self.trace_from_s):
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # spans, not every call
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self._tracing = True
                self._window_span = jax.profiler.TraceAnnotation(
                    "bench.window.0")
                self._window_span.__enter__()
                self.trace_window = (time.monotonic(), None)
            elif self._tracing and since >= self.trace_from_s + self.trace_s:
                self.stop_trace()
        if self.cut_s is not None and since >= self.cut_s:
            raise WindowClosed
        return now

    def stop_trace(self) -> None:
        if self._tracing:
            import jax
            t1 = time.monotonic()
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._tracing = False
            self.trace_window = (self.trace_window[0], t1)

    def _span(self, kind: str):
        import jax
        self._seq += 1
        return self._seq, jax.profiler.TraceAnnotation(
            f"bench.{kind}.{self._seq}")

    # -- stalls -----------------------------------------------------------------
    def _open(self, kind: str, t0: float) -> None:
        self._open_span = (kind, t0, time.process_time())

    def _close(self, t1: float) -> None:
        span, self._open_span = self._open_span, None
        kind, t0, cpu0 = span
        if t1 - t0 >= STALL_S:
            stack = (self._stack[1] if self._stack and self._stack[0] is span
                     else [])
            self.stalls.append(Stall(kind, t0, t1 - t0, stack,
                                     time.process_time() - cpu0))

    def _watch(self) -> None:
        """Every 50 ms: note how long it slept, and once a span has run
        STALL_S, the main thread's stack."""
        main = threading.main_thread().ident
        last = time.monotonic()
        while not self._watch_stop.wait(0.05):
            now = time.monotonic()
            if now - last > self.watch_gap[0]:
                self.watch_gap = (now - last, last)
            last = now
            span = self._open_span
            if (span is None or (self._stack and self._stack[0] is span)
                    or time.monotonic() - span[1] < STALL_S):
                continue
            frame = sys._current_frames().get(main)
            self._stack = (span, [
                f"{Path(f.filename).name}:{f.lineno} {f.name}"
                for f in traceback.extract_stack(frame)[-6:]]
                if frame else [])

    # -- the hooks ------------------------------------------------------------
    def install(self) -> None:
        import jax
        import numpy as np

        sched = self.sched
        admit0, decode0 = sched._admit, sched._decode

        def admit(slot, req):
            self._tick()
            seq, span = self._span("admit")
            t0 = time.monotonic()
            self._open("admit", t0)
            with span:
                out = admit0(slot, req)
            t1 = time.monotonic()
            self._close(t1)
            self.admits.append(Admit(seq, req.rid, len(req.tokens), t0, t1))
            return out

        def decode(*args):
            self._tick()
            rs = sched._rs
            rows = [s for s in range(sched.max_slots)
                    if rs.slot_req[s] is not None and rs.active[s]]
            slots = [(rs.slot_req[s].rid, len(rs.slot_out[s]),
                      rs.slot_req[s].max_gen, int(rs.pos[s])) for s in rows]
            seq, span = self._span("decode")
            t0 = time.monotonic()
            self._open("decode", t0)
            with span:
                out = decode0(*args)
                jax.block_until_ready(out)
            t1 = time.monotonic()
            self._close(t1)
            emitted = np.asarray(out[1]).sum(axis=1)
            self.blocks.append(Block(seq, t0, t1, slots, {
                rid: int(emitted[s]) for s, (rid, *_) in zip(rows, slots)}))
            return out

        sched._admit = admit
        sched._decode = decode
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, time.monotonic()
                                   - self._gc_t0, info["generation"]))
            self._gc_t0 = None

    def run(self, requests) -> list[Outcome]:
        """Drive ``SlotScheduler.run`` over ``requests``; returns every
        request that reached a terminal status."""
        watch = threading.Thread(target=self._watch, daemon=True)
        watch.start()
        try:
            self.sched.run(requests)
        except WindowClosed:
            pass
        finally:
            self._watch_stop.set()
            watch.join()
            self.stop_trace()
            if self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)
        return [Outcome(c.rid, c.status, list(c.tokens))
                for c in self.sched._rs.done]
