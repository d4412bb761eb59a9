"""One run's record, and the arithmetic that the metric readers share.

A metric reader (``bench/metrics/<name>.py``) is a function
``read(run: Run) -> float | None``.  It returns None where the run holds
nothing for it to read (no such span in this cell, no trace), and the
harness then leaves the metric out of the result line.  A roofline or
peak share never reads 0 for want of data: it reads None.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
from pathlib import Path

from bench import arch, hooks

KERNELS = Path(__file__).resolve().parent / "kernels"


@dataclasses.dataclass
class Run:
    cell: dict              # the BENCHMARK.json workload entry
    cfg: dict               # the configuration file
    mix: dict               # the traffic mix
    seconds: float
    setup_s: float
    peaks: dict             # bench/peaks.py entry of this device
    requests: list          # [(rid, prompt, budget, due_ms)]
    rec: hooks.Recorder     # host spans of the window
    outcomes: list          # [hooks.Outcome], terminal requests
    server: dict            # scheduler as built: chunk, caps, cache_len
    trace: object = None    # bench.trace.Reduction in a traced run

    # -- requests -----------------------------------------------------------
    def first_token_s(self) -> dict:
        """rid -> host time of its first token."""
        return {a.rid: a.t1 for a in self.rec.admits}

    def token_times(self) -> dict:
        """rid -> [host time at which the request's token count grew, and
        to what]: its first token, then every block it decoded in, capped
        by its budget as the scheduler caps what it collects."""
        out = {a.rid: [(a.t1, 1)] for a in self.rec.admits}
        for b in self.rec.blocks:
            for rid, had, budget, _pos in b.slots:
                n = min(had + b.emitted.get(rid, 0), budget)
                if n > had:
                    out.setdefault(rid, []).append((b.t1, n))
        return out

    # -- spans --------------------------------------------------------------
    def spans(self, kind: str, traced: bool):
        """Admissions or blocks inside (traced=True) or outside the traced
        part of the window."""
        items = self.rec.admits if kind == "admit" else self.rec.blocks
        tw = self.rec.trace_window
        if tw is None or tw[1] is None:
            return [] if traced else list(items)
        inside = [x for x in items if x.t0 >= tw[0] and x.t1 <= tw[1]]
        if traced:
            return inside
        return [x for x in items if x not in inside]

    def kernel(self, name: str):
        """``bench/kernels/<name>.py``: the kernel's operation and byte
        counts."""
        spec = importlib.util.spec_from_file_location(
            f"bench_kernel_{name}", KERNELS / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def least_s(self, ops: float, nbytes: float, ops_peak: str) -> float:
        """The least time the chip could take: the larger of operations
        over the peak rate and bytes over the HBM bandwidth."""
        return max(ops / self.peaks[ops_peak],
                   nbytes / self.peaks["hbm_bytes_per_s"])


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) count."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def mean_ms(spans) -> float | None:
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)


def roofline_pct(run: Run, kernel: str, kind: str) -> float | None:
    """A kernel's share of its roofline over the traced spans of ``kind``:
    the least time its calls in those spans could take (the kernel's own
    op/byte function) over the device time of its events in them."""
    if run.trace is None:
        return None
    spans = run.spans(kind, traced=True)
    mod = run.kernel(kernel)
    least = sum(run.least_s(*c, mod.OPS_PEAK)
                for s in spans for c in mod.calls(run, kind, s))
    busy = run.trace.kernel_seconds(kernel, spans)
    if not busy or not least:
        return None
    return 100.0 * least / busy


def decode_mfu_pct(run: Run) -> float | None:
    """Useful model operations of the decode blocks over their host time
    at the int8 peak: each token a slot kept (budgets cap what a block
    emits), its matrix products, readout and attention over its context."""
    spans = run.spans("decode", traced=False)
    if not spans:
        return None
    mo = arch.of(run.cfg).model_ops(run.cfg)
    ops = 0
    for b in spans:
        for rid, had, budget, pos in b.slots:
            kept = min(b.emitted.get(rid, 0), budget - had)
            keys = kept * pos + kept * (kept + 1) // 2
            ops += kept * (mo["matmul"] + mo["readout"]) + keys * mo["per_key"]
    secs = sum(b.t1 - b.t0 for b in spans)
    return 100.0 * ops / (secs * run.peaks["int8_ops"])


def prefill_mfu_pct(run: Run) -> float | None:
    """Useful model operations of the admissions over their host time at
    the int8 peak: the prompt's tokens through the matrix products, causal
    attention over the prompt, and one readout row."""
    spans = run.spans("admit", traced=False)
    if not spans:
        return None
    mo = arch.of(run.cfg).model_ops(run.cfg)
    ops = sum(a.prompt_len * mo["matmul"] + mo["readout"]
              + a.prompt_len * (a.prompt_len + 1) // 2 * mo["per_key"]
              for a in spans)
    secs = sum(a.t1 - a.t0 for a in spans)
    return 100.0 * ops / (secs * run.peaks["int8_ops"])


def idle_pct(run: Run) -> float | None:
    if run.trace is None or not run.trace.busy_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
