"""The program's own spans of a window, placed on the trace's clock.

The serving scheduler records its phases itself
(``src/repro/launch/telemetry.py``, one process-wide tracer on
``time.monotonic()``; the span names are listed in docs/serving.md).
This module picks the window's run out of that tracer, maps its host
times onto the trace's nanoseconds, and does the interval arithmetic the
readers of ``queue_wait_ms``, ``loop_host_ms``, ``idle_host`` and
``idle_admit`` share.  A checkout whose program records no spans gives
None everywhere, as ``bench/measure.py`` asks of a reader.
"""
from __future__ import annotations

import math

import numpy as np

from bench import hooks, measure

# the scheduler's own host work: its bookkeeping between device calls
HOST = ("sched.loop", "decode.transfer", "decode.collect", "decode.commit")
# an admission's or readmission's device round trips
ADMIT = ("admit.keys", "admit.prefill", "admit.insert", "admit.first_token",
         "readmit.resume", "readmit.insert")
# every leaf: together they tile the scheduler's run
LEAVES = HOST + ADMIT + ("sched.wait", "decode.call", "decode.fetch")


def records(run) -> list | None:
    """The window's span records: the run whose ``sched.run`` span starts
    at the window's origin (``run.rec.t_start``, the scheduler's own run
    origin), or None."""
    t_start = run.rec.t_start
    if t_start is None:
        return None
    hooks.import_program()
    try:
        from repro.launch import telemetry
    except ImportError:
        return None
    tr = telemetry.tracer()
    for rec in tr.records():
        if rec.name == "sched.run" and rec.t0 == t_start:
            return tr.records(rec.run_id)
    return None


def to_trace_ns(run):
    """Host seconds -> trace nanoseconds, through the one instant both
    clocks saw: the harness opened ``bench.window.0`` at
    ``run.rec.trace_window[0]`` on the host, and the trace holds it at
    ``run.trace.window[0]``.  None in a run without a trace."""
    tw = run.rec.trace_window
    if run.trace is None or tw is None:
        return None
    h0, d0 = tw[0], run.trace.window[0]
    return lambda t: d0 + (np.asarray(t, np.float64) - h0) * 1e9


def profiled(run, recs) -> tuple | None:
    """(start, end) on the host clock of the part of the window that the
    profiler held: from the end of the program's last leaf before the
    trace began to the start of its first leaf after the trace stopped.
    Starting and stopping the profiler (which writes the trace, seconds
    of it) fall in between.  None in an untraced run."""
    tw = run.rec.trace_window
    if tw is None or tw[1] is None:
        return None
    leaves = [r for r in recs if r.name in LEAVES]
    return (max((r.t1 for r in leaves if r.t1 <= tw[0]), default=tw[0]),
            min((r.t0 for r in leaves if r.t0 >= tw[1]), default=math.inf))


def untraced(run, recs) -> list:
    """The records that lie wholly outside the profiler's part of the
    window (``profiled``)."""
    part = profiled(run, recs)
    if part is None:
        return list(recs)
    return [r for r in recs if r.t1 <= part[0] or r.t0 >= part[1]]


def busy_until(busy, t) -> np.ndarray:
    """Device-busy nanoseconds before each time in ``t``, over merged
    busy intervals ``busy = (starts, ends)``."""
    bs, be = (np.asarray(x, np.float64) for x in busy)
    t = np.asarray(t, np.float64)
    if not len(bs):
        return np.zeros_like(t)
    cum = np.concatenate([[0.0], np.cumsum(be - bs)])
    # the intervals that start by t; the last of them may run past it
    i = np.searchsorted(bs, t, side="right")
    over = np.where(i > 0, np.maximum(0.0, be[np.maximum(i - 1, 0)] - t),
                    0.0)
    return cum[i] - over


def idle_pct(run, names) -> float | None:
    """% of the traced window in which no device operation ran and the
    program was in a span named in ``names``."""
    recs = records(run)
    ns = to_trace_ns(run)
    if recs is None or ns is None or not run.trace.busy_s:
        return None
    w0, w1 = run.trace.window
    leaves = [r for r in recs if r.name in names]
    if not leaves:
        return 0.0
    a = np.clip(ns([r.t0 for r in leaves]), w0, w1)
    b = np.clip(ns([r.t1 for r in leaves]), w0, w1)
    idle = (b - a) - (busy_until(run.trace.busy, b)
                      - busy_until(run.trace.busy, a))
    return 100.0 * float(idle.sum()) / (w1 - w0)


def loop_host_ms(run) -> float | None:
    """Mean host time per decode block of the scheduler's own work,
    outside the profiler's part of the window."""
    recs = records(run)
    if recs is None:
        return None
    recs = untraced(run, recs)
    blocks = sum(r.name == "sched.decode" for r in recs)
    if not blocks:
        return None
    return 1e3 * sum(r.t1 - r.t0 for r in recs if r.name in HOST) / blocks


def queue_wait_ms(run, q: float) -> float | None:
    """Percentile ``q`` of the wait from due to the start of the first
    admission, over every request of the window whose wait lies outside
    the profiler's part of it (``profiled``: stopping the profiler holds
    the host for seconds, and every request queued then waits it out).  A
    request never admitted waits forever (1e12 ms at most, as
    ``ttft_p90_ms`` carries it)."""
    recs = records(run)
    if recs is None:
        return None
    admitted: dict = {}
    for r in recs:
        if r.name == "sched.admit":
            admitted[r.rid] = min(admitted.get(r.rid, math.inf), r.t0)
    t0 = run.rec.t_start
    part = profiled(run, recs) or (math.inf, math.inf)
    ms = []
    for rid, _toks, _budget, due in run.requests:
        t_due, t_admit = t0 + due * 1e-3, admitted.get(rid, math.inf)
        if t_admit <= part[0] or t_due >= part[1] or t_admit == math.inf:
            ms.append(1e3 * (t_admit - t_due))
    return min(measure.percentile(ms, q), 1e12) if ms else None
