"""Host spans of the serving loop, on ``time.monotonic()``.

One process-wide tracer (:func:`tracer`) keeps span records in memory,
the way ``logging`` keeps its loggers: records outlive the scheduler that
wrote them, so a caller can free the scheduler and read its timeline
afterwards.  Recording is always on.  A span costs two ``monotonic()``
reads, a ``jax.profiler.TraceAnnotation`` and a list append, so it is
placed per admission and per decode block, never per token or per layer.

A record (:class:`SpanRecord`) holds its span id, its parent's id, the id
of its run (the id of its outermost span), the request id it belongs to
(or None), its name, ``t0``/``t1`` on ``time.monotonic()`` (the clock of
``SlotScheduler``'s run origin), and a small dict of attributes.  Each
span also enters a ``TraceAnnotation`` of its name with ``span_id`` as an
argument, so in a profiled run the same spans appear in the profiler's
trace beside the device operations (TensorBoard, Perfetto); with no
profiler session that costs about a microsecond.

The buffer keeps the newest :data:`MAX_RUNS` runs and at most
:data:`MAX_RECORDS` records; past either it drops the oldest whole run
first, and only a single run longer than the record cap loses its oldest
records.  The span names the scheduler writes are listed in
docs/serving.md ("Spans").
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import threading
import time
from collections import OrderedDict

import jax

MAX_RUNS = 4            # runs kept: the newest, whole
MAX_RECORDS = 100_000   # records kept over all runs


@dataclasses.dataclass(slots=True)
class SpanRecord:
    span_id: int
    parent_id: int | None
    run_id: int
    rid: int | None         # the request the span belongs to
    name: str
    t0: float               # time.monotonic(), seconds
    t1: float
    attrs: dict

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class Span:
    """An open span: a context manager that writes its record when it
    closes.  ``attrs`` may be added to until then; an exception leaving
    the span is noted as ``attrs["error"]``."""

    __slots__ = ("_tracer", "name", "rid", "attrs", "span_id", "parent_id",
                 "run_id", "t0", "t1", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, rid, t0, attrs: dict):
        self._tracer, self.name, self.rid = tracer, name, rid
        self.attrs, self._t0 = attrs, t0
        self.t0 = self.t1 = None

    # the clock is read first on entry and last on exit, so the span's own
    # cost falls inside it and consecutive spans leave no gap between them
    def __enter__(self) -> "Span":
        self.t0 = time.monotonic() if self._t0 is None else self._t0
        stack = self._tracer._stack()
        parent = stack[-1] if stack else None
        self.span_id = next(self._tracer._ids)
        self.parent_id = parent.span_id if parent is not None else None
        self.run_id = parent.run_id if parent is not None else self.span_id
        self._ann = jax.profiler.TraceAnnotation(self.name,
                                                 span_id=self.span_id)
        self._ann.__enter__()
        stack.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._tracer._stack().pop()
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        rec = SpanRecord(self.span_id, self.parent_id, self.run_id, self.rid,
                         self.name, self.t0, self.t0, dict(self.attrs))
        self._tracer._add(rec)
        rec.t1 = self.t1 = time.monotonic()
        return False

    @contextlib.contextmanager
    def paused(self):
        """Close this span for the enclosed block and reopen it, as a new
        span of the same name, after: the block's own spans become its
        siblings, not its children, so consecutive leaves stay apart."""
        self.__exit__(None, None, None)
        try:
            yield
        finally:
            self._t0 = None
            self.__enter__()


class Tracer:
    """The span buffer; use the process-wide one, :func:`tracer`."""

    def __init__(self, max_runs: int = MAX_RUNS,
                 max_records: int = MAX_RECORDS):
        self.max_runs, self.max_records = max_runs, max_records
        self._ids = itertools.count(1)
        self._runs: OrderedDict = OrderedDict()   # run id -> [SpanRecord]
        self._n = 0
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _add(self, rec: SpanRecord) -> None:
        with self._lock:
            self._runs.setdefault(rec.run_id, []).append(rec)
            self._n += 1
            while len(self._runs) > self.max_runs or (
                    self._n > self.max_records and len(self._runs) > 1):
                _, dropped = self._runs.popitem(last=False)
                self._n -= len(dropped)
            if self._n > self.max_records:
                recs = self._runs[rec.run_id]
                cut = self._n - self.max_records
                del recs[:cut]
                self._n -= cut

    def span(self, name: str, rid=None, t0: float | None = None,
             **attrs) -> Span:
        """A span of ``name`` under the innermost open one (a new run if
        none is open).  ``t0`` backdates its start (``time.monotonic()``
        seconds)."""
        return Span(self, name, rid, t0, attrs)

    def record(self, name: str, t0: float, t1: float, rid=None,
               **attrs) -> None:
        """Write a finished span after the fact, as a child of the open
        run's outermost span (no annotation reaches the profiler)."""
        stack = self._stack()
        run_id = stack[0].run_id if stack else None
        sid = next(self._ids)
        self._add(SpanRecord(sid, run_id, run_id if run_id is not None
                             else sid, rid, name, t0, t1, dict(attrs)))

    def records(self, run_id: int | None = None) -> list:
        """Every kept record, or those of one run, in the order written
        (a span after its children)."""
        with self._lock:
            if run_id is not None:
                return list(self._runs.get(run_id, ()))
            return [r for recs in self._runs.values() for r in recs]

    def run_ids(self) -> list:
        """The kept runs, oldest first."""
        with self._lock:
            return list(self._runs)

    def write_jsonl(self, path, run_id: int | None = None) -> int:
        """Write one run's records (default: the newest run's) to ``path``
        as JSON lines, by start time; returns how many."""
        ids = self.run_ids()
        if run_id is None and ids:
            run_id = max(ids)
        recs = sorted(self.records(run_id) if run_id is not None else [],
                      key=lambda r: (r.t0, r.span_id))
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r.to_dict()) + "\n")
        return len(recs)


_TRACER = Tracer()


def tracer() -> Tracer:
    """The process-wide tracer."""
    return _TRACER


def span(name: str, rid=None, t0: float | None = None, **attrs) -> Span:
    """``tracer().span(...)``."""
    return _TRACER.span(name, rid, t0, **attrs)


def record(name: str, t0: float, t1: float, rid=None, **attrs) -> None:
    """``tracer().record(...)``."""
    _TRACER.record(name, t0, t1, rid, **attrs)
