"""bench/spans.py and the readers of the program's own spans: on
hand-made records (the interval arithmetic, the clock anchor, the traced
part left out), then on a tiny scheduler run on the CPU, where each of the
program's request times falls inside the harness's span that it
reconstructs."""
import importlib.util
import json
import math
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import hooks, measure, run, spans, trace, traffic

METRICS = Path(__file__).resolve().parents[2] / "bench" / "metrics"
DATA = Path(__file__).resolve().parent / "data"
NEW = ("queue_wait_ms", "loop_host_ms", "idle_host", "idle_admit")

hooks.import_program()
from repro.launch.telemetry import SpanRecord  # noqa: E402


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def rec(name, t0, t1, rid=None):
    return SpanRecord(0, None, 0, rid, name, t0, t1, {})


def a_run(requests=(), trace_window=None, red=None, t_start=100.0):
    window = SimpleNamespace(t_start=t_start, trace_window=trace_window,
                             admits=[], blocks=[])
    return measure.Run(None, None, None, 1.0, 0, {}, list(requests), window,
                       [], {}, trace=red)


def test_queue_wait_counts_a_never_admitted_request_as_infinite(monkeypatch):
    # ten requests due at 0..9 ms, each admitted 1..9 ms after it is due,
    # the last never
    reqs = [(rid, None, 4, float(rid)) for rid in range(10)]
    recs = [rec("sched.admit", 100.0 + (2 * rid + 1) * 1e-3,
                100.0 + (2 * rid + 5) * 1e-3, rid) for rid in range(9)]
    # a second admission of a request (after a failed one) does not count
    recs.append(rec("sched.admit", 100.5, 100.6, 0))
    monkeypatch.setattr(spans, "records", lambda run: recs)
    read = reader("queue_wait_ms")
    assert read(a_run(reqs)) == pytest.approx(9.0)
    assert read(a_run(reqs[4:])) == 1e12
    # traced from 4.5 to 6 ms: the last leaf before it ends at 4 ms, the
    # first after it starts at 7 ms; waits across 4-7 ms are left out
    recs += [rec("decode.call", 100.0, 100.004),
             rec("decode.fetch", 100.007, 100.02)]
    traced = a_run(reqs, trace_window=(100.0045, 100.006))
    assert spans.profiled(traced, recs) == (100.004, 100.007)
    # kept: requests 0 and 1 (admitted by 4 ms), 7 and 8 (due from 7 ms)
    # and 9 (never admitted): 1, 2, 8, 9 ms and infinity
    assert spans.queue_wait_ms(traced, 0.5) == pytest.approx(8.0)
    assert read(traced) == 1e12


def test_idle_split_maps_host_spans_through_the_anchor(monkeypatch):
    # the window span opened at host 50.0 s and at trace 1000 ns; the
    # device is busy over 1000-1150 and 1250-1400 of 1000-2000
    red = trace.Reduction(
        ops={}, busy=(np.array([1000, 1250]), np.array([1150, 1400])),
        spans={}, window=(1000, 2000), busy_s=300e-9, window_s=1000e-9)
    h = 50.0
    recs = [rec("sched.loop", h + 100e-9, h + 300e-9),        # idle 100 ns
            rec("admit.prefill", h + 500e-9, h + 800e-9, 3),  # idle 300 ns
            rec("decode.call", h + 800e-9, h + 900e-9),       # neither
            rec("decode.commit", h + 950e-9, h + 1200e-9),    # 50 ns inside
            rec("sched.admit", h + 500e-9, h + 800e-9, 3)]    # not a leaf
    monkeypatch.setattr(spans, "records", lambda run: recs)
    r = a_run(trace_window=(h, h + 1e-6), red=red)
    host, admit = reader("idle_host")(r), reader("idle_admit")(r)
    assert host == pytest.approx(15.0, abs=1e-3)
    assert admit == pytest.approx(30.0, abs=1e-3)
    assert host + admit <= measure.idle_pct(r) == pytest.approx(70.0)
    # the same records read no trace in an untraced run
    assert reader("idle_host")(a_run(trace_window=(h, h + 1e-6))) is None


def test_busy_until():
    busy = (np.array([10, 30]), np.array([20, 40]))
    assert spans.busy_until(busy, [0, 10, 15, 25, 35, 50]).tolist() == [
        0, 0, 5, 10, 15, 20]
    assert spans.busy_until((np.array([]), np.array([])), [5]).tolist() == [0]


def test_loop_host_ms_leaves_out_the_traced_part(monkeypatch):
    recs = []
    for k, t in enumerate((0.0, 1.0, 2.0, 3.0)):
        recs += [rec("sched.loop", t, t + 0.001 * (k + 1) ** 2),
                 rec("decode.transfer", t + 0.1, t + 0.102),
                 rec("decode.call", t + 0.102, t + 0.5),
                 rec("decode.collect", t + 0.5, t + 0.503),
                 rec("decode.commit", t + 0.503, t + 0.504),
                 rec("sched.decode", t + 0.1, t + 0.504)]
    monkeypatch.setattr(spans, "records", lambda run: recs)
    read = reader("loop_host_ms")
    # untraced: (1 + 4 + 9 + 16) / 4 + 2 + 3 + 1 ms a block
    assert read(a_run()) == pytest.approx(13.5)
    # traced from 0.9 to 2.6 s: the profiler's part runs from the end of
    # the first block to the start of the last, which alone are left
    assert read(a_run(trace_window=(0.9, 2.6))) == pytest.approx(
        (1 + 16) / 2 + 6)


def test_readers_read_nothing_without_the_programs_spans(monkeypatch):
    # no window origin, or no run of the program starting there
    assert spans.records(a_run(t_start=None)) is None
    assert spans.records(a_run(t_start=-1.0)) is None
    monkeypatch.setattr(spans, "records", lambda run: None)
    red = trace.Reduction(ops={}, busy=(np.array([0]), np.array([5])),
                          spans={}, window=(0, 10), busy_s=5e-9,
                          window_s=10e-9)
    r = a_run([(0, None, 4, 0.0)], trace_window=(0.0, 1.0), red=red)
    for name in NEW:
        assert reader(name)(r) is None


@pytest.fixture(scope="module")
def tiny_window():
    cfg = json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    _engine, sched, server = run.set_up(cfg, mix, 2**31 + 7)
    requests = traffic.generate(mix, cfg["vocab_size"], 2.0, 2**31 + 7)
    window = run.serve(sched, mix, requests, 2.0, False)
    return measure.Run(None, cfg, mix, 2.0, 0, {}, requests, window,
                       window.outcomes, server)


def test_program_times_fall_inside_the_harness_spans(tiny_window):
    r = tiny_window
    recs = spans.records(r)
    (root,) = [x for x in recs if x.name == "sched.run"]
    assert root.t0 == r.rec.t_start
    assert run.WARM_RID not in {x.rid for x in recs}
    admits = {a.rid: a for a in r.rec.admits}
    assert r.first_token_s().keys() == admits.keys()
    harness = sorted(r.rec.admits + r.rec.blocks, key=lambda x: x.t0)
    starts = [x.t0 for x in harness]
    steps = r.token_times()
    done = [x for x in recs if x.name == "sched.request"]
    assert sorted(x.rid for x in done) == sorted(admits)
    for x in done:
        a = admits[x.rid]
        # admission and first token: inside the harness's admission span
        assert a.t0 <= x.attrs["t_admit"] <= x.attrs["t_first"] <= a.t1
        assert r.first_token_s()[x.rid] == a.t1
        # last token: fetched after the harness's block that delivered it
        # waited for it, before the harness's next span began
        t_host, n = steps[x.rid][-1]
        assert n == x.attrs["n_tokens"]
        (b,) = [b for b in r.rec.blocks if b.t1 == t_host] or [a]
        nxt = next((s for s in starts if s > b.t0), root.t1)
        assert b.t0 <= x.attrs["t_last"] <= nxt


def test_readers_on_the_tiny_window(tiny_window):
    r = tiny_window
    wait = reader("queue_wait_ms")(r)
    assert 0 <= wait <= reader("ttft_p90_ms")(r)
    host = reader("loop_host_ms")(r)
    assert 0 < host < reader("decode_block_ms")(r)
    # untraced: nothing to lay over a device trace
    assert reader("idle_host")(r) is None and reader("idle_admit")(r) is None
    assert not math.isnan(wait)
