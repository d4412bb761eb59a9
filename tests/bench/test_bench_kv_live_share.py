"""The reader of ``kv_live_share`` on recorded ``sched.decode`` spans: the
live share over the window's blocks, the profiler's part left out, and
nothing from a program whose blocks carry no tile counts."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import hooks, measure, spans

METRIC = Path(__file__).resolve().parents[2] / "bench" / "metrics" / \
    "kv_live_share.py"

hooks.import_program()
from repro.launch.telemetry import SpanRecord  # noqa: E402


def read(run):
    spec = importlib.util.spec_from_file_location("kv_live_share", METRIC)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def a_run(trace_window=None):
    window = SimpleNamespace(t_start=0.0, trace_window=trace_window,
                             admits=[], blocks=[])
    return measure.Run(None, None, None, 1.0, 0, {}, [], window, [], {})


def block(t, live, total=64, **more):
    """A decode block at ``t`` s with its leaves; tile counts in attrs."""
    attrs = dict(kv_tiles=total, kv_tiles_live=live, **more)
    return [SpanRecord(0, None, 0, None, "sched.decode", t, t + 0.5, attrs),
            SpanRecord(1, 0, 0, None, "decode.call", t, t + 0.4, {}),
            SpanRecord(2, 0, 0, None, "decode.fetch", t + 0.4, t + 0.5, {})]


def test_share_over_the_windows_blocks(monkeypatch):
    recs = block(0.0, 16) + block(1.0, 8) + block(2.0, 40) + block(3.0, 32)
    monkeypatch.setattr(spans, "records", lambda run: recs)
    assert read(a_run()) == pytest.approx((16 + 8 + 40 + 32) / 256)
    # traced from 1.6 to 2.2 s: the profiler's part runs from the end of
    # the second block to the start of the last, which alone are kept
    assert read(a_run(trace_window=(1.6, 2.2))) == pytest.approx(
        (16 + 8 + 32) / 192)


def test_nothing_without_tile_counts(monkeypatch):
    # a program whose blocks carry no counts (a speculative block, or a
    # program without them) reads nothing, and no spans read nothing
    recs = [r for r in block(0.0, 0) if r.name != "sched.decode"] + [
        SpanRecord(0, None, 0, None, "sched.decode", 0.0, 0.5, {"block": 0})]
    monkeypatch.setattr(spans, "records", lambda run: recs)
    assert read(a_run()) is None
    monkeypatch.setattr(spans, "records", lambda run: None)
    assert read(a_run()) is None


def test_tiny_window_counts_what_the_harness_saw():
    """On a tiny scheduler run on the CPU, the program's counts equal the
    tiles that the harness's own record of each block (every decoding
    slot's position and emissions) says the kernel needed."""
    import json

    from bench import run, traffic
    from repro.kernels.decode_attention import decode_block_s

    data = Path(__file__).resolve().parent / "data"
    cfg = json.loads((data / "tiny.json").read_text())
    mix = json.loads((data / "tiny-chat.json").read_text())
    _engine, sched, server = run.set_up(cfg, mix, 2**31 + 11)
    requests = traffic.generate(mix, cfg["vocab_size"], 2.0, 2**31 + 11)
    window = run.serve(sched, mix, requests, 2.0, False)
    r = measure.Run(None, cfg, mix, 2.0, 0, {}, requests, window,
                    window.outcomes, server)
    bs = decode_block_s(sched.cache_len)
    live = sum(-(-(pos + j + 1) // bs)
               for b in window.blocks for rid, _had, _budget, pos in b.slots
               for j in range(b.emitted[rid]))
    n_tiles = -(-sched.cache_len // bs)
    total = len(window.blocks) * sched.max_slots * n_tiles * \
        sched.block_steps
    assert 0 < live <= total
    assert read(r) == pytest.approx(live / total)
