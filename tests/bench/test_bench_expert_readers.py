"""The readers of the expert metrics and ``bench/kernels/expert_matmul.py``
on recorded runs, and the correctness check on a tiny Mellum2 cell.

``expert_rows_max`` reads the program's routing counters on its
``sched.decode`` spans outside the profiler's part; ``expert_busy_share``
and ``expert_matmul_roofline`` read the trace inside the traced decode
blocks, the roofline's counts coming from the counters of the record
that holds each block.  A program without the counters (the parent of
this harness's MoE cell) reads nothing.
"""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import hooks, measure, run, spans, trace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
MELLUM = json.loads((ROOT / "bench/configs/mellum2-12b.json").read_text())

hooks.import_program()
from repro.launch.telemetry import SpanRecord  # noqa: E402


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def block(t, rows, rows_max, live, **more):
    attrs = dict(expert_rows=rows, expert_rows_max=rows_max,
                 experts_live=live, **more)
    return [SpanRecord(0, None, 0, None, "sched.decode", t, t + 0.5, attrs),
            SpanRecord(1, 0, 0, None, "decode.call", t, t + 0.4, {}),
            SpanRecord(2, 0, 0, None, "decode.fetch", t + 0.4, t + 0.5, {})]


def a_run(trace_window=None, blocks=(), red=None):
    rec = SimpleNamespace(t_start=0.0, trace_window=trace_window, admits=[],
                          blocks=list(blocks))
    return measure.Run(None, MELLUM, None, 1.0, 0, PEAKS, [], rec, [], {},
                       trace=red)


def test_rows_max_over_the_windows_blocks(monkeypatch):
    recs = (block(0.0, 4096, 1000, 500) + block(1.0, 4096, 700, 510)
            + block(2.0, 2048, 300, 300) + block(3.0, 4096, 1100, 505))
    monkeypatch.setattr(spans, "records", lambda run: recs)
    read = reader("expert_rows_max")
    # 64 experts: the mean group of a block is rows / 64
    assert read(a_run()) == pytest.approx(
        (1000 + 700 + 300 + 1100) / ((4096 * 3 + 2048) / 64))
    # the profiler's part (the end of the 2nd block to the start of the
    # 4th) is left out
    assert read(a_run(trace_window=(1.6, 2.2))) == pytest.approx(
        (1000 + 700 + 1100) / (4096 * 3 / 64))


def test_nothing_without_counters(monkeypatch):
    plain = [SpanRecord(0, None, 0, None, "sched.decode", 0.0, 0.5,
                        {"kv_tiles": 8, "kv_tiles_live": 4})]
    monkeypatch.setattr(spans, "records", lambda run: plain)
    harness_block = hooks.Block(1, 0.1, 0.4, [(0, 1, 8, 4)], {0: 8})
    red = traced_reduction()
    assert reader("expert_rows_max")(a_run()) is None
    assert reader("expert_matmul_roofline")(
        a_run((0.0, 1.0), [harness_block], red)) is None
    monkeypatch.setattr(spans, "records", lambda run: None)
    assert reader("expert_rows_max")(a_run()) is None
    # no trace, nothing of the device
    assert reader("expert_busy_share")(a_run()) is None


def traced_reduction():
    """One traced decode block (trace ns 0-20000): 12 us of expert_matmul
    in 16 us of busy time, and an op that started before the block."""
    return trace.Reduction(
        ops={"expert_matmul": (np.array([1000, 8000]), np.array([7000,
                                                                  14000])),
             "fusion": (np.array([-500, 15000]), np.array([1000, 17000]))},
        busy=(np.array([-500, 15000]), np.array([14000, 17000])),
        spans={("decode", 1): (0, 20000), ("window", 0): (-1000, 30000)},
        window=(-1000, 30000), busy_s=16.5e-6, window_s=31e-6)


def test_busy_share_and_roofline_of_a_traced_block(monkeypatch):
    rows, live = 4096, 480
    recs = [SpanRecord(0, None, 0, None, "sched.decode", 0.9, 2.1,
                       dict(expert_rows=rows, expert_rows_max=900,
                            experts_live=live))]
    monkeypatch.setattr(spans, "records", lambda run: recs)
    b = hooks.Block(1, 1.0, 2.0, [(0, 1, 8, 4)], {0: 8})
    r = a_run(trace_window=(0.95, 2.05), blocks=[b], red=traced_reduction())
    # busy inside the block: 0-14000 and 15000-17000 ns
    assert reader("expert_busy_share")(r) == pytest.approx(100 * 12 / 16)
    d, f = MELLUM["hidden_size"], MELLUM["moe_intermediate_size"]
    ops = 3 * 2 * rows * d * f
    nbytes = 3 * live * d * f + live * 4 * (2 * f + d) + 3 * rows * 2 * (d + f)
    least = max(ops / PEAKS["int8_ops"], nbytes / PEAKS["hbm_bytes_per_s"])
    assert reader("expert_matmul_roofline")(r) == pytest.approx(
        100 * least / 12e-6)


# -- a tiny Mellum2 cell on the CPU -------------------------------------------

CELL = {"name": "tiny.mellum2", "config": "tiny-mellum2",
        "traffic": "tiny-chat", "chips": 1}
SPEC = {"end_to_end": [{"name": "setup_s", "unit": "s"}], "per_layer": []}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2**31 + 303


def tiny_cfg(**kw):
    return dict(json.loads((DATA / "tiny-mellum2.json").read_text()), **kw)


def run_tiny(cfg):
    import jax

    mix = json.loads((DATA / "tiny-chat.json").read_text())
    return run.run_cell(jax, CELL, cfg, mix, SPEC, SEED, 2.0, False, DEVICE)


def test_tiny_window_counts_what_the_harness_saw():
    """The program's counters on each decode block equal what the
    harness's own record of it implies: top_k rows a layer for each token
    a slot emitted; and the kernel's counts are read from them."""
    from bench import traffic

    cfg = tiny_cfg()
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    _engine, sched, server = run.set_up(cfg, mix, SEED)
    requests = traffic.generate(mix, cfg["vocab_size"], 2.0, SEED)
    window = run.serve(sched, mix, requests, 2.0, False)
    r = measure.Run(None, cfg, mix, 2.0, 0, PEAKS, requests, window,
                    window.outcomes, server)
    counts = r.kernel("expert_matmul")
    per_row = cfg["num_experts_per_tok"] * cfg["num_hidden_layers"]
    for b in window.blocks:
        attrs = counts.counters(r, b)
        assert attrs["expert_rows"] == per_row * sum(b.emitted.values())
        got = list(counts.calls(r, "decode", b))
        assert len(got) == 3 and all(o > 0 and n > 0 for o, n in got)
    assert reader("expert_rows_max")(r) >= 1.0


def test_sound_cell_is_correct_and_the_control_is_not():
    sound = run_tiny(tiny_cfg())
    assert sound["correct"], sound["checks"]
    control = run_tiny(tiny_cfg(weight_bits=4))
    assert not control["correct"]
    gap = control["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_an_expert_output_zeroed_is_not_correct(monkeypatch):
    """The output of each token's first-choice expert is zeroed where the
    program combines the experts: the check fails."""
    from repro.models import moe as M

    route = M.route

    def first_zeroed(logits, top_k):
        idx, weights = route(logits, top_k)
        return idx, weights.at[:, 0].set(0.0)

    monkeypatch.setattr(M, "route", first_zeroed)
    res = run_tiny(tiny_cfg())
    assert not res["correct"], res["checks"]
