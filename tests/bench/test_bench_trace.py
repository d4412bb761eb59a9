"""The trace reduction: interval arithmetic, instruction names, and a
small trace recorded on a v5e (the tiny test configuration served through
``bench/hooks.py`` with the profiler on: three admissions, decode blocks
of eight steps)."""
import gzip
import shutil
from pathlib import Path

import numpy as np

from bench import hooks, trace

DATA = Path(__file__).resolve().parent / "data"


def test_merge_and_names():
    s, e = trace.merge(np.array([0, 5, 3, 20, 21]), np.array([4, 8, 6, 25, 22]))
    assert s.tolist() == [0, 20] and e.tolist() == [8, 25]
    assert trace.op_name("%quant_matmul.12 = bf16[32,576] custom-call(%x)") \
        == "quant_matmul"
    assert trace.op_name("%fusion.3 = f32[8] fusion(%quant_matmul.2)") \
        == "fusion"
    assert trace.op_name("%pad_add_fusion = u32[2] fusion(%a)") \
        == "pad_add_fusion"


def test_kernel_seconds_and_breakdown():
    ops = {"quant_matmul": (np.array([10, 30, 70]), np.array([20, 35, 80])),
           "fusion": (np.array([20, 50]), np.array([30, 60])),
           "while": (np.array([10]), np.array([80]))}
    red = trace.Reduction(
        ops=ops, busy=(np.array([10, 50, 70]), np.array([35, 60, 80])),
        spans={("admit", 1): (5, 40), ("decode", 2): (45, 90),
               ("window", 0): (0, 100)},
        window=(0, 100), busy_s=45e-9, window_s=100e-9)
    admit = hooks.Admit(1, 0, 8, 0.0, 1.0)
    block = hooks.Block(2, 0.0, 1.0, [], {})
    assert red.kernel_seconds("quant_matmul", [admit]) == 15 * 1e-9
    assert red.kernel_seconds("quant_matmul", [block]) == 10 * 1e-9
    assert red.kernel_seconds("prefill_attention", [admit]) == 0.0
    bd = red.breakdown()
    assert [n for n, _ in bd["device_ops"]] == ["quant_matmul", "fusion"]
    # gaps 35..50 (inside no admission: the decode span starts at 45, so
    # its middle 42 is host time) and 60..70 (inside the decode block)
    assert bd["idle_gaps"] == [["host", 15 * 1e-9], ["decode", 10 * 1e-9]]


def test_recorded_v5e_trace(tmp_path):
    src = DATA / "tiny_v5e.xplane.pb.gz"
    dst = tmp_path / "plugins" / "profile" / "run" / "t.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    red = trace.reduce(tmp_path)
    admits = sorted(k for k in red.spans if k[0] == "admit")
    blocks = sorted(k for k in red.spans if k[0] == "decode")
    assert len(admits) == 3 and len(blocks) >= 2
    assert 0 < red.busy_s <= red.window_s
    assert {"quant_matmul", "prefill_attention",
            "decode_attention"} <= set(red.ops)
    recs = ([hooks.Admit(n, 0, 8, 0.0, 0.0) for _k, n in admits]
            + [hooks.Block(n, 0.0, 0.0, [], {}) for _k, n in blocks])
    in_admit = red.kernel_seconds("prefill_attention", recs[:3])
    in_decode = red.kernel_seconds("decode_attention", recs[3:])
    assert in_admit > 0 and in_decode > 0
    # every kernel event lies inside the span that issued it
    assert red.kernel_seconds("prefill_attention", recs[3:]) == 0
    assert red.kernel_seconds("decode_attention", recs[:3]) == 0
    total = sum(red.kernel_seconds("quant_matmul", [r]) for r in recs)
    s, e = red.ops["quant_matmul"]
    assert abs(total - (e - s).sum() * 1e-9) < 1e-12
    bd = red.breakdown()
    assert len(bd["device_ops"]) == 10 and bd["idle_gaps"]
