"""From a profiler trace to device busy time, kernel time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes for the
traced part of the window; ``jax.profiler.ProfileData`` reads it.  Device
planes are ``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one
event per HLO operation run, named by the instruction's text
(``%quant_matmul.3 = bf16[..] custom-call(..)``): a Pallas kernel's
instruction carries the name its ``pallas_call`` was given.  Host planes
hold the harness's own spans (``bench.admit.<n>``, ``bench.decode.<n>``
and ``bench.window.0`` around the whole traced part; ``bench/hooks.py``)
on the same clock, so every device event can be placed in the admission
or the decode block that issued it.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

import numpy as np

from bench import hooks

OPS_LINE = "XLA Ops"
# operations that only contain others (a scanned loop's ``while``): they
# count toward busy time, but the breakdown names what runs inside them
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%quant_matmul.3 = bf16[..] custom-call(..)`` -> ``quant_matmul``:
    the instruction's name, without its number."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def merge(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """Union of intervals: (starts, ends) of the merged ones, sorted."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx) if len(idx) else e[:0]


@dataclasses.dataclass
class Reduction:
    ops: dict               # op name -> (starts, ends) ns, sorted by start
    busy: tuple             # merged busy intervals (starts, ends), ns
    spans: dict             # (kind, seq) -> (start, end) ns
    window: tuple           # (start, end) ns of the traced window
    busy_s: float           # device-busy seconds in it, mean over chips
    window_s: float

    def kernel_seconds(self, kernel: str, records) -> float:
        """Device seconds of ``kernel``'s events that start inside the given
        host spans (admissions or decode blocks of ``bench/hooks.py``)."""
        if kernel not in self.ops:
            return 0.0
        starts, ends = self.ops[kernel]
        total = 0
        for r in records:
            kind = "admit" if isinstance(r, hooks.Admit) else "decode"
            se = self.spans.get((kind, r.seq))
            if se is None:
                continue
            i, j = np.searchsorted(starts, se)
            total += int((ends[i:j] - starts[i:j]).sum())
        return total * 1e-9

    def breakdown(self) -> dict:
        """The device operations that took most time, and the longest idle
        gaps by what the host was doing then: an admission, a decode
        block, or the scheduler's own work between them ("host")."""
        per_op = {n: int((e - s).sum()) for n, (s, e) in self.ops.items()
                  if n not in CONTAINERS}
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        bs, be = self.busy
        gap_len = bs[1:] - be[:-1]
        top = np.argsort(-gap_len, kind="stable")[:10]
        labelled = sorted(self.spans.items(), key=lambda kv: kv[1][0])
        starts = [se[0] for _k, se in labelled]
        gaps = []
        for g in top:
            mid = (be[g] + bs[g + 1]) // 2
            i = bisect.bisect_right(starts, mid) - 1
            what = "host"
            if i >= 0:
                (kind, _n), (a, b) = labelled[i]
                if a <= mid < b and kind != "window":
                    what = kind
            gaps.append([what, int(gap_len[g]) * 1e-9])
        return {"device_ops": [[n, t * 1e-9] for n, t in ops],
                "idle_gaps": gaps}


def reduce(trace_dir) -> Reduction:
    """Reduce the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    spans, devices = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            names, starts, ends = [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        names.append(e.name)
                        starts.append(e.start_ns)
                        ends.append(e.start_ns + e.duration_ns)
            if names:
                devices.append((names, np.asarray(starts, np.int64),
                                np.asarray(ends, np.int64)))
        else:
            for line in plane.lines:
                for e in line.events:
                    parts = e.name.split(".")
                    if len(parts) == 3 and parts[0] == "bench":
                        spans[(parts[1], int(parts[2]))] = (
                            int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
    if ("window", 0) not in spans:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = spans[("window", 0)]
    ops: dict = {}
    busy_ns, all_s, all_e = 0, [], []
    for names, s, e in devices:
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        keep = e > s
        ms, me = merge(s[keep], e[keep])
        busy_ns += int((me - ms).sum())
        all_s.append(ms)
        all_e.append(me)
        by_name: dict = {}
        base: dict = {}         # instruction text -> op name, memoized
        for k in np.flatnonzero(keep):
            t = names[k]
            if t not in base:
                base[t] = op_name(t)
            by_name.setdefault(base[t], []).append(k)
        for n, idx in by_name.items():
            idx = np.asarray(idx)
            prev = ops.get(n, (np.zeros(0, np.int64), np.zeros(0, np.int64)))
            ops[n] = (np.concatenate([prev[0], s[idx]]),
                      np.concatenate([prev[1], e[idx]]))
    for n, (s, e) in ops.items():
        order = np.argsort(s, kind="stable")
        ops[n] = (s[order], e[order])
    busy = merge(np.concatenate(all_s) if all_s else np.zeros(0, np.int64),
                 np.concatenate(all_e) if all_e else np.zeros(0, np.int64))
    return Reduction(ops=ops, busy=busy, spans=spans, window=(w0, w1),
                     busy_s=busy_ns * 1e-9 / max(len(devices), 1),
                     window_s=(w1 - w0) * 1e-9)
