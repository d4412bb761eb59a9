"""Routing imbalance of the MoE layers at decode: the sum of
``expert_rows_max`` (the largest group of each step and layer) over the
sum of ``expert_rows`` / ``num_experts`` (the mean group), the program's
own counters on its ``sched.decode`` spans, over the window's blocks
outside the profiler's part (``bench/spans.py:profiled``).  The largest
group sets the grouped kernel's longest run of tiles for one expert.
None where the program records no such counters."""
from bench import spans


def read(run):
    recs = spans.records(run)
    if recs is None:
        return None
    blocks = [r.attrs for r in spans.untraced(run, recs)
              if r.name == "sched.decode" and "expert_rows" in r.attrs]
    rows = sum(a["expert_rows"] for a in blocks)
    if not rows:
        return None
    return sum(a["expert_rows_max"] for a in blocks) / (
        rows / run.cfg["num_experts"])
