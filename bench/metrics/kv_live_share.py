"""Share of the decode kernel's KV tiles that held a live key: the sum of
``kv_tiles_live`` over the sum of ``kv_tiles``, the program's own
attributes of its ``sched.decode`` spans, over the window's blocks outside
the profiler's part (``bench/spans.py:profiled``).  The kernel reads and
computes the live tiles and skips the rest.  None where the program
records no such attributes."""
from bench import spans


def read(run):
    recs = spans.records(run)
    if recs is None:
        return None
    blocks = [r.attrs for r in spans.untraced(run, recs)
              if r.name == "sched.decode" and "kv_tiles" in r.attrs]
    total = sum(a["kv_tiles"] for a in blocks)
    if not total:
        return None
    return sum(a["kv_tiles_live"] for a in blocks) / total
