"""90th percentile of time to first token over every request of the
window: from the request's due time to its first token on the host.  A
request that never got its first token counts as infinite; the line
carries 1e12 ms for a percentile that lands on one."""
from bench import measure


def read(run):
    first = run.first_token_s()
    t0 = run.rec.t_start
    ms = [1e3 * (first[rid] - t0) - due if rid in first else float("inf")
          for rid, _toks, _budget, due in run.requests]
    return min(measure.percentile(ms, 0.9), 1e12)
