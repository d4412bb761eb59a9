"""90th percentile over the finished requests of the time per output
token after the first: (last token on the host - first token on the host)
/ (tokens - 1).  Tokens reach the host a decode block at a time, so this
is a per-request mean, not a per-gap statistic."""
from bench import measure


def read(run):
    times = run.token_times()
    ms = []
    for o in run.outcomes:
        steps = times.get(o.rid, [])
        if o.status == "ok" and len(o.tokens) > 1 and steps:
            (t_first, _), (t_last, n) = steps[0], steps[-1]
            if n == len(o.tokens):
                ms.append(1e3 * (t_last - t_first) / (n - 1))
    return measure.percentile(ms, 0.9) if ms else None
