"""90th percentile of the wait in the queue, from a request's due time to
the start of its first admission (``sched.admit``, the program's own
span), over every request of the window whose wait lies outside the
profiler's part of it (``bench/spans.py:profiled``).  A request never
admitted counts as infinite; the line carries 1e12 ms for a percentile
that lands on one."""
from bench import spans


def read(run):
    return spans.queue_wait_ms(run, 0.9)
