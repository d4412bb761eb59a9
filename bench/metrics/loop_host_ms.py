"""Mean host time per decode block, outside the profiler's part of the
window (``bench/spans.py:profiled``), of the scheduler's own work between
device calls: its spans ``sched.loop``, ``decode.transfer``,
``decode.collect`` and ``decode.commit``."""
from bench import spans


def read(run):
    return spans.loop_host_ms(run)
