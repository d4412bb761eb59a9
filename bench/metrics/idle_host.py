"""Share of the traced window in which no operation ran on the device
while the scheduler did its own host work (``sched.loop``,
``decode.transfer``, ``decode.collect``, ``decode.commit``)."""
from bench import spans


def read(run):
    return spans.idle_pct(run, spans.HOST)
