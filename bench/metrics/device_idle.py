"""Share of the traced window in which no operation ran on the device."""
from bench import measure


def read(run):
    return measure.idle_pct(run)
