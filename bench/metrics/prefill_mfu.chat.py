"""Useful model operations of the admissions over their host time, as a share of the int8 peak."""
from bench import measure


def read(run):
    return measure.prefill_mfu_pct(run)
