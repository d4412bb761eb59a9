"""Useful model operations of the decode blocks over their host time, as a share of the int8 peak."""
from bench import measure


def read(run):
    return measure.decode_mfu_pct(run)
