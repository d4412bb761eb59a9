"""expert_matmul's share of its roofline in the traced decode blocks."""
from bench import measure


def read(run):
    return measure.roofline_pct(run, "expert_matmul", "decode")
