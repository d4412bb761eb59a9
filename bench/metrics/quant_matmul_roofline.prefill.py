"""quant_matmul's share of its roofline in the traced admissions."""
from bench import measure


def read(run):
    return measure.roofline_pct(run, "quant_matmul", "admit")
