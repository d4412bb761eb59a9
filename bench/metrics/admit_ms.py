"""Mean host time of one admission (chunked prefill, splice, first token on the host), outside the traced part of the window."""
from bench import measure


def read(run):
    return measure.mean_ms(run.spans("admit", traced=False))
