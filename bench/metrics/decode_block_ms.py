"""Mean host time of one decode block, until its tokens are on the host, outside the traced part of the window."""
from bench import measure


def read(run):
    return measure.mean_ms(run.spans("decode", traced=False))
