"""Share of the traced window in which no operation ran on the device
inside an admission's or readmission's round trips (``admit.keys``,
``admit.prefill``, ``admit.insert``, ``admit.first_token``,
``readmit.resume``, ``readmit.insert``)."""
from bench import spans


def read(run):
    return spans.idle_pct(run, spans.ADMIT)
