"""Seconds from the window's export start to the first batch the step
loop receives (harness clock): the wait before the pipe delivers."""

import math


def read(summary, counters, peak):
    v = counters.get("first_batch_s")
    return v if v is not None and math.isfinite(v) else None
