"""Share of the traced window in which no op ran on the device (the union
of the device's op intervals is its busy time), train cells."""

from chipbench.readers import idle_pct


def read(summary, counters, peak):
    return idle_pct(summary)
