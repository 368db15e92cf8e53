"""Share of the traced window in which no op ran on the device, serve
cells."""

from chipbench.readers import idle_pct


def read(summary, counters, peak):
    return idle_pct(summary)
