"""The decode step's share of the chip's HBM bandwidth: the bytes the traced
steps require (each weight once, the keys and values each active row holds,
the new ones written; not the empty cache), over the decode program's
device time."""

from chipbench.readers import module, share


def read(summary, counters, peak):
    steps = counters.get("traced_steps", 0)
    m = module(summary, count=steps)
    if m is None or not steps:
        return None
    count, seconds = m
    moved = counters["traced_bytes"] / steps * count
    return share(moved, seconds * counters["chips"], peak["hbm_bytes_per_s"])
