"""The decode step's share of the chip's bf16 peak: the FLOPs the traced
steps require (2 per weight per active row, attention over the positions
each row holds; empty rows count nothing), over the decode program's device
time.  The decode program is the one run once per engine step."""

from chipbench.readers import module, share


def read(summary, counters, peak):
    steps = counters.get("traced_steps", 0)
    m = module(summary, count=steps)
    if m is None or not steps:
        return None
    count, seconds = m
    flops = counters["traced_flops"] / steps * count
    return share(flops, seconds * counters["chips"], peak["bf16_flops_per_s"])
