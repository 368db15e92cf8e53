"""The train step's share of the chip's bf16 peak: the FLOPs its steps
require (6 per weight per token plus causal attention, no recomputation),
over the device time of the step program in the trace."""

from chipbench.readers import module, share


def read(summary, counters, peak):
    m = module(summary, name_part="step_fn")
    if m is None:
        return None
    count, seconds = m
    return share(counters["step_flops"] * count, seconds * counters["chips"],
                 peak["bf16_flops_per_s"])
