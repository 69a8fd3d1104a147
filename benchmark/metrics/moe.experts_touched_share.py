"""Distinct routed experts an expert layer touches in one decode step,
over the experts it holds, in percent: ``moe_experts_touched`` (the
decode step counts each layer's assignments per expert on the device
and hands them back with the sampled tokens; the engine sums the
experts with at least one) over decode steps x expert layers x experts,
all over the window.  Under even routing 64 slots x 6 touch
128 x (1 - e^-3) = 95% of 128: what a step streams of the experts'
weights."""
from benchmark.lib import flops_bytes_deepseek_v3 as fb


def read(run):
    moe, steps = run.get("moe"), run["counters"]["decode_steps"]
    if not moe or not steps:
        return None
    arch = run["arch"]
    return 100.0 * moe["moe_experts_touched"] / (
        steps * fb.moe_layers(arch) * arch["n_routed_experts"])
