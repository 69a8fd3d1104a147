"""Distinct HELD experts an expert layer touches in one decode step,
over the experts the chip holds, in percent, for the grouped-query
window/global family (``cohere2_moe``): ``moe_experts_touched`` (the
decode step counts each layer's assignments per held expert on the
device; the engine sums the held experts with at least one) over
decode steps x layers (``num_hidden_layers``) x held experts
(``num_experts``: a chip's share), all over the window.  At 64 slots x
8 of 128 columns a held expert sees 4 rows a step, and 1 - e^-4 = 98%
of the 16 are touched: what a step streams of the held experts'
weights."""


def read(run):
    moe, steps = run.get("moe"), run["counters"]["decode_steps"]
    if not moe or not steps:
        return None
    arch = run["arch"]
    return 100.0 * moe["moe_experts_touched"] / (
        steps * arch["num_hidden_layers"] * arch["num_experts"])
