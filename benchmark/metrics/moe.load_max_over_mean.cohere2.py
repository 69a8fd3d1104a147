"""The fullest HELD expert's load over the held experts' mean load, per
expert layer per decode step, as a ratio of the window's sums, for the
grouped-query window/global family (``cohere2_moe``):
``moe_max_expert_load`` (the largest per-expert assignment count of each
layer among the chip's held experts, summed over layers and steps) over
``moe_assignments`` (the assignments to held experts) / the held
experts (``num_experts``: a chip's share).  1.0 is perfectly even; the
grouped matmul's longest group, the straggler, grows with it.
``moe.load_max_over_mean``'s sibling for a configuration that names its
held experts ``num_experts``."""


def read(run):
    moe = run.get("moe")
    if not moe or not moe["moe_assignments"]:
        return None
    mean = moe["moe_assignments"] / run["arch"]["num_experts"]
    return moe["moe_max_expert_load"] / mean
