"""The fullest expert's load over the mean load, per expert layer per
decode step, as a ratio of the window's sums: ``moe_max_expert_load``
(the largest per-expert assignment count of each layer, summed over
layers and steps) over ``moe_assignments`` / experts.  1.0 is perfectly
even; the grouped matmul's longest group, the straggler, grows with
it."""


def read(run):
    moe = run.get("moe")
    if not moe or not moe["moe_assignments"]:
        return None
    mean = moe["moe_assignments"] / run["arch"]["n_routed_experts"]
    return moe["moe_max_expert_load"] / mean
