"""The least time the chip could take for the median decode step of the
grouped-query window/global family (``cohere2_moe``), over the time it
took, in percent: a STEP-level share, like ``decode_step_roofline.scmoe``
for the latent expert-share family.  The least time is the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s
(``lib/flops_bytes_cohere2_moe.py``): every weight every token passes
through (attention, router, shared experts a layer), the tied head, the
held experts the step TOUCHED (the program's counter), and the K/V rows
the step's window and full layers read (the program's counters); memory
binds.  Active slots are the mean of the window's per-step samples."""
from benchmark.lib import flops_bytes, flops_bytes_cohere2_moe as fb, peaks


def read(run):
    samples, p50 = run.get("samples"), run["hist"]["decode"]["p50"]
    moe, attn = run.get("moe"), run.get("attn")
    steps = run["counters"]["decode_steps"]
    if not samples or not p50 or not moe or not attn or not steps \
            or not run["on_chip"]:
        return None
    arch = run["arch"]
    active = sum(s[0] for s in samples) / len(samples)
    per_layer = steps * arch["num_hidden_layers"]
    rows = (attn["attn_window_rows"] + attn["attn_full_rows"]) / steps
    least, _ = flops_bytes.roofline_seconds(
        fb.decode_step_flops(arch, active, rows,
                             moe["moe_assignments"] / per_layer),
        fb.decode_step_bytes(arch, active, rows,
                             moe["moe_experts_touched"] / per_layer,
                             run["weight_itemsize"], run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / p50
