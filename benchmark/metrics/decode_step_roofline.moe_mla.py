"""The least time the chip could take for the median decode step of the
expert / latent-attention family, over the time it took, in percent: a
STEP-level share, like ``decode_step_roofline`` for GPT.  The least time
is the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s
(``lib/flops_bytes_deepseek_v3.py``): every weight every token passes
through, the head, the routed experts the step TOUCHED (the program's
counter, not all of them), and the live latent positions; memory binds.
Active slots and live positions are the means of the window's per-step
samples."""
from benchmark.lib import flops_bytes, flops_bytes_deepseek_v3 as fb, peaks


def read(run):
    samples, p50 = run.get("samples"), run["hist"]["decode"]["p50"]
    moe, steps = run.get("moe"), run["counters"]["decode_steps"]
    if not samples or not p50 or not moe or not steps or not run["on_chip"]:
        return None
    arch = run["arch"]
    active = sum(s[0] for s in samples) / len(samples)
    live = sum(s[2] for s in samples) / len(samples)
    touched = moe["moe_experts_touched"] / (steps * fb.moe_layers(arch))
    least, _ = flops_bytes.roofline_seconds(
        fb.decode_step_flops(arch, active, live),
        fb.decode_step_bytes(arch, active, live, touched,
                             run["weight_itemsize"], run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / p50
