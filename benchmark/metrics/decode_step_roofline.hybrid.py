"""The least time the chip could take for the median decode step of the
hybrid family, over the time it took, in percent: a STEP-level share,
like ``decode_step_roofline`` for GPT.  The least time is the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s
(``lib/flops_bytes_phi4flash.py``): the weights once, the pooled
layer's live positions once for the full layer and once for every cross
layer, the rings' live rows, the state-space state read and written, the
logits.  Active slots and live positions are the means of the window's
per-step samples; the ring rows a window layer read a step come from the
decode step's own counter."""
from benchmark.lib import flops_bytes, flops_bytes_phi4flash as fb, peaks


def read(run):
    samples, p50 = run.get("samples"), run["hist"]["decode"]["p50"]
    hybrid, steps = run.get("hybrid"), run["counters"]["decode_steps"]
    if not samples or not p50 or not hybrid or not steps \
            or not run["on_chip"]:
        return None
    arch = run["arch"]
    active = sum(s[0] for s in samples) / len(samples)
    live = sum(s[2] for s in samples) / len(samples)
    ring_rows = hybrid["window_rows_read"] / steps
    least, _ = flops_bytes.roofline_seconds(
        fb.decode_step_flops(arch, active, live, ring_rows),
        fb.decode_step_bytes(arch, active, live, ring_rows,
                             run["weight_itemsize"], run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / p50
