"""The paged differential-attention decode kernel's share of its
roofline, in percent: the least time the chip could take for the calls
of one decode step — the window layers' calls over the rings' live rows,
the full and cross layers' calls over the pooled layer's live positions,
each with its queries in and its outputs back
(``lib/flops_bytes_phi4flash.py``; memory binds) — over the kernel's own
time a step, from the traced tail's ``XLA Ops`` events whose name starts
with ``paged_diff_attn_decode``.  Live positions are the mean of the
traced tail's per-step samples, active slots and ring rows the traced
tail's own counters; every call attends the row it has just written
too."""
from benchmark.lib import flops_bytes, flops_bytes_phi4flash as fb, peaks


def read(run):
    kernel, samples = run.get("kernel"), run.get("tail_samples")
    tail = run.get("tail_hybrid")
    if not kernel or not samples or not tail or not tail["decode_steps"] \
            or not run["on_chip"]:
        return None
    arch = run["arch"]
    steps = tail["decode_steps"]
    active = tail["state_steps"] / steps
    live = sum(s[2] for s in samples) / len(samples) + active
    ring_rows = tail["window_rows_read"] / steps
    calls = {"pool": (fb.pool_reads_a_step(arch), live),
             "ring": (fb.count_of(arch, "window"), ring_rows)}
    least = 0.0
    for n, rows in calls.values():
        t, _ = flops_bytes.roofline_seconds(
            fb.paged_diff_attn_decode_flops(arch, rows),
            fb.paged_diff_attn_decode_bytes(arch, active, rows,
                                            run["kv_itemsize"]),
            peaks.peaks_for(run["device_kind"]))
        least += n * t
    steps_traced = kernel["calls"] / fb.kernel_calls_a_step(arch)
    return 100.0 * least * steps_traced / kernel["seconds"]
