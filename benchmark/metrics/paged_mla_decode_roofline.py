"""The latent decode kernel's share of its roofline, in percent: the
least time the chip could take for one call — the live latent positions
of the active slots read once, the absorbed queries in and the latent
outputs back, against its two products per head
(``lib/flops_bytes_deepseek_v3.py``; memory binds) — over the kernel's
own time a call, from the traced tail's ``XLA Ops`` events whose name
starts with ``paged_mla_decode``.  Live positions and active slots are
the means of the traced tail's own per-step samples; every step attends
the position it has just written too."""
from benchmark.lib import flops_bytes, flops_bytes_deepseek_v3 as fb, peaks


def read(run):
    kernel, samples = run.get("kernel"), run.get("tail_samples")
    if not kernel or not samples or not run["on_chip"]:
        return None
    arch = run["arch"]
    active = sum(s[0] for s in samples) / len(samples)
    live = sum(s[2] for s in samples) / len(samples) + active
    least, _ = flops_bytes.roofline_seconds(
        fb.mla_decode_flops(arch, live),
        fb.mla_decode_bytes(arch, active, live, run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
