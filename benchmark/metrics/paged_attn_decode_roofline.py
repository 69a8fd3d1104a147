"""The paged decode kernel's share of its roofline, in percent, where a
looped model calls it once for every (pass, layer) pair: the least time
the chip could take for one call — the live K and V rows of the active
slots read once, the queries in and the outputs back, against its two
products per head (``lib/flops_bytes_ouro.py``; memory binds) — over the
kernel's own time a call, from the traced tail's ``XLA Ops`` events
whose name starts with ``paged_attn_decode``.  Live positions and
active slots are the means of the traced tail's own per-step samples;
every step attends the position it has just written too."""
from benchmark.lib import flops_bytes, flops_bytes_ouro as fb, peaks


def read(run):
    kernel, samples = run.get("kernel"), run.get("tail_samples")
    if not kernel or not samples or not run.get("loop") \
            or not run["on_chip"]:
        return None
    arch = run["arch"]
    active = sum(s[0] for s in samples) / len(samples)
    live = sum(s[2] for s in samples) / len(samples) + active
    least, _ = flops_bytes.roofline_seconds(
        fb.paged_attn_decode_flops(arch, live),
        fb.paged_attn_decode_bytes(arch, active, live, run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
