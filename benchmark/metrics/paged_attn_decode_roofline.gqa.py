"""The grouped-query paged decode kernel's share of its roofline, in
percent: the least time the chip could take for the traced tail's
calls — window layers' over their rings, full layers' over the pool —
over the kernel's own time in the tail, from the ``XLA Ops`` events
whose name starts with ``paged_attn_decode``.  The least time is the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s
(``lib/flops_bytes_cohere2_moe.py``): the rows each call reached (the
tail's own counters, ``attn_window_rows`` + ``attn_full_rows``: ``min(p
+ 1, W)`` a ring, ``p + 1`` the pool, an active slot's) K and V of 1,024
values each, plus each call's queries in and outputs out; memory
binds."""
from benchmark.lib import flops_bytes, flops_bytes_cohere2_moe as fb, peaks


def read(run):
    kernel, tail = run.get("kernel"), run.get("tail_attn")
    if not kernel or not tail or not tail["decode_steps"] \
            or not run["on_chip"]:
        return None
    arch = run["arch"]
    rows = tail["attn_window_rows"] + tail["attn_full_rows"]
    least, _ = flops_bytes.roofline_seconds(
        fb.paged_attn_decode_flops(arch, rows),
        fb.paged_attn_decode_bytes(arch, kernel["calls"], rows,
                                   run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / kernel["seconds"]
