"""The least time the chip could take for the median decode step of the
looped family, over the time it took, in percent: a STEP-level share,
like ``decode_step_roofline`` for GPT.  The least time is the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s
(``lib/flops_bytes_ouro.py``): the layers' weights once for EVERY pass,
the head, the final norm and the gate, and the live K/V of every (pass,
layer) pair; memory binds.  Active slots and live positions are the
means of the window's per-step samples."""
from benchmark.lib import flops_bytes, flops_bytes_ouro as fb, peaks


def read(run):
    samples, p50 = run.get("samples"), run["hist"]["decode"]["p50"]
    if not samples or not p50 or not run.get("loop") or not run["on_chip"]:
        return None
    arch = run["arch"]
    active = sum(s[0] for s in samples) / len(samples)
    live = sum(s[2] for s in samples) / len(samples)
    least, _ = flops_bytes.roofline_seconds(
        fb.decode_step_flops(arch, active, live),
        fb.decode_step_bytes(arch, active, live, run["weight_itemsize"],
                             run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / p50
