"""The least time the chip could take for the median decode step, over
the time it took, in percent: a STEP-level share from the host clock,
not a kernel's.  The least time is the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s (``lib/flops_bytes.py``: every matmul weight
once plus the live K/V of the active slots); at these sizes memory binds.
Active slots and live tokens are the means of the window's per-step
samples."""
from benchmark.lib import flops_bytes, peaks


def read(run):
    samples, p50 = run.get("samples"), run["hist"]["decode"]["p50"]
    if not samples or not p50 or not run["on_chip"]:
        return None
    arch = run["arch"]
    active = sum(s[0] for s in samples) / len(samples)
    live = sum(s[2] for s in samples) / len(samples)
    least, _ = flops_bytes.roofline_seconds(
        flops_bytes.decode_step_flops(arch, active, live),
        flops_bytes.decode_step_bytes(arch, active, live,
                                      run["weight_itemsize"],
                                      run["kv_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / p50
