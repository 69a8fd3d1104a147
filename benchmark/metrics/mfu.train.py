"""Model FLOP/s utilization: the forward+backward FLOPs a token requires
(``lib/flops_bytes.py``: causal attention, tied head, no lookups, no
recomputation) times tokens per second, over chips times the bf16 peak."""
from benchmark.lib import flops_bytes, peaks


def read(run):
    if not run["on_chip"]:
        return None
    per_token = flops_bytes.train_flops_per_token(run["arch"],
                                                  run["seq_len"])
    peak = peaks.peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return (100.0 * per_token * run["tokens"] / run["window_s"]
            / (run["chips"] * peak))
