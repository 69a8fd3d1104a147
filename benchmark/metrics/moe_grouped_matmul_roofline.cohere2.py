"""The experts' grouped matmul's share of its roofline in the decode
step of the grouped-query window/global family (``cohere2_moe``), in
percent: the least time the chip could take for one layer's two calls —
the touched held experts' weights read once, the held assignments'
rows in and out (``lib/flops_bytes_cohere2_moe.py``; memory binds) —
over the kernels' own time a layer, from the traced tail's ``XLA Ops``
events whose name starts with ``moe_grouped_matmul`` and whose output
has the decode step's ``slots x num_experts_per_tok`` rows padded to
whole windows (``drivers/serve_cohere2.py``'s ``grouped_matmul``: a
prefill wave's calls have other rows and are left out).  Touched
experts and held rows are the window's counters a layer a step.  What
the kernel writes past the held rows (zeros) is not in the least time:
the algorithm does not need it."""
from benchmark.lib import flops_bytes, flops_bytes_cohere2_moe as fb, peaks


def read(run):
    calls, moe = run.get("grouped_matmul"), run.get("moe")
    steps = run["counters"]["decode_steps"]
    if not calls or not moe or not steps or not run["on_chip"]:
        return None
    arch = run["arch"]
    per_layer = steps * arch["num_hidden_layers"]
    rows = moe["moe_assignments"] / per_layer
    least, _ = flops_bytes.roofline_seconds(
        fb.grouped_matmul_flops(arch, rows),
        fb.grouped_matmul_bytes(arch, moe["moe_experts_touched"] / per_layer,
                                rows, run["weight_itemsize"]),
        peaks.peaks_for(run["device_kind"]))
    # two calls a layer: gate-up, then down
    return 100.0 * least * (calls["calls"] / 2) / calls["seconds"]
