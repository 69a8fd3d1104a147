"""Host time per ``eng.step()`` outside the jitted calls: the wall time
of every step in the window minus what the engine's own
``serving.decode_step_s`` and ``serving.prefill_s`` histograms observed
inside them (each a host clock around one jitted call that ends in the
sampled-token readback).  Admission, paging, numpy bookkeeping, operand
transfer set-up, COW copies.  With every slot busy a step's host time is
time no slot decodes."""


def read(run):
    steps = run.get("step_s")
    if not steps:
        return None
    inside = run["hist"]["decode"]["sum"] + run["hist"]["prefill"]["sum"]
    return 1e3 * (sum(steps) - inside) / len(steps)
