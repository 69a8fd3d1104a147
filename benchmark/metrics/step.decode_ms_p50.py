"""Median of the engine's ``serving.decode_step_s`` histogram over the
window: host clock around the jitted decode call, readback included."""


def read(run):
    p = run["hist"]["decode"]["p50"]
    return None if p is None else 1e3 * p
