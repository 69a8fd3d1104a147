"""Device time a prompt token costs, in microseconds: the summed
``device_s`` of the ``serving.prefill_wave`` records read back in the
window (set by ``PagedServingEngine._read_back``) over their summed
``tokens`` (set by ``_prefill_group``: the prompts' own lengths, padding
not counted).  What a prefill optimisation moves, whether by less
padding or by a faster program; 2 x parameters over the chip's peak is
its floor."""
from benchmark.lib import programs


def read(run):
    device_s = programs.total(run, "device_s", programs.WAVE)
    tokens = programs.total(run, "tokens", programs.WAVE)
    if device_s is None or not tokens:
        return None
    return 1e6 * device_s / tokens
