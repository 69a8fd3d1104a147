"""Share of the time in ``serving.step`` that the host spends blocked on
the device for sampled tokens (``serving.decode.readback`` and
``serving.prefill_wave.readback``): how far the device, not the host,
sets the pace."""
from benchmark.lib import spans


def read(run):
    return spans.share_of_step(run, spans.READBACK)
