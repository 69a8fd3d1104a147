"""Host time per engine step before the device has the work: operands
to the device and the executable's lookup (``serving.*_operands``), then
the enqueue of the jitted call (``serving.*.dispatch``), prefill waves
and the decode step together."""
from benchmark.lib import spans


def read(run):
    return spans.ms_per_step(run, spans.DISPATCH)
