"""Share of the time in ``serving.step`` that is inside a prefill wave
(``serving.prefill_wave``: jitted call, first-token readback, commit —
the interval the ``serving.prefill_s`` histogram observes)."""
from benchmark.lib import spans


def read(run):
    return spans.share_of_step(run, ("serving.prefill_wave",))
