"""Scheduler time per engine step, told by the program: the SELF time of
``serving.step``, ``serving.admit``, ``serving.prefill_wave`` and
``serving.decode`` (what each spends outside its child spans: queue
grouping, numpy operands, counters, the first-token commit) plus the
per-slot commit loop ``serving.decode.commit``."""
from benchmark.lib import spans


def read(run):
    return spans.ms_per_step(run, spans.SCHEDULER, table="self_s")
