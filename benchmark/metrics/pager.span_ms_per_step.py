"""KV-pager time per engine step: ``serving.pager.admit`` (page table +
prefix lookup of every admitted prompt) plus ``serving.pager.ensure``
(a writable position for every active slot: fresh tail pages, COW
copies, preemption)."""
from benchmark.lib import spans


def read(run):
    return spans.ms_per_step(run, spans.PAGER)
