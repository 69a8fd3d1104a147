"""Share of the rows the window's prefill programs ran that held no
prompt token, in percent: 100 x (1 - tokens / rows) over the
``serving.prefill_wave`` records read back in the window.  ``tokens`` is
the sum of the wave's prompts' lengths and ``rows`` its bucket, batch x
seq — both set by ``PagedServingEngine._prefill_group`` where the wave is
padded to ``_batch_bucket(len(group))`` rows of ``_seq_bucket`` positions.
A count: it repeats exactly for a seed and an order of admissions.  The
sequence ladder's part of it can be reckoned from the cell's length law
and its ``seq_buckets``; the batch ladder's part depends on how many
prompts of one bucket the queue held when slots came free."""
from benchmark.lib import programs


def read(run):
    tokens = programs.total(run, "tokens", programs.WAVE)
    rows = programs.total(run, "rows", programs.WAVE)
    if tokens is None or not rows:
        return None
    return 100.0 * (1.0 - tokens / rows)
