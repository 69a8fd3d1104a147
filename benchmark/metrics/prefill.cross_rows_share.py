"""The share of prefilled rows that went through the stateless layers
(the memory units and cross layers, which keep nothing a later position
reads), in percent, over the window: ``prefill_cross_rows`` over
``prefill_rows``, both returned by the wave's own program (the rows its
two halves were traced over; ``prefill_paged`` hands them back behind
the pools and they ride the first tokens' readback) and summed by the
engine.  A prompt needs those layers for its last position alone: under
2% while that saving is engaged (one row of a bucket of 128..1024), 100%
when the program runs them on every row."""


def read(run):
    hybrid = run.get("hybrid")
    if not hybrid or not hybrid["prefill_rows"]:
        return None
    return 100.0 * hybrid["prefill_cross_rows"] / hybrid["prefill_rows"]
