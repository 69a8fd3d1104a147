"""Metric arithmetic on plain numbers: percentiles, gaps, rates, spreads.
Kept here so every PR computes the same number the same way."""
import math
import statistics


def percentile(values, p):
    """Nearest-rank percentile (p in 0..100) of ``values``; None if empty.
    Nearest rank returns a value that was observed — a tail of request
    times is a request's time, not an interpolation."""
    data = sorted(values)
    if not data:
        return None
    rank = max(1, math.ceil(p / 100.0 * len(data)))
    return data[min(rank, len(data)) - 1]


def gaps_ending_in(stamps, t0, t1):
    """The gaps of one request whose LATER token fell in [t0, t1]: a gap
    belongs to the window that saw it end."""
    return [b - a for a, b in zip(stamps, stamps[1:]) if t0 <= b <= t1]


def count_in(stamps, t0, t1):
    return sum(1 for s in stamps if t0 <= s <= t1)


def rate(count, seconds):
    """Work per second over ALL the time of the window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def iqr_share(values):
    """The spread the contract's bounds are set from: the distance
    between the first and third quartile as Python's
    ``statistics.quantiles(values, n=4)`` gives them, over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
