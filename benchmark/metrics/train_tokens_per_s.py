"""Tokens trained per second: every step dispatched in the window, over
all the time from the window's opening to the last loss being ready."""
from benchmark.lib import stats


def read(run):
    return stats.rate(run["tokens"], run["window_s"])
