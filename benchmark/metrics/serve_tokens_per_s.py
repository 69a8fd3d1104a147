"""Output tokens the engine handed over per second of the window: every
token stamped inside it, finished request or not, over the whole window."""
from benchmark.lib import stats


def read(run):
    n = sum(stats.count_in(stamps, run["t0"], run["t1"])
            for stamps in run["stamps"])
    return stats.rate(n, run["window_s"])
