"""Wall time per train step: the window over the steps in it."""


def read(run):
    return 1e3 * run["window_s"] / run["steps"]
