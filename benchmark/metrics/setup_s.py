"""Process start to window start: imports, device start-up, weights,
compilation (or loading from the persistent cache), warm-up, ramp."""


def read(run):
    return run["setup_s"]
