"""Preemptions per request finished in the window: how often the pool,
not the slots, bound.  The sizing rule aims at 0."""


def read(run):
    done = run["counters"]["requests_completed"]
    if not done:
        return None
    return 100.0 * run["counters"]["preemptions"] / done
