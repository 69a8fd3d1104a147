"""Programs the persistent compilation cache did not hold during set-up
(``compile.persistent_cache_misses``): 0 on every run after a checkout's
first, or set-up is paying for compilation again."""


def read(run):
    return run["setup_compile"]["persistent_cache_misses"]
