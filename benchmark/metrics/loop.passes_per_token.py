"""Passes of the stacked layers a decoded token ran, over the window:
``loop_passes`` (the sum over passes of the active slots the published
exit rule kept running, counted on the device from the gate's
cumulative probability and handed back with the sampled tokens) over
``loop_tokens`` (active slots a step, summed).  ``total_ut_steps`` while
the threshold is 1; where per-token adaptive depth will show."""


def read(run):
    loop = run.get("loop")
    if not loop or not loop["loop_tokens"]:
        return None
    return loop["loop_passes"] / loop["loop_tokens"]
