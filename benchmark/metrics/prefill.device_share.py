"""Share of the device's time that went to prefill waves, in percent:
the summed ``device_s`` of the ``serving.prefill_wave`` records read back
in the window over the summed ``device_s`` of all its records (waves and
decode steps).  ``device_s`` is set by ``PagedServingEngine._read_back``
on the readback-side span of every program: the time from the later of
(its enqueue returned, the program before it arrived on the host) to its
own tokens' arrival — the interval the ``serving.prefill_s`` and
``serving.decode_step_s`` histograms observe, so this reads
``hist.prefill.sum / (hist.prefill.sum + hist.decode.sum)`` of the same
window, program by program.

What ``step.prefill_share`` meant to say and cannot since the loop runs
a step ahead of its readbacks: that one is the HOST's time inside the
wave's two spans over its time in ``serving.step``, and under-reads the
device's share by the host's lead."""
from benchmark.lib import programs


def read(run):
    whole = programs.total(run, "device_s")
    if not whole:
        return None
    # a window of decode steps alone reads 0, not nothing
    return 100.0 * (programs.total(run, "device_s", programs.WAVE) or 0.0) \
        / whole
