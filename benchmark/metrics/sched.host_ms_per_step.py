"""Host time per ``eng.step()`` that the device did NOT hide: the window
minus what the engine's own ``serving.decode_step_s`` and
``serving.prefill_s`` histograms observed in it, over the window's steps.
Each histogram observation runs from the later of (the program's enqueue
returned, the host read the program before it) to the host's reading of
its own tokens, so the two sums tile the time in which the host had a
program in flight, program by program; what is left of the window is the
time in which it had none — admission, paging and dispatch done with the
device's queue empty.  About 0 while the host leads the device (step n+1
dispatched before step n is read back), and the whole of the host's work
a step for an engine that reads each step back before it builds the
next.

What it does not see: a host that is slow WHILE a program is in flight.
A program's arrival is stamped when the host reads it, not when the
device finished it, so its interval swallows the wait; the device's idle
share (``device.busy_s`` over ``window_s``, from the trace) is what
shows that case, and the launch gaps between queued programs (0.1% of
the window on the chip) fall inside the intervals too.  So it reads 0 to
the fourth digit on the chip today and moves only once a dispatch finds
nothing in flight (a drain: ``stats()["drains"]``, or a loop that stops
overlapping) — ``tests/benchmark/test_program_spans.py::
test_host_ms_per_step_rises_by_the_time_the_queue_stood_empty`` shows
both.  The host's own work, hidden or not, is the three span metrics'
(``sched.span_self_ms_per_step``, ``pager.span_ms_per_step``,
``step.dispatch_ms_per_step``).  It may read a hair under 0: a program
that straddles the window's edge is observed whole (at most one
program's time over the steps of a window, about -0.02 ms on the chip).
That is not clamped."""


def read(run):
    steps = run["step_s"]
    if not steps:
        return None
    inside = run["hist"]["decode"]["sum"] + run["hist"]["prefill"]["sum"]
    return 1e3 * (run["window_s"] - inside) / len(steps)
