"""What the harness reads from the process around the system under test:
compile counters, device memory, and the profiler around a short window."""
import contextlib
import tempfile

from . import xplane

COMPILE_COUNTERS = ("count", "persistent_cache_hits",
                    "persistent_cache_misses", "persistent_cache_requests")


def compile_counters():
    """The program's ``compile.*`` counters (XLA backend compiles and the
    persistent cache's traffic), as they stand."""
    from paddle_tpu.observability import metrics
    return {k: metrics.counter(f"compile.{k}").value
            for k in COMPILE_COUNTERS}


def delta(after, before):
    return {k: after[k] - before[k] for k in after}


def memory_peak_bytes(devices):
    """``peak_bytes_in_use`` of the fullest device: the process's
    high-water mark — sound because one run is one process.  None where
    the backend reports nothing (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return None if None in peaks else int(max(peaks))


@contextlib.contextmanager
def traced(result, on_chip):
    """Profile the body into a directory under TMPDIR, reduce the trace
    (``xplane.reduce``) into ``result["trace"]`` and throw the directory
    away.  ``on_chip``: only a device's own operations count, never the
    CPU stand-in.  The python tracer is off: it slows the host it is
    measuring and the harness's own ``bench.*`` spans say what the host
    did."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        planes = xplane.load(d)
    result["trace"] = xplane.reduce(planes, on_chip)
