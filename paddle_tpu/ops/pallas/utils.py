"""Shared probes for the Pallas kernel modules."""
from __future__ import annotations

import os

import jax

try:
    from jax.experimental import pallas as pl  # noqa: F401
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401
    HAS_PALLAS = True
except ImportError:  # pragma: no cover
    HAS_PALLAS = False


def on_tpu():
    """Is the default device a TPU?  A backend that fails to start
    raises here: "no chip" and "the chip did not answer" are different
    answers, and only the first may select the XLA path."""
    return jax.devices()[0].platform == "tpu"


def pallas_enabled():
    """Master gate for the compiled Pallas paths.  Set
    ``PADDLE_TPU_DISABLE_PALLAS=1`` to force every op to its XLA fallback
    (an operator's switch for A/B runs such as tools/bench_sweep.py;
    nothing in the repo sets it on a kernel failure — a kernel the
    chip's compiler refuses fails the run)."""
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS", "") not in ("", "0"):
        return False
    return HAS_PALLAS and on_tpu()


def count_paged_kernel():
    """Trace-time engagement counter of the paged-attention Pallas
    kernel, fp and int8 pools alike (``serving.paged_kernel_calls``).
    Like :func:`count_dequant_kernel` it fires once per kernel instance
    per compiled executable — it answers "did what XLA built contain
    the kernel?", which is what ``chip_smoke.py`` reads instead of
    assuming the gate admitted the shape."""
    from ...observability import metrics
    metrics.counter("serving.paged_kernel_calls").inc()


def count_paged_diff_kernel():
    """Trace-time engagement counter of the paged differential-attention
    kernel (``serving.paged_diff_kernel_calls``): one a kernel instance
    a compiled executable, as :func:`count_paged_kernel`'s."""
    from ...observability import metrics
    metrics.counter("serving.paged_diff_kernel_calls").inc()


def count_grouped_matmul_kernel():
    """Trace-time engagement counter of the experts' grouped-matmul
    Pallas kernel (``serving.grouped_matmul_kernel_calls``): one a
    kernel instance a compiled executable, as
    :func:`count_paged_kernel`'s."""
    from ...observability import metrics
    metrics.counter("serving.grouped_matmul_kernel_calls").inc()


def count_flash_prefill_kernel():
    """Trace-time engagement counter of the prefill flash forward
    (``serving.flash_prefill_kernel_calls``): one a kernel instance a
    compiled executable, as :func:`count_paged_kernel`'s."""
    from ...observability import metrics
    metrics.counter("serving.flash_prefill_kernel_calls").inc()


def count_dequant_kernel(kernel):
    """Trace-time engagement counter for the quantized-serving kernels
    (ISSUE 9): bumps the aggregate ``serving.dequant_kernel_calls``
    family cell AND a per-kernel series
    (``serving.dequant_kernel_calls_<kernel>``), so "the dequant GEMM
    engaged but quantized paged attention fell back" stays visible.
    Fires once per kernel per compiled executable — it answers "did the
    Pallas path engage in what XLA built?", not "how many steps ran".
    Telemetry must never break a trace, so failures are swallowed."""
    try:
        from ...observability import metrics
        metrics.counter("serving.dequant_kernel_calls").inc()
        metrics.counter(f"serving.dequant_kernel_calls_{kernel}").inc()
    except Exception:                                  # noqa: BLE001
        pass
