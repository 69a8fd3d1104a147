"""Test harness config.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §4).  The device
count is an XLA flag read when the backend starts, so it has to be in the
environment before python does: on first entry we re-exec pytest with
JAX_PLATFORMS=cpu and 8 forced host devices.  The re-exec happens in
pytest_configure after stopping global capture so the child writes to the
real stdout.
"""
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight model-level tests (full pretrain steps, "
        "pallas interpret mode) excluded from the tier-1 budget")
    if os.environ.get("PADDLE_TPU_TEST_MODE") == "1":
        return
    cap = config.pluginmanager.getplugin("capturemanager")
    if cap is not None:
        try:
            cap.stop_global_capturing()
        except Exception:
            pass
    env = dict(os.environ)
    env["PADDLE_TPU_TEST_MODE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p and p != _REPO_ROOT])
    os.chdir(_REPO_ROOT)
    sys.stdout.flush()
    sys.stderr.flush()
    os.execve(sys.executable,
              [sys.executable, "-m", "pytest"] + sys.argv[1:], env)


if os.environ.get("PADDLE_TPU_TEST_MODE") == "1":
    import numpy as np
    import pytest

    @pytest.fixture(autouse=True)
    def _seed():
        import paddle_tpu as paddle
        paddle.seed(1234)
        np.random.seed(1234)
        yield
