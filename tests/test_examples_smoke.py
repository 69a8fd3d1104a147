"""Examples are user-facing documentation — they must actually run.

Each fast example executes as a real subprocess through its public CLI
(the exact invocation the README/docstring advertises), asserting its
success line.  The heavyweight hybrid/TP examples are exercised by the
model tests instead (test_gpt_hybrid, test_bert, test_rec).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=300):
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                "PADDLE_TPU_TEST_MODE": "1"})
    return subprocess.run([sys.executable] + args, env=env, cwd=REPO,
                          capture_output=True, timeout=timeout)


@pytest.mark.parametrize("script,args,expect", [
    ("examples/fluid_style_mnist.py", [],
     b"fluid-style static training on the TPU-native core: OK"),
    ("examples/fluid_py_reader_mnist.py", [],
     b"fluid py_reader async input on the TPU-native core: OK"),
    ("examples/ps_dataset_pipeline.py", [],
     b"PS-era dataset pipeline on the TPU-native core: OK"),
    pytest.param("examples/mnist_lenet.py", ["--steps", "3"],
                 b"test accuracy",
                 marks=pytest.mark.slow),   # ~14s; tier-1 budget
])
def test_example_runs(script, args, expect):
    out = _run([script] + args)
    assert out.returncode == 0, out.stderr.decode()[-2000:]
    assert expect in out.stdout, out.stdout[-2000:]


@pytest.mark.slow          # ~15s subprocess; tier-1 budget
def test_mnist_example_loss_starts_sane():
    """Regression for the normalization bug: the first logged loss must
    be near ln(10), not in the hundreds (raw-0-255 inputs hitting a
    [0,1]-scale Normalize blew it up to ~1400)."""
    out = _run(["examples/mnist_lenet.py", "--steps", "2"])
    assert out.returncode == 0, out.stderr.decode()[-2000:]
    first = next(ln for ln in out.stdout.decode().splitlines()
                 if "loss" in ln)
    assert float(first.rsplit("loss", 1)[1]) < 10.0, first
