"""Clean child-interpreter environment for spawning CPU-backend worker
processes (the chaos bench, the multi-process recovery tests, any script
fanning out supervised workers on a dev box).

A worker that must run on the CPU backend with N host devices needs
``JAX_PLATFORMS`` and the host-platform device count in its environment
BEFORE python starts (XLA reads the flag when the backend starts).  This
is the one shared implementation — ``bench.py``'s ``_reexec_cpu_mesh``
keeps a private copy only so that it need not import ``paddle_tpu`` (and
thus this module) twice.

Stdlib-only, like the rest of paddle_tpu.testing.
"""
from __future__ import annotations

import os


def clean_cpu_env(repo_root, device_count=1, base=None):
    """A child env dict: repo-first PYTHONPATH (operator-provided entries
    kept), JAX_PLATFORMS=cpu, and XLA_FLAGS rewritten to force
    ``device_count`` host devices (foreign flags preserved)."""
    env = dict(base if base is not None else os.environ)
    kept = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
            if p and p != repo_root]
    env["PYTHONPATH"] = os.pathsep.join([repo_root] + kept)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={device_count}"])
    return env
