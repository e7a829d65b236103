"""Re-exec environment for virtual-CPU-device subprocesses.

JAX backends can't be reconfigured after first use — so any code that
needs "N virtual CPU devices" after this process has touched a backend
(multichip dryrun, scale proofs) must re-exec a fresh interpreter with
the platform pinned BEFORE startup. This is the one shared
implementation of that environment.
"""

from __future__ import annotations

import os
import re


def cpu_reexec_env(n_devices: int, base_env: dict | None = None,
                   repo: str | None = None) -> dict:
    """Environment for a child interpreter running on `n_devices` virtual
    CPU devices: forces the CPU platform, swaps the host-device-count
    XLA flag, and prepends `repo` (default: the package's
    repository root) to PYTHONPATH while PRESERVING existing entries (they
    carry this environment's site customizations)."""
    env = dict(base_env if base_env is not None else os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    xla = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                 env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        f"{xla} --xla_force_host_platform_device_count={int(n_devices)}"
    ).strip()
    if repo is None:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    parts = [repo] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                      if p and p != repo]
    env["PYTHONPATH"] = os.pathsep.join(parts)
    return env
