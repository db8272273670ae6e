"""Virtual multi-device CPU provisioning (shared by tests/conftest.py and
tools/graft_check.py).

JAX can emulate an n-device mesh on one host with
--xla_force_host_platform_device_count — the capability that lets this
framework test TP/PP/DP collectives anywhere, where the reference needs
>= 2 physical GPUs (SURVEY.md §4). This module must stay import-safe
before jax initializes (no jax imports).
"""

from __future__ import annotations

import re
from typing import MutableMapping, Optional


def force_virtual_cpu_devices(
    n: int, env: Optional[MutableMapping[str, str]] = None
) -> MutableMapping[str, str]:
    """Set the env vars that force an n-device virtual CPU platform.

    Mutates and returns `env` (os.environ or a subprocess env copy). Must
    take effect before the jax backend initializes.
    """
    if env is None:
        import os

        env = os.environ
    env["JAX_PLATFORMS"] = "cpu"
    # replace any pre-existing device-count flag rather than appending
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    return env
