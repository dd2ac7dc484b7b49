"""Force the CPU platform with 8 virtual devices.

Import first in dev scripts (``import devcpu``) to run on the CPU before
JAX initializes a backend: tests get the same treatment from
tests/conftest.py, and the CLI has ``--platform cpu``. The chip is reached
only through the chip tool (``python chip_smoke.py``).
"""

import os

_DEFAULT_DEVICES = 8

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={_DEFAULT_DEVICES}"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
