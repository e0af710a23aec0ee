"""Test config: the CPU jax backend with 8 virtual devices.

This is the rebuild's Gloo-equivalent (SURVEY.md §4 takeaway (c)): multi-device
logic runs on a fake 8-device CPU mesh, no TPU needed. Both variables are read
when the backend initialises, so setting them here — before any test imports
jax — is enough, also on a host that has a chip.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
