"""CPU rehearsal of the chip benchmark: four virtual CPU devices stand in for
the chips, and every cell runs at a tiny size. Nothing here is a device
measurement."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
