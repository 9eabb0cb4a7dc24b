"""Test harness: force an 8-device virtual CPU mesh so multi-chip sharding
logic is exercised without TPU hardware (bench.py, by contrast, runs on the
real chip and must NOT import this).

The CPU platform is named both in the environment (for child processes)
and in jax.config (after the env vars, before any backend use).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
