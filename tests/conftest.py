"""Test env: force an 8-device virtual CPU mesh before jax backends init.

The suite runs on the CPU: sharding tests run over
xla_force_host_platform_device_count=8 (see __graft_entry__.py), and
the platform is forced here as well as by JAX_PLATFORMS=cpu in the
tier-1 command, so a bare `pytest` on a machine that holds a chip does
not take it. The chip is exercised by chip_smoke.py, not by this suite.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import random

import pytest


@pytest.fixture
def rng():
    return random.Random(0xC0FFEE)
