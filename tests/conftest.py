"""Test configuration: an 8-device virtual CPU mesh, Pallas interpreted."""

import os
import sys

# the multi-device tests need a mesh; the flag must precede the first
# backend touch
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# tests run on the CPU backend even on a machine that has a chip
jax.config.update("jax_platforms", "cpu")

from quiver_tpu.ops.pallas import set_interpret  # noqa: E402

# the CPU backend cannot compile Pallas TPU kernels: this is the one place
# that runs them interpreted
set_interpret(True)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_info_once():
    """Each test starts with a clean ``info_once`` memory — otherwise
    one-shot log state leaks across tests in the same process and
    log-assertion tests become order-dependent."""
    from quiver_tpu.utils.trace import reset_once

    reset_once()
    yield
