"""Test bootstrap: make ``src/`` and the tests dir importable regardless of
how pytest was invoked (``PYTHONPATH=src`` stays the documented tier-1
command, but plain ``python -m pytest`` must work too)."""

import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "src")

for p in (_HERE, _SRC):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def mesh8():
    """An 8-way ("model",) mesh when the process actually has 8+ devices.

    XLA's host-device count is fixed before ``import jax`` (the CI ``mesh``
    leg exports ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
    in a plain single-device run the in-process mesh tests skip and the
    subprocess-based parity tests cover the shard_map path instead."""
    import jax

    from repro.launch.mesh import make_mesh

    if jax.device_count() < 8:
        pytest.skip("needs 8 devices (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8 before jax "
                    "imports — the scripts/ci.sh mesh leg does)")
    return make_mesh((8,), ("model",))
