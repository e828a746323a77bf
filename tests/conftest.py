"""Test harness: an 8-device virtual CPU mesh replacing real TPU chips.

This is the framework's "fake backend" (SURVEY §4): tests exercise the real
SPMD train step, shardings and collectives on forced host devices, so the
same code compiles unchanged on a TPU pod.  The platform and the device
count are set here, before the first import of JAX.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# Persistent compilation cache: integration tests recompile identical SPMD
# programs across runs; on the single-core CI box that dominates wall time.
from trustworthy_dl_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return devices[:8]


@pytest.fixture(autouse=True)
def _reset_global_mode_meshes():
    """Trainers in 'sequence'/'expert' (and elastic rebuilds) bind global
    collectives meshes that would otherwise leak across tests — a test
    expecting the unbound state (ring fallback, dense-MLP equivalence)
    fails depending on execution order.  Reset BEFORE each test; bindings
    made within a test stay live for its own duration."""
    from trustworthy_dl_tpu.models.moe import set_expert_mesh
    from trustworthy_dl_tpu.parallel.sequence import set_sequence_mesh

    set_sequence_mesh(None)
    set_expert_mesh(None)
    yield
