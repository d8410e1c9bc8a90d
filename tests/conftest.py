"""Test harness: run everything on a virtual 8-device CPU mesh.

Env vars must be set before the first ``import jax`` anywhere in the test
process (SURVEY.md §4: XLA CPU exposes multiple devices via
``--xla_force_host_platform_device_count``, which is how sharding logic is
tested without TPU hardware).
"""

import os

# Tests never need an accelerator: pin the CPU platform and its eight virtual
# devices before jax is first imported.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# Tests neither read nor write a persistent compilation cache: entry points
# under test call place_compile_cache(), which would otherwise switch one on
# for the rest of the process at whatever test first reached it.
jax.config.update("jax_enable_compilation_cache", False)

import pathlib

import pytest

REFERENCE_ROOT = pathlib.Path("/root/reference")


@pytest.fixture(scope="session")
def reference_phase1_results():
    """The reference's committed phase-1 results JSON — the golden record for
    metric-parity tests. Skips when the reference tree isn't mounted."""
    path = REFERENCE_ROOT / "results" / "phase1" / "phase1_results.json"
    if not path.exists():
        pytest.skip("reference results not available")
    import json

    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def eight_device_mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from fairness_llm_tpu.config import MeshConfig
    from fairness_llm_tpu.parallel import make_mesh

    return make_mesh(MeshConfig(dp=2, tp=4, sp=1))
