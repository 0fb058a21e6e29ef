"""Test harness: force an 8-device virtual CPU platform.

This is the TPU-world analog of a fake distributed backend (SURVEY.md §4): multi-chip SPMD
logic (mesh construction, batch sharding, the fused gradient all-reduce, ppermute rings) runs
and is verified on 8 virtual CPU devices, no TPU pod required.
"""

import os

# Opt-in hardware mode: ``FRAMEWORK_TEST_PLATFORM=tpu pytest tests/test_pallas*.py
# tests/test_paged_attention.py`` leaves the real backend alone so the TPU-gated
# Mosaic compile paths actually run on a chip. Default is the 8-virtual-device CPU
# platform.
_platform = os.environ.get("FRAMEWORK_TEST_PLATFORM", "cpu").strip().lower()
if _platform not in ("cpu", "tpu"):
    # Fail fast: a typo here must not silently skip the CPU pin and put the whole
    # suite on the chip.
    raise RuntimeError(
        f"FRAMEWORK_TEST_PLATFORM must be 'cpu' or 'tpu', got {_platform!r}")

if _platform == "cpu":
    # Exported (not just set through jax.config) so subprocess-based tests —
    # launchers, replicas, CLI benches — inherit the same platform.
    os.environ["JAX_PLATFORMS"] = "cpu"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if _platform == "cpu":
    # Also through jax.config: a pytest plugin may have imported jax (and read
    # the environment) before this file ran.
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
