"""Test harness: force an 8-device virtual CPU platform so multi-chip
sharding paths (mesh/pjit/shard_map/all_to_all) are exercised without TPUs.
Mirrors the reference's strategy of testing its distributed PS
single-process multi-device (SURVEY.md §4, heter_ps/test_comm.cu).
``JAX_PLATFORMS=cpu`` plus the ``XLA_FLAGS`` device count, set before
jax is imported, is all it takes."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")


def pytest_configure(config):
    # chaos: seeded fault-injection recovery tests (tests/test_resilience,
    # scripts/chaos_check). Fast ones run in tier-1; long soak variants
    # carry `slow` as well and stay out of the default selection.
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection / recovery tests (resilience)")
