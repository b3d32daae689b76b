import os

# Force a virtual 8-device CPU mesh so multi-chip sharding paths are
# exercised without TPU hardware (the driver's dryrun does the same).
# XLA_FLAGS must be set before the CPU backend initializes.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Unit tests run on the host CPU (the eight virtual devices above), also
# on a machine that has an accelerator.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: the full chaos/replication suites
    # (multi-process supervised kills, long closed-loop load) carry the
    # marker; fast smokes of the same machinery stay in tier-1
    config.addinivalue_line(
        "markers",
        "slow: heavy multi-process chaos/replication suites excluded "
        "from the tier-1 `-m 'not slow'` run",
    )


@pytest.fixture(autouse=True)
def _clear_parse_graph():
    from pathway_tpu.internals import parse_graph
    from pathway_tpu.internals.errors import clear_errors

    parse_graph.G.clear()
    clear_errors()
    yield
    parse_graph.G.clear()
    clear_errors()
