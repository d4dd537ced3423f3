"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding
(`shard_map` over the node axis) is exercised without TPU hardware;
`chip_smoke.py` on the four-chip host validates the real multi-chip
path. The CPU is forced here whatever the caller's environment says:
a test run must never take the chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Collector processes (logmon) normally OUTLIVE the agent so a
# restarted agent can reattach; a test suite spawning hundreds of
# short-lived agents must not leak hundreds of pollers (a past round's
# benchmarks degraded under exactly that load). With this set, a
# collector also exits once its spawning agent is gone.
os.environ["NOMAD_TPU_LOGMON_ORPHAN_EXIT"] = "1"
# Server.start() tunes the interpreter's cyclic GC for long-running
# processes (deferred full passes). A suite starting hundreds of
# short-lived servers in ONE process must keep normal GC behavior or
# cyclic garbage accumulates across tests.
os.environ["NOMAD_TPU_GC_TUNING"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier conventions (ROADMAP.md tier-1 runs `-m 'not slow'`):
    #   slow   -- excluded from tier-1
    #   stress -- the contention-repetition tier (`pytest -m stress`,
    #             N-rerun loops over broker/coalescer/membership
    #             contention); stress tests are ALSO marked slow so
    #             tier-1 never pays for repetition
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "stress: contention-repetition tier (pytest -m stress); "
        "always paired with slow")
    # buffer-donation misalignment is silent perf debt (XLA ignores the
    # donation and warns); promote it to an error so a donate_argnums
    # edit that can't alias its outputs fails the suite instead of
    # regressing quietly (ISSUE 2 satellite)
    config.addinivalue_line(
        "filterwarnings",
        "error:Some donated buffers were not usable")
