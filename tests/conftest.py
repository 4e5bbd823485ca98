"""Test config: run on a simulated 8-device CPU mesh so every parallelism
test (dp/tp/ep/pp/cp) executes real XLA collectives without TPU hardware
(SURVEY.md §4 — replaces the reference's mpirun-based distributed tests).

The suite is CPU-only by contract: it must give the same count on a
machine with a chip as on one without, and several xdist workers cannot
share one chip.  So the platform is forced here whatever ``JAX_PLATFORMS``
the caller had — the env var for child processes the tests spawn, the
config update for this process.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the suite compiles thousands of throwaway programs: keep them out of the
# checkout's persistent compile cache (configure_compile_cache only names
# the directory; this switch decides whether jax uses it)
jax.config.update("jax_enable_compilation_cache", False)
# newer jax defaults this ON; the parity tests (single-device vs sharded
# with dropout RNG inside shard_map) assume sharding-invariant random
# bits, which is exactly what the partitionable threefry gives
jax.config.update("jax_threefry_partitionable", True)


def pytest_configure(config):
    # pytest-timeout is not installed on this image; the mark is registered
    # as DOCUMENTATION of each test's budget (silences unknown-mark
    # warnings).  The real hang protection in the multiprocess tests is
    # their explicit subprocess deadlines (communicate(timeout=...) against
    # a shared monotonic deadline + kill() in finally).
    config.addinivalue_line(
        "markers",
        "timeout(seconds): intended wall-clock budget; enforced by the "
        "tests' own subprocess deadlines, not by a pytest plugin")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 budgeted run (-m 'not slow'); "
        "the full unfiltered suite still runs these — heavyweight "
        "end-to-end/interpret-mode parity tests whose core coverage a "
        "cheaper sibling already provides, plus multiprocess launcher "
        "tests that need more CPU than the 1.5-core CI box offers")


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _counters_start_at_zero():
    """The program's counters are process-wide and its ``_hw`` gauges keep
    the largest value seen: a file that reads a window's state BY KIND
    (``decode_state_bytes_<kind>_hw``) would see the kinds of whatever file
    its xdist worker ran before it — a ``ring`` in a cell that has none.
    Which files share a worker moves with every file a PR adds."""
    from hetu_tpu import metrics
    metrics.reset_all()
