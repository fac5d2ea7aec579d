"""Test fixtures: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test harness shape (tests/meta_test.py in the
reference spawns a scheduler+server and forces distributed mode on one
machine); here the analog is XLA host-platform device virtualization —
8 CPU "chips" stand in for a TPU slice so every collective path is exercised
without hardware (SURVEY.md §4).
"""

import os

# Must run before the first JAX backend initialization.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Once the compression engine is wired, tests force compression regardless
# of tensor size, as the reference harness does (meta_test.py:31-33).  Until
# then this only exercises the Config parsing path.
os.environ.setdefault("BYTEPS_MIN_COMPRESS_BYTES", "0")

# Flight-recorder dumps default to a per-user temp dir (config.py
# _default_flight_dir); still route them to one session-scoped temp dir
# so parallel test sessions never see each other's dumps (tests that
# assert on dumps set BYTEPS_FLIGHT_DIR explicitly anyway).
if "BYTEPS_FLIGHT_DIR" not in os.environ:
    import tempfile

    os.environ["BYTEPS_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="bps_flight_test_")

# Same hygiene for trace flushes (Tracer defaults trace_dir to cwd): a
# test arming BYTEPS_TRACE_ON/TRACE_SAMPLE without an explicit dir must
# not shed bps_trace_rank*.json files into the repo root.
if "BYTEPS_TRACE_DIR" not in os.environ:
    import tempfile

    os.environ["BYTEPS_TRACE_DIR"] = tempfile.mkdtemp(
        prefix="bps_trace_test_")

# Durable state plane (server/wal.py): durability is strictly opt-in
# (durable_dir defaults to ""), so tests run WAL-free unless they arm it
# themselves.  But if the operator exported BYTEPS_DURABLE_DIR into the
# test session, re-point it at a temp dir — a test run must never replay
# or truncate a real deployment's journal.
if os.environ.get("BYTEPS_DURABLE_DIR"):
    import tempfile

    os.environ["BYTEPS_DURABLE_DIR"] = tempfile.mkdtemp(
        prefix="bps_durable_test_")

import jax  # noqa: E402

# The suite is CPU-only whether or not JAX_PLATFORMS=cpu is exported.
jax.config.update("jax_platforms", "cpu")
# The example scripts (run in-process by test_examples.py) turn on the
# persistent compile cache; the suite must neither read nor fill a cache
# directory, so the feature is off for the whole session.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    # registered here as well as in pyproject.toml so the marker exists
    # even under bare `pytest tests/` invocations with a stripped config
    # (tools/run_chaos.sh's integrity lane selects on it)
    config.addinivalue_line(
        "markers",
        "integrity: data-integrity envelope / dedup / quarantine tests "
        "(common/integrity.py wire paths)")


def free_port() -> int:
    """An OS-assigned free TCP port (shared by the multi-process and
    failure-detector tests)."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(autouse=True)
def _fresh_config():
    """Each test gets a config rebuilt from the current environment."""
    from byteps_tpu.common.config import reset_config
    reset_config()
    yield
    reset_config()


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Reset the process-wide observability singletons BETWEEN tests:
    the metrics registry (counters/gauges/histograms), the flight
    recorder's ring, and any leaked obs HTTP endpoint.  Without this,
    ``counters`` leaks across test files and every assertion on an
    absolute count is order-dependent (ISSUE 6 satellite)."""
    yield
    from byteps_tpu.common import flight_recorder as _flight
    from byteps_tpu.common import metrics as _metrics
    from byteps_tpu.common import obs_server as _obs
    from byteps_tpu.common import tracing as _btracing
    from byteps_tpu.common.telemetry import attribution as _attribution
    from byteps_tpu.utils import slowness as _slowness
    _obs.stop_server()
    # transport servers registered via comm.transport.serve() hold accept
    # threads and sockets; close any a test left behind (imported lazily:
    # most tests never touch the transport)
    import sys as _sys
    _transport = _sys.modules.get("byteps_tpu.comm.transport")
    if _transport is not None:
        _transport._reset_for_tests()
    _tier = _sys.modules.get("byteps_tpu.server.serving_tier")
    if _tier is not None:
        _tier._reset_for_tests()
    # the process-lifetime durable trainer store (server/wal.py) holds an
    # open journal file handle; close it so the next test's temp dir
    # starts cold
    _wal = _sys.modules.get("byteps_tpu.server.wal")
    if _wal is not None:
        _wal._reset_for_tests()
    _metrics.registry.reset()
    _metrics._reset_components_for_tests()
    _flight._reset_for_tests()
    _slowness._reset_for_tests()
    _btracing._reset_for_tests()
    _attribution.reset()
