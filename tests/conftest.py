"""Test fixtures.

Mirrors the reference's two-tier strategy (SURVEY.md §4): Tier 1 tests are
pure codec tests with no devices; Tier 2 tests fake a TPU pod with an
8-device CPU mesh (`--xla_force_host_platform_device_count=8`), the analog of
the reference's in-process Spark local mode (SharedSparkSessionSuite.scala).
"""

import os
import sys

# Must be set before the CPU backend is CREATED (not merely before jax is
# imported — a sitecustomize may import jax at interpreter start). Backends
# initialize lazily, so forcing the platform through jax.config still works
# here. The suite runs on 8 virtual CPU devices; the persistent compile
# cache stays off (tpu_tfrecord.compile_cache is for entry points).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import signal  # noqa: E402
import traceback  # noqa: E402

import pytest  # noqa: E402

#: Seconds one test may run (the set-up of module fixtures apart). The slowest
#: case (tests/test_tpu_compile.py's largest compile) takes 57 s of it beside
#: five busy workers on eight cores, so about 230 s on a machine four times
#: slower, as the driver's is: a test that waits for ever costs its own
#: failure, not the run's limit, and a slow machine fails no test by it.
TEST_LIMIT_S = 450.0


@pytest.fixture(autouse=True)
def _test_limit(request):
    """Fail a test that runs past ``TEST_LIMIT_S`` with its name and the
    stack it was stopped in; the run goes on. An interval timer on the main
    thread, which is where pytest (and an xdist worker) runs a test."""

    def stop(signum, frame):
        pytest.fail(
            f"{request.node.nodeid} ran past its limit of {TEST_LIMIT_S:g} s, at:\n"
            + "".join(traceback.format_stack(frame)), pytrace=False)

    was = signal.signal(signal.SIGALRM, stop)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)


#: Files whose tests start first. Under xdist's ``--dist loadfile`` a file
#: goes whole to one worker in the order of collection, which is the alphabet,
#: and these long ones stand at its end: the run would close with one worker
#: on each and the others idle. (tests/test_tpu_compile.py cannot be cut in
#: two instead: one process at a time holds libtpu.)
STARTED_FIRST = ("test_tpu_compile.py", "test_pattern_lm.py", "test_train_telemetry.py")


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: item.path.name not in STARTED_FIRST)  # stable: the alphabet within


@pytest.fixture
def sandbox(tmp_path):
    """Temp working dir, the analog of the reference's `tf-sandbox` fixture
    (SharedSparkSessionSuite.scala:29-43)."""
    d = tmp_path / "tf-sandbox"
    d.mkdir()
    return d
