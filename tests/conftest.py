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

import pytest  # noqa: E402


@pytest.fixture
def sandbox(tmp_path):
    """Temp working dir, the analog of the reference's `tf-sandbox` fixture
    (SharedSparkSessionSuite.scala:29-43)."""
    d = tmp_path / "tf-sandbox"
    d.mkdir()
    return d
