"""CPU-only: JAX on the host's CPU with four virtual devices, set before
JAX is imported, and the checkout on the import path."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

# the benchmark keeps JAX's persistent cache in the checkout; CPU test
# programs go to a directory of their own that is removed at exit
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_CACHE = tempfile.mkdtemp(prefix="bench_tests_jax_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE
atexit.register(shutil.rmtree, _CACHE, ignore_errors=True)
