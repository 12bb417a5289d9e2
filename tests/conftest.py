"""Test fixture: an 8-device virtual CPU mesh (SURVEY.md §4).

Environment must be set before the first `import jax` anywhere in the test
process, hence module scope here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in _flags:
    # tests are compile-time dominated on the CPU backend; O0 keeps XLA
    # semantics while cutting suite wall time ~2.5x (VERDICT r1 weak #5)
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402
import pytest  # noqa: E402

# a pytest plugin may have imported jax before this module set the
# environment; the config route works until first backend use
jax.config.update("jax_platforms", "cpu")
# entry points the tests call in-process place the persistent compile cache
# (parallel.mesh.setup_compile_cache); the suite itself neither reads nor
# fills it
jax.config.update("jax_enable_compilation_cache", False)


def pytest_collection_modifyitems(config, items):
    """Run the ``imports_smoke`` tests first: a broken import then fails in
    seconds as one named test instead of as 20 opaque collection errors at
    the end of the run."""
    items.sort(key=lambda it: 0 if it.get_closest_marker("imports_smoke")
               else 1)


@pytest.fixture(scope="session")
def mesh8():
    from tpu_compressed_dp.parallel.mesh import make_data_mesh

    assert len(jax.devices()) >= 8, "expected 8 virtual CPU devices"
    return make_data_mesh(8)


@pytest.fixture
def loopback_exclusive(tmp_path_factory):
    """Held by every test that runs a multi-process job over the machine's
    loopback interface or reads that interface's byte counters
    (`tools/validate_transport.py` reads `/proc/net/dev`, which counts the
    whole machine's traffic): a file lock shared by the xdist workers, so
    that no two such tests overlap whichever workers they land on."""
    import fcntl

    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent      # each worker's base is a child of the run's
    with open(base / "loopback.lock", "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        yield
