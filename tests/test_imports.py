"""Import smoke: every ``tpu_compressed_dp`` submodule must import cleanly.

The seed's single bad ``from jax import shard_map`` surfaced as 20 opaque
pytest collection errors (every test module transitively importing
``train/step.py``).  This file turns the next such regression into one
named failure in seconds: each submodule gets its own test, collected FIRST
in the tier-1 run (``conftest.pytest_collection_modifyitems`` orders the
``imports_smoke`` marker to the front), so the broken import is the first
line of output instead of noise spread over the whole suite.
"""

import importlib
import pkgutil

import pytest

import tpu_compressed_dp


def _submodules():
    names = ["tpu_compressed_dp"]
    for mod in pkgutil.walk_packages(tpu_compressed_dp.__path__,
                                     prefix="tpu_compressed_dp."):
        names.append(mod.name)
    # native holds only the C++ source (no python module); everything else
    # must import
    return [n for n in sorted(set(names)) if not n.endswith(".native")]


@pytest.mark.quick
@pytest.mark.imports_smoke
@pytest.mark.parametrize("module", _submodules())
def test_submodule_imports(module):
    importlib.import_module(module)


def _tool_modules():
    import os
    tools_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    return sorted(f"tools.{f[:-3]}" for f in os.listdir(tools_dir)
                  if f.endswith(".py"))


@pytest.mark.quick
@pytest.mark.imports_smoke
@pytest.mark.parametrize("module", _tool_modules())
def test_tool_imports_side_effect_free(module):
    """Every tool must import without mutating process state (os.environ,
    sys.path, jax platform config) and expose a ``main`` entry point —
    the contract that lets tcdp-lint, the test suite, and other tools
    import them for their helpers without surprise reconfiguration."""
    import os
    import sys

    env_before = dict(os.environ)
    path_before = list(sys.path)
    mod = importlib.import_module(module)
    assert dict(os.environ) == env_before, "import mutated os.environ"
    assert list(sys.path) == path_before, "import mutated sys.path"
    assert callable(getattr(mod, "main", None)), f"{module} has no main()"


@pytest.mark.quick
@pytest.mark.imports_smoke
def test_public_surface():
    # the stateful-compressor entry points must be reachable from their
    # canonical homes
    from tpu_compressed_dp.ops.compressors import REGISTRY, get_compressor
    from tpu_compressed_dp.parallel.dp import init_comp_state  # noqa: F401

    assert "powersgd" in REGISTRY
    assert get_compressor("powersgd").is_stateful
