"""chip_smoke.py off the chip: the gates that must hold before anything
compiles.  The phases themselves only run on a TPU (``python chip_smoke.py``
through the chip tool)."""

import os
import types

import jax
import pytest

import chip_smoke
from tpu_compressed_dp.parallel import mesh as mesh_mod

pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """setup_compile_cache writes jax's global config; put it back."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_main_refuses_cpu_before_any_compile(cache_config, capsys):
    compiles = []

    def on_event(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        rc = chip_smoke.main()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    out, err = capsys.readouterr()
    assert rc != 0
    assert "platform='cpu'" in err
    assert compiles == []
    assert '"ok"' not in out


def test_cache_dir_from_outside_is_left_alone(cache_config, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert mesh_mod.setup_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_the_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert mesh_mod.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_unknown_device_kind_fails_the_mfu_check():
    # the harness omits `mfu` for a chip utils/flops.py has no peak for
    unknown = types.SimpleNamespace(device_kind="TPU v99")
    with pytest.raises(chip_smoke.SmokeFailure, match="'TPU v99' is NOT in"):
        chip_smoke.check_mfu({"img/s": 1.0}, unknown)
    known = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert chip_smoke.check_mfu({"mfu": 0.4}, known) == 0.4
