"""The launchers' device helpers and compile-cache placement
(``repro.launch.mesh``)."""
import pytest

jax = pytest.importorskip("jax")

from repro.launch import mesh  # noqa: E402


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache settings after the test."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = (jax.config.jax_enable_compilation_cache,
             jax.config.jax_compilation_cache_dir)
    yield
    jax.config.update("jax_enable_compilation_cache", saved[0])
    jax.config.update("jax_compilation_cache_dir", saved[1])
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n_devices", [2, 4])
def test_compile_cache_off_on_several_devices(cache_config, monkeypatch,
                                              tmp_path, n_devices):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert mesh.use_compile_cache(n_devices) == "off"
    assert not jax.config.jax_enable_compilation_cache


def test_compile_cache_env_dir_is_left_to_jax(cache_config, monkeypatch,
                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert mesh.use_compile_cache(1) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_dir_in_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = mesh.use_compile_cache(1)
    assert path == str(mesh.COMPILE_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert mesh.COMPILE_CACHE_DIR.name == ".jax_cache"


def test_peak_rates_keyed_by_device_kind():
    assert mesh.peak_rates(mesh.TARGET_KIND)["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no peak rates"):
        mesh.peak_rates("no such chip")


def test_take_devices_refuses_more_than_present():
    present = len(jax.devices())
    assert len(mesh.take_devices(0)) == present
    with pytest.raises(SystemExit, match="only"):
        mesh.take_devices(present + 1)
