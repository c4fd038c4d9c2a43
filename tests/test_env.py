"""repro.env: the centralized XLA/JAX measurement-environment knobs."""
import os
import warnings

import pytest

from repro import env

_KEYS = ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_ENABLE_X64")


@pytest.fixture(autouse=True)
def _restore_environment():
    import jax
    saved = {k: os.environ.get(k) for k in _KEYS}
    saved_x64 = bool(jax.config.read("jax_enable_x64"))
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    jax.config.update("jax_enable_x64", saved_x64)


def _force_jax_init():
    import jax
    jax.devices()


class TestKnobs:
    def test_host_device_count_merges_into_existing_flags(self):
        _force_jax_init()
        os.environ["XLA_FLAGS"] = \
            "--foo=1 --xla_force_host_platform_device_count=4"
        with pytest.warns(RuntimeWarning, match="after jax initialized"):
            env.set_host_device_count(8)
        flags = os.environ["XLA_FLAGS"]
        assert "--foo=1" in flags                      # preserved
        assert "--xla_force_host_platform_device_count=8" in flags
        assert "device_count=4" not in flags           # replaced, not stacked

    def test_set_platform_sets_env_and_warns_when_late(self):
        _force_jax_init()
        with pytest.warns(RuntimeWarning, match="not take effect"):
            env.set_platform("cpu")
        assert os.environ["JAX_PLATFORMS"] == "cpu"

    def test_enable_x64_toggles_live_jax_config(self):
        import jax
        env.enable_x64(True)
        assert os.environ["JAX_ENABLE_X64"] == "1"
        assert jax.config.read("jax_enable_x64") is True
        env.enable_x64(False)
        assert os.environ["JAX_ENABLE_X64"] == "0"
        assert jax.config.read("jax_enable_x64") is False

    def test_jax_initialized_detection(self):
        _force_jax_init()
        assert env._jax_initialized() is True


class TestBenchmarkPinning:
    def test_configure_applies_all_knobs(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            env.configure(platform="cpu", x64=False, host_devices=2)
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert os.environ["JAX_ENABLE_X64"] == "0"
        assert "--xla_force_host_platform_device_count=2" in \
            os.environ["XLA_FLAGS"]

    def test_pin_for_benchmarks_pins_and_describes(self):
        import jax
        os.environ.pop("JAX_PLATFORMS", None)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            d = env.pin_for_benchmarks()
        # no platform is chosen for the caller: jax's own pick is recorded
        assert "JAX_PLATFORMS" not in os.environ
        assert d["x64"] is False
        assert d["jax_platform"] == jax.default_backend()
        assert d["device_kind"] == jax.devices()[0].device_kind
        assert d["device_count"] >= 1
        assert d["jax_version"]

    def test_pin_keeps_caller_exported_platform(self):
        os.environ["JAX_PLATFORMS"] = "cpu"
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            d = env.pin_for_benchmarks()
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert "xla_flags" in d


class TestCompileCache:
    """The helper's choice of directory, observed through jax.config
    updates it makes (stubbed, so no test turns the cache on)."""

    @pytest.fixture
    def updates(self, monkeypatch):
        import jax
        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_exported_dir_is_used_and_nothing_else_set(self, monkeypatch,
                                                       updates, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert env.enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates

    def test_default_is_fixed_dir_in_checkout(self, monkeypatch, updates):
        import pathlib
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = env.enable_compile_cache()
        root = pathlib.Path(env.__file__).resolve().parents[2]
        assert path == str(root / ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path
        assert env.enable_compile_cache() == path      # stable across calls
