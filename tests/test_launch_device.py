"""The launch-time device helpers: compile cache location, expected
platform, the platform check that stops a silent CPU fallback, and the
per-device peaks the roofline reads."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch.device import (
    enable_compile_cache,
    expected_platform,
    require_platform,
)


@pytest.fixture
def cache_dir_restored(monkeypatch):
    """Tests never leave the persistent cache on: restore the directory
    (and drop any cache object) before anything compiles."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


def _changed(before: dict) -> set:
    after = dict(jax.config.values)
    return {k for k in after if after[k] != before.get(k)}


def test_compile_cache_honours_env_var(monkeypatch, tmp_path,
                                       cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = dict(jax.config.values)
    assert enable_compile_cache() == str(tmp_path)
    assert _changed(before) == set()        # JAX's own reading stands


def test_compile_cache_falls_back_to_checkout(monkeypatch,
                                              cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    before = dict(jax.config.values)
    path = enable_compile_cache()
    checkout = Path(__file__).resolve().parents[1]
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert _changed(before) == {"jax_compilation_cache_dir"}


@pytest.mark.parametrize("env,want", [
    ({"JAX_PLATFORMS": "cpu"}, "cpu"),
    ({"JAX_PLATFORMS": "tpu,cpu"}, "tpu"),
    ({"JAX_PLATFORMS": " cpu "}, "cpu"),
])
def test_expected_platform_follows_jax_platforms(env, want):
    assert expected_platform(env) == want


def _node(path: Path, text: str = "") -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


@pytest.mark.parametrize("vendors,want", [
    ({"2": "0x1ae0"}, 1),                       # a TPU VM's chip
    ({"2": "0x1ae0", "5": "0x1ae0"}, 2),
    ({"7": "0x10de"}, 0),                       # a GPU passed through
    ({"2": "0x1ae0", "7": "0x10de"}, 1),
    ({"3": None}, 0),                           # group without sysfs entry
])
def test_attached_tpu_chips_counts_only_google_vfio_groups(tmp_path, vendors,
                                                           want):
    from repro.launch.device import attached_tpu_chips
    _node(tmp_path / "dev" / "vfio" / "vfio")   # the container node
    for group, vendor in vendors.items():
        _node(tmp_path / "dev" / "vfio" / group)
        if vendor is not None:
            _node(tmp_path / "sys" / "kernel" / "iommu_groups" / group
                  / "devices" / "0000:00:04.0" / "vendor", vendor + "\n")
    assert attached_tpu_chips(str(tmp_path)) == want


def test_attached_tpu_chips_counts_accel_nodes(tmp_path):
    from repro.launch.device import attached_tpu_chips
    for i in range(4):
        _node(tmp_path / "dev" / f"accel{i}")
    assert attached_tpu_chips(str(tmp_path)) == 4
    assert attached_tpu_chips(str(tmp_path / "empty")) == 0


def test_require_platform_refuses_a_fallback():
    here = jax.devices()[0].platform
    info = require_platform(here)
    assert info == {"platform": here, "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
    assert require_platform(None)["platform"] == here
    other = "tpu" if here != "tpu" else "cpu"
    with pytest.raises(RuntimeError, match="another process holds it"):
        require_platform(other)


def test_peaks_are_keyed_by_device_kind():
    from repro.roofline.analysis import peaks
    v5e = peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
