"""The accelerator a process runs on, and where its compiled programs go.

* :func:`expected_platform` says which platform a process must get, decided
  without initialising JAX: ``JAX_PLATFORMS`` when it is set, else ``tpu``
  when TPU chips are attached to this machine.  :func:`require_platform`
  then checks what JAX actually initialised.  JAX falls back to the CPU
  in silence when another process holds the chip (a chip belongs to one
  process at a time), so a process that expects the chip checks.
* :func:`enable_compile_cache` turns on JAX's persistent compilation cache
  for the entry points (``serve``, ``chip_smoke.py``, the benchmarks) —
  never for tests.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

# the checkout this module lives in: src/repro/launch/device.py -> root
CHECKOUT = Path(__file__).resolve().parents[3]
COMPILE_CACHE_DIR = CHECKOUT / ".jax_cache"
GOOGLE_PCI_VENDOR = "0x1ae0"


def _google_vfio_group(root: Path, group: str) -> bool:
    """Whether VFIO group ``group`` holds a PCI device of Google's."""
    devices = root / "sys" / "kernel" / "iommu_groups" / group / "devices"
    for vendor in devices.glob("*/vendor"):
        try:
            if vendor.read_text().strip() == GOOGLE_PCI_VENDOR:
                return True
        except OSError:
            pass
    return False


def attached_tpu_chips(root: str = "/") -> int:
    """TPU chips this machine exposes: device nodes ``/dev/accel*``, or on
    newer TPU VMs the ``/dev/vfio/<group>`` nodes whose IOMMU group holds a
    PCI device with Google's vendor id (any other VFIO passthrough device
    is no TPU).  0 on a machine without TPUs."""
    r = Path(root)
    accel = list(r.glob("dev/accel[0-9]*"))
    if accel:
        return len(accel)
    return sum(_google_vfio_group(r, node.name)
               for node in r.glob("dev/vfio/[0-9]*"))


def expected_platform(environ: Optional[Mapping[str, str]] = None
                      ) -> Optional[str]:
    """The platform a JAX process started with ``environ`` must get: the
    first entry of ``JAX_PLATFORMS``, else ``"tpu"`` when chips are
    attached, else ``None`` (whatever JAX finds)."""
    env = os.environ if environ is None else environ
    names = [p.strip() for p in env.get("JAX_PLATFORMS", "").split(",")
             if p.strip()]
    if names:
        return names[0]
    return "tpu" if attached_tpu_chips() else None


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX initialised."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_platform(platform: Optional[str]) -> dict:
    """Raise unless JAX initialised on ``platform`` (no check for None);
    returns :func:`device_info`."""
    info = device_info()
    if platform is not None and info["platform"] != platform:
        raise RuntimeError(
            f"expected JAX on {platform!r} but it initialised on "
            f"{info['platform']!r} ({info['kind']}): this machine has no "
            f"{platform} device, or another process holds it (a chip "
            f"serves one process at a time)")
    return info


def enable_compile_cache() -> str:
    """Keep compiled programs across runs; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` in
    the checkout: a fixed path, because the path is part of the cache key.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
