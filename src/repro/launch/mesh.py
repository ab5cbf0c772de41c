"""Devices, meshes, peak rates and the compile cache.

Production meshes target TPU v5e pods: one pod is 256 chips as
(data=16, model=16); two pods are 512 chips as (pod=2, data=16,
model=16), where the "pod" axis crosses DCN -- the contended
inter-server path of the paper's model.

Every mesh here has ``AxisType.Auto`` axes, so the in-model
``shard_hint`` constraints act as hints XLA reshards to.  The functions
never run at import, so importing this module touches no device state.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.sharding import AxisType

#: Per-chip peak rates keyed by ``jax.Device.device_kind``.  TPU v5e
#: ("TPU v5 lite"), Google Cloud documentation "TPU v5e": 197 TFLOP/s
#: bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip (4 ICI links x 50 GB/s);
#: DCN: 4 x 100 Gbit/s NICs per 8-chip host = 6.25 GB/s per chip.
PEAK_RATES: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "ici_bw": 50e9, "dcn_bw": 6.25e9},
}

#: The chip the production meshes (and the dry-run's roofline) target.
TARGET_KIND = "TPU v5 lite"

#: Fallback compile-cache directory: one fixed path inside the checkout.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def peak_rates(device_kind: str) -> dict[str, float]:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``."""
    try:
        return PEAK_RATES[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAK_RATES)}") from None


def use_compile_cache(n_devices: int = 1) -> str:
    """Set up JAX's persistent compilation cache for a process whose
    programs run on ``n_devices`` devices; returns its path, or "off".

    A process on more than one device caches nothing.  On a TPU v5e 2x2
    host, a two-chip program read back from a persistent cache written
    on another host of the same topology halted a core ("Invalid logical
    z: enhanced-barrier"); the same program compiled anew ran.  That was
    seen once and not reproduced, so the cache stays off for every
    multi-device process until the cause is known.

    Otherwise, where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing else is set; else the cache lives at
    :data:`COMPILE_CACHE_DIR`, a fixed path (the path is part of the cache
    key, so it never comes from a temp name, a pid or the time)."""
    if n_devices > 1:
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        return "off"
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def request_cpu_devices(n: int) -> None:
    """Run on ``n`` virtual CPU devices.  Call before JAX's first device
    use (XLA reads the flag when its backends start)."""
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()
    jax.config.update("jax_platforms", "cpu")


def take_devices(n: int) -> list:
    """The first ``n`` devices present (all of them for ``n == 0``); an
    error when fewer exist."""
    devices = jax.devices()
    if n > len(devices):
        raise SystemExit(
            f"asked for {n} devices, but only {len(devices)} "
            f"{devices[0].platform} device(s) are present")
    return devices[:n] if n else devices


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """A mesh with ``AxisType.Auto`` axes over ``devices`` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
