"""The device this process runs on: one question, one answer.

Every kernel gate, the accelerator auto-detect and the bench scripts ask
`platform()`; nothing else in the package probes the backend.  Exceptions
from `jax.devices()` propagate: a process that cannot reach its device
must fail there, not run a slower path that looks plausible.

Also here, because they are decided once per process the same way: the
peak-rate table (keyed by `device_kind`, an unknown kind is an error) and
the persistent compile cache's location.
"""
from __future__ import annotations

import os

import jax

__all__ = ["platform", "on_tpu", "device_peaks", "place_compile_cache"]


def platform() -> str:
    """Platform name of the first device JAX reports ("tpu", "cpu", ...)."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


# Published per-chip peaks.  Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def device_peaks(kind: str | None = None) -> dict:
    """Peak rates of `kind` (default: the attached device's `device_kind`).
    A device that is not in the table is an error, never a default."""
    kind = kind if kind is not None else jax.devices()[0].device_kind
    if kind not in _PEAKS:
        raise KeyError(
            f"no published peak rates for device kind {kind!r} "
            f"(known: {sorted(_PEAKS)}); add it to "
            f"deepspeed_tpu.utils.device._PEAKS with its source")
    return dict(_PEAKS[kind])


def place_compile_cache(min_compile_secs: float = 0.0) -> str:
    """Point JAX's persistent compilation cache somewhere it can be found
    again.  Where `JAX_COMPILATION_CACHE_DIR` is set JAX already reads it:
    do nothing and set no directory in code.  Otherwise
    `<checkout>/.cache/xla`: fixed, derived from the package's location
    (the directory is part of the cache key — a path that moves never
    hits).  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".cache", "xla")
    jax.config.update("jax_compilation_cache_dir", path)
    # default floor 0: JAX's own default skips anything that compiled in
    # under a second, which is most of the serving programs' buckets
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
