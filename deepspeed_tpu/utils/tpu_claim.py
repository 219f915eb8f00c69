"""TPU guard for the benchmark drivers and `chip_smoke.py`: a measurement
path that finds no chip fails — a CPU run would print a plausible-looking
but wrong metric."""
from __future__ import annotations

from . import device

__all__ = ["require_tpu"]


def require_tpu() -> None:
    found = device.platform()
    if found != "tpu":
        raise RuntimeError(
            f"this entry point measures a TPU and found platform "
            f"{found!r}; it does not run elsewhere")
