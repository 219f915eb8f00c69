"""TPU guard of `chip_smoke.py`, its one caller: a path that exists to run
on the chip fails where it finds none — a CPU run would look plausible and
prove nothing.  (The benchmark refuses the same way, on its own:
`benchmark/harness.py`.)"""
from __future__ import annotations

from . import device

__all__ = ["require_tpu"]


def require_tpu() -> None:
    found = device.platform()
    if found != "tpu":
        raise RuntimeError(
            f"this entry point measures a TPU and found platform "
            f"{found!r}; it does not run elsewhere")
