"""Accelerator selection (reference: accelerator/real_accelerator.py
`get_accelerator` :51 — env var `DS_ACCELERATOR` override, else
auto-detect)."""
from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import DeepSpeedAccelerator
from .tpu_accelerator import CPU_Accelerator, TPU_Accelerator

__all__ = ["get_accelerator", "set_accelerator", "is_current_accelerator_supported"]

_accelerator: Optional[DeepSpeedAccelerator] = None

_BY_NAME = {"tpu": TPU_Accelerator, "cpu": CPU_Accelerator}


def set_accelerator(accel: DeepSpeedAccelerator) -> DeepSpeedAccelerator:
    global _accelerator
    _accelerator = accel
    return accel


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator
    name = os.environ.get("DSTPU_ACCELERATOR",
                          os.environ.get("DS_ACCELERATOR", ""))
    if name:
        if name not in _BY_NAME:
            raise ValueError(
                f"DS_ACCELERATOR={name!r} unsupported; one of {sorted(_BY_NAME)}")
        return set_accelerator(_BY_NAME[name]())
    # auto-detect from the live jax backend
    from ..utils.device import on_tpu
    cls = TPU_Accelerator if on_tpu() else CPU_Accelerator
    return set_accelerator(cls())


def is_current_accelerator_supported() -> bool:
    return get_accelerator()._name in _BY_NAME
