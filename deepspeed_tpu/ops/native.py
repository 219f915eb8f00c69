"""ctypes loader + Python API for the native host ops (csrc/host_ops.cpp).

Plays the role of the reference's op_builder JIT-build machinery
(op_builder/builder.py:116 `OpBuilder.load`->`jit_load`:540): the shared
library is compiled with g++ on first use into `<checkout>/.cache/native/`,
keyed on a hash of the source, so a changed source builds a new library.

Python surface:
- `adam_step/adagrad_step/lion_step` over numpy fp32 arrays (offloaded
  optimizer states — the CPUAdam analog).
- `AsyncIOHandle` — pread/pwrite with async submit + wait (the `aio` op).
- bf16<->fp32 conversion for offloaded param mirrors.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

__all__ = ["lib", "adam_step", "adagrad_step", "lion_step",
           "bf16_to_fp32", "fp32_to_bf16", "AsyncIOHandle", "build"]

_SRC = os.path.join(os.path.dirname(__file__), "..", "csrc", "host_ops.cpp")
_FLAGS = ["-O3", "-march=native", "-shared", "-fPIC", "-std=c++17", "-pthread"]
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _so_path() -> str:
    """`<checkout>/.cache/native/libdstpu_host-<hash>.so`: a fixed place
    inside the checkout that git ignores, keyed on the source and the
    flags — never on mtimes, which a copy of the tree makes arbitrary."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(_FLAGS).encode())
    checkout = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                            "..", ".."))
    return os.path.join(checkout, ".cache", "native",
                        f"libdstpu_host-{digest.hexdigest()[:16]}.so")


def build(force: bool = False) -> str:
    """Compile the native library from csrc/host_ops.cpp (g++) unless the
    library for exactly this source is already there.  No compiler is an
    error for whoever needs the library (offload, aio) — never a reason to
    load some other binary."""
    so = _so_path()
    if force or not os.path.exists(so):
        os.makedirs(os.path.dirname(so), exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"     # concurrent builders race safely
        subprocess.run(["g++", *_FLAGS, os.path.abspath(_SRC), "-o", tmp],
                       check=True, capture_output=True)
        os.replace(tmp, so)
    return so


def _load() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            L = ctypes.CDLL(build())
            i64, f32 = ctypes.c_int64, ctypes.c_float
            pf = ctypes.POINTER(ctypes.c_float)
            pu16 = ctypes.POINTER(ctypes.c_uint16)
            L.dstpu_adam_step.argtypes = [pf, pf, pf, pf, i64, f32, f32, f32,
                                          f32, f32, ctypes.c_int, ctypes.c_int]
            L.dstpu_adagrad_step.argtypes = [pf, pf, pf, i64, f32, f32, f32]
            L.dstpu_lion_step.argtypes = [pf, pf, pf, i64, f32, f32, f32, f32]
            L.dstpu_bf16_to_fp32.argtypes = [pu16, pf, i64]
            L.dstpu_fp32_to_bf16.argtypes = [pf, pu16, i64]
            L.dstpu_aio_new_handle.restype = ctypes.c_void_p
            L.dstpu_aio_free_handle.argtypes = [ctypes.c_void_p]
            L.dstpu_aio_pwrite.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           ctypes.c_void_p, i64, i64]
            L.dstpu_aio_pread.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_void_p, i64, i64]
            L.dstpu_aio_wait.argtypes = [ctypes.c_void_p]
            L.dstpu_aio_wait.restype = ctypes.c_int
            L.dstpu_aio_pending.argtypes = [ctypes.c_void_p]
            L.dstpu_aio_pending.restype = ctypes.c_int
            L.dstpu_aio_bytes_done.argtypes = [ctypes.c_void_p]
            L.dstpu_aio_bytes_done.restype = i64
            _lib = L
    return _lib


class _LazyLib:
    def __getattr__(self, name):
        return getattr(_load(), name)


lib = _LazyLib()


def _fp(a: np.ndarray):
    assert a.dtype == np.float32 and a.flags["C_CONTIGUOUS"]
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def adam_step(param, m, v, grad, lr, beta1=0.9, beta2=0.999, eps=1e-8,
              weight_decay=0.0, adam_w=True, step=1):
    """In-place Adam on host fp32 arrays (CPUAdam analog)."""
    _load().dstpu_adam_step(_fp(param), _fp(m), _fp(v), _fp(grad), param.size,
                            lr, beta1, beta2, eps, weight_decay,
                            int(adam_w), int(step))


def adagrad_step(param, acc, grad, lr, eps=1e-8, weight_decay=0.0):
    _load().dstpu_adagrad_step(_fp(param), _fp(acc), _fp(grad), param.size,
                               lr, eps, weight_decay)


def lion_step(param, m, grad, lr, beta1=0.9, beta2=0.99, weight_decay=0.0):
    _load().dstpu_lion_step(_fp(param), _fp(m), _fp(grad), param.size,
                            lr, beta1, beta2, weight_decay)


def bf16_to_fp32(src: np.ndarray) -> np.ndarray:
    assert src.dtype == np.uint16
    out = np.empty(src.shape, np.float32)
    _load().dstpu_bf16_to_fp32(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)), _fp(out), src.size)
    return out


def fp32_to_bf16(src: np.ndarray) -> np.ndarray:
    out = np.empty(src.shape, np.uint16)
    _load().dstpu_fp32_to_bf16(
        _fp(np.ascontiguousarray(src, np.float32)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)), src.size)
    return out


class AsyncIOHandle:
    """Async tensor<->file transfers (reference: deepspeed_py_io_handle.cpp
    pread/pwrite sync+async API surface)."""

    def __init__(self):
        self._h = _load().dstpu_aio_new_handle()
        self._keepalive = []  # buffers pinned until wait()

    def pwrite(self, path: str, arr: np.ndarray, offset: int = 0):
        arr = np.ascontiguousarray(arr)
        self._keepalive.append(arr)
        _load().dstpu_aio_pwrite(self._h, path.encode(), arr.ctypes.data,
                                 arr.nbytes, offset)

    def pread(self, path: str, arr: np.ndarray, offset: int = 0):
        assert arr.flags["C_CONTIGUOUS"] and arr.flags["WRITEABLE"]
        self._keepalive.append(arr)
        _load().dstpu_aio_pread(self._h, path.encode(), arr.ctypes.data,
                                arr.nbytes, offset)

    def wait(self) -> int:
        """Block until all submitted ops finish; returns the error count for
        this submission batch (handle counters reset, so it is reusable)."""
        errs = _load().dstpu_aio_wait(self._h)
        self._keepalive.clear()
        return errs

    @property
    def pending(self) -> int:
        return _load().dstpu_aio_pending(self._h)

    @property
    def bytes_done(self) -> int:
        return _load().dstpu_aio_bytes_done(self._h)

    def __del__(self):
        try:
            if self.pending:
                self.wait()
            _load().dstpu_aio_free_handle(self._h)
        except Exception:
            pass
