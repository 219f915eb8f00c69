"""Accelerator abstraction (SURVEY L0).

Reference: `accelerator/abstract_accelerator.py` `DeepSpeedAccelerator` —
the ~100-method ABC every backend implements (device mgmt :35-59, RNG
:64-88, streams/events :94-111, memory :116-164, dtype support :169-182,
graphs :211-219, pinned memory :259-267, op builders :271-289,
`communication_backend_name` :202, `is_synchronized_device` :18).

TPU-first trimming: methods that only exist to paper over CUDA stream
semantics collapse to the synchronized-device contract the reference's CPU
accelerator already models (is_synchronized_device() -> True); graph
capture maps to `jax.jit`.  The surface kept here is everything the rest of
this framework (and user code following reference idioms) calls.

Contract map — what the reference's ~100 methods became (so a torch-xla or
new-backend shim knows exactly what to supply and what it may skip):

KEPT (abstract here): device_name/device/device_count/current_device(+name)
  · set_device · synchronize · manual_seed / random (RNG seam) ·
  memory_allocated / max_memory_allocated / memory_stats / empty_cache ·
  is_bf16_supported / is_fp16_supported / supported_dtypes ·
  communication_backend_name · is_synchronized_device · pin_memory ·
  is_available · op_builder_dir/create_op_builder (host-ops build seam).

COLLAPSED (non-abstract defaults, one behavior for all sync backends):
  - streams/events (Stream, Event, stream, current_stream, default_stream,
    wait_stream, record/elapsed — reference :94-111): no-ops; XLA owns
    scheduling.  is_synchronized_device() == True is the load-bearing bit
    the runtime checks, exactly like the reference's CPU accelerator.
  - graphs (create_graph/capture_to_graph/replay_graph :211-219): jit IS
    capture+replay; the seam survives as models' jitted callables.
  - per-stream memory pools (reset_peak_* variants :116-164): folded into
    memory_stats()/max_memory_allocated().

DROPPED (CUDA-/vendor-only, no TPU meaning — callers must not need them):
  - visible_devices_envs / set_visible_devices_envs (the launcher owns
    process-device mapping via JAX distributed init).
  - nvtx range_push/pop (utils/spans.py `span` annotates via jax.profiler).
  - LazyCall/TorchTensorOps passthroughs (torch-specific proxying).
  - handles_memory_backpressure, use_host_timers, resolves to fixed
    answers on XLA (False/True) and is read nowhere in this runtime.
If a future torch-xla shim needs a dropped method, add it HERE (abstract
or defaulted) rather than on the concrete class, so every backend keeps
one contract.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional

__all__ = ["DeepSpeedAccelerator"]


class DeepSpeedAccelerator(abc.ABC):
    _name: str = "abstract"
    _communication_backend_name: str = "xla"

    # -- identity -------------------------------------------------------
    @abc.abstractmethod
    def device_name(self, device_index: Optional[int] = None) -> str: ...

    @abc.abstractmethod
    def device(self, device_index: Optional[int] = None): ...

    @abc.abstractmethod
    def device_count(self) -> int: ...

    @abc.abstractmethod
    def current_device(self) -> int: ...

    def current_device_name(self) -> str:
        return self.device_name(self.current_device())

    def set_device(self, device_index: int) -> None:
        # SPMD: device placement is sharding-driven, not thread-local
        pass

    def is_available(self) -> bool:
        return self.device_count() > 0

    # -- execution model ------------------------------------------------
    def is_synchronized_device(self) -> bool:
        """True: no user-visible streams; ops complete in program order
        (reference: abstract_accelerator.py:18; the CPU accelerator is the
        template for this mode, and XLA follows it)."""
        return True

    def synchronize(self, device_index: Optional[int] = None) -> None:
        pass

    # -- RNG (reference :64-88) -----------------------------------------
    @abc.abstractmethod
    def manual_seed(self, seed: int) -> None: ...

    def manual_seed_all(self, seed: int) -> None:
        self.manual_seed(seed)

    @abc.abstractmethod
    def initial_seed(self) -> int: ...

    def default_generator(self, device_index: int):
        raise NotImplementedError(
            "stateful generators do not exist under JAX; thread PRNG keys")

    # -- streams/events: no-ops on synchronized devices (ref :94-111) ----
    def Stream(self, *args, **kwargs):
        return None

    def stream(self, stream):
        import contextlib
        return contextlib.nullcontext()

    def current_stream(self, device_index=None):
        return None

    def default_stream(self, device_index=None):
        return None

    def Event(self, **kwargs):
        return None

    # -- memory (reference :116-164) -------------------------------------
    @abc.abstractmethod
    def memory_stats(self, device_index: Optional[int] = None) -> Dict: ...

    def memory_allocated(self, device_index=None) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index=None) -> int:
        return int(self.memory_stats(device_index).get(
            "peak_bytes_in_use", self.memory_allocated(device_index)))

    def reset_peak_memory_stats(self, device_index=None) -> None:
        pass

    def total_memory(self, device_index=None) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index=None) -> int:
        return self.total_memory(device_index) - self.memory_allocated(device_index)

    def empty_cache(self) -> None:
        pass

    # -- dtype support (reference :169-182) -------------------------------
    @abc.abstractmethod
    def is_bf16_supported(self) -> bool: ...

    @abc.abstractmethod
    def is_fp16_supported(self) -> bool: ...

    @abc.abstractmethod
    def supported_dtypes(self) -> List: ...

    # -- graphs (reference :211-219): jit is the capture mechanism --------
    def create_graph(self):
        return None

    def capture_to_graph(self, graph, **kwargs):
        import jax
        return jax.jit

    def replay_graph(self, graph) -> None:
        pass

    # -- host/pinned memory (reference :259-267) --------------------------
    def pin_memory(self, array, align_bytes: int = 1):
        return array

    def is_pinned(self, array) -> bool:
        return False

    # -- comm / op-builder seams ------------------------------------------
    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    def op_builder_dir(self) -> str:
        return "deepspeed_tpu.ops"

    def create_op_builder(self, class_name: str):
        return None

    def get_op_builder(self, class_name: str):
        return None

    def build_extension(self):
        from ..ops import native
        return native.build

    # -- env ---------------------------------------------------------------
    def visible_devices_envs(self) -> List[str]:
        return ["TPU_VISIBLE_DEVICES", "JAX_PLATFORMS"]

    def on_accelerator(self, array) -> bool:
        try:
            import jax
            return isinstance(array, jax.Array)
        except Exception:
            return False
