"""Accelerator abstraction seam.

Capability parity with the reference's ``accelerator/abstract_accelerator.py:10
DeepSpeedAccelerator`` ABC — device naming, memory stats, RNG, synchronization,
communication-backend name — re-expressed for JAX backends. The seam exists so
offload code and the test harness run unchanged on a CPU host without TPUs
(reference motivation: accelerator/real_accelerator.py:45).

Streams/events have no user-visible analog under XLA (the compiler schedules
async ops); the matching surface here is async dispatch + ``synchronize`` =
``block_until_ready``.
"""

from __future__ import annotations

import abc
from typing import Any, List, Optional


class Accelerator(abc.ABC):
    _name: str = "abstract"
    _communication_backend_name: str = "abstract"

    # --- identity ---
    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def is_available(self) -> bool:
        return self.device_count() > 0

    @abc.abstractmethod
    def devices(self) -> List[Any]:
        ...

    def device_count(self) -> int:
        return len(self.devices())

    def local_devices(self) -> List[Any]:
        import jax

        return [d for d in self.devices() if d.process_index == jax.process_index()]

    def current_device(self):
        return self.devices()[0]

    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    # --- synchronization (streams/events ≅ async dispatch under XLA) ---
    def synchronize(self, tensors=None) -> None:
        import jax

        if tensors is not None:
            jax.block_until_ready(tensors)
        else:
            import numpy as np

            # A tiny device round-trip drains the dispatch queue on all local
            # devices, standing in for torch.cuda.synchronize().
            for d in self.local_devices():
                jax.block_until_ready(jax.device_put(np.zeros(()), d))

    # --- RNG ---
    def default_generator(self, seed: int):
        import jax

        return jax.random.PRNGKey(seed)

    # --- memory ---
    def memory_stats(self, device=None) -> dict:
        dev = device if device is not None else self.current_device()
        try:
            return dict(dev.memory_stats() or {})
        except Exception:
            return {}

    def memory_allocated(self, device=None) -> int:
        return int(self.memory_stats(device).get("bytes_in_use", 0))

    def max_memory_allocated(self, device=None) -> int:
        return int(self.memory_stats(device).get("peak_bytes_in_use", 0))

    def total_memory(self, device=None) -> int:
        return int(self.memory_stats(device).get("bytes_limit", 0))

    def available_memory(self, device=None) -> int:
        stats = self.memory_stats(device)
        return int(stats.get("bytes_limit", 0)) - int(stats.get("bytes_in_use", 0))

    # --- dtypes ---
    def supported_dtypes(self):
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32]

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16

    # --- tracing ranges (NVTX analog; surfaced to jax profiler) ---
    def range_push(self, msg: str):
        from ..telemetry import default_tracer

        tc = default_tracer().span(msg)
        tc.__enter__()
        self._range_stack = getattr(self, "_range_stack", [])
        self._range_stack.append(tc)

    def range_pop(self):
        stack = getattr(self, "_range_stack", [])
        if stack:
            stack.pop().__exit__(None, None, None)

    def on_accelerator(self, array) -> bool:
        try:
            return any(d in self.devices() for d in array.devices())
        except AttributeError:
            return False
