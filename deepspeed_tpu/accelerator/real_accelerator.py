"""Accelerator selection (≅ reference ``accelerator/real_accelerator.py:45``).

Selection order: ``DSTPU_ACCELERATOR`` env override, else the platform of
``jax.devices()`` (tpu → TpuAccelerator, gpu → GpuAccelerator, cpu →
CpuAccelerator). Any other platform raises: reporting host RAM as an
unknown accelerator's HBM would hide the device.
"""

from __future__ import annotations

import os
from typing import List, Optional

from .abstract_accelerator import Accelerator

_accelerator: Optional[Accelerator] = None


class TpuAccelerator(Accelerator):
    _name = "tpu"
    _communication_backend_name = "ici"

    def devices(self) -> List:
        import jax

        return jax.devices("tpu")

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16


class GpuAccelerator(Accelerator):
    _name = "gpu"
    _communication_backend_name = "nccl"

    def devices(self) -> List:
        import jax

        return jax.devices("gpu")


class CpuAccelerator(Accelerator):
    _name = "cpu"
    _communication_backend_name = "gloo"

    def devices(self) -> List:
        import jax

        return jax.devices("cpu")

    def memory_stats(self, device=None) -> dict:
        try:
            import psutil

            vm = psutil.virtual_memory()
            return {"bytes_in_use": vm.used, "bytes_limit": vm.total,
                    "peak_bytes_in_use": vm.used}
        except Exception:
            return {}


_ACCELERATORS = {"tpu": TpuAccelerator, "gpu": GpuAccelerator, "cpu": CpuAccelerator}


def get_accelerator() -> Accelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator

    name = os.environ.get("DSTPU_ACCELERATOR", "").lower() or None
    if name is None:
        import jax

        name = jax.default_backend()
    if name not in _ACCELERATORS:
        raise RuntimeError(
            f"no accelerator class for platform {name!r}; expected one of "
            f"{sorted(_ACCELERATORS)}")
    _accelerator = _ACCELERATORS[name]()
    return _accelerator


def set_accelerator(accel: Accelerator) -> None:
    global _accelerator
    _accelerator = accel
