"""Lazy native-op build system (≅ reference ``op_builder/builder.py:102
OpBuilder`` JIT-load contract, radically smaller).

The reference JIT-compiles torch CUDA extensions per op at first use
(builder.py:443). Here the native surface is two host-side C++ libraries
(CPU Adam, AIO) compiled with g++ to plain shared objects and bound with
ctypes — no pybind11/torch toolchain. Pallas kernels need no building.

``OpBuilder.load()`` compiles on first use into ``_build/`` next to this
file and returns a ``ctypes.CDLL``. The object's file name carries a hash of
its sources, its flags and the CPU it was built for (``-march=native``), so
a binary left behind by other sources or another machine is never loaded —
it simply has another name. Failures mark
the builder incompatible (``is_compatible()`` → False) so callers can fall
back to pure-numpy paths — the analog of the reference's compatibility
probes.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from typing import List, Optional

from ...utils.logging import logger

_CSRC = os.path.join(os.path.dirname(__file__), "..", "csrc")
_BUILD = os.path.join(os.path.dirname(__file__), "..", "_build")


def _machine_tag() -> str:
    """Architecture plus the CPU feature flags ``-march=native`` compiles
    for (x86 ``flags`` / arm ``Features`` line of /proc/cpuinfo)."""
    features = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    features = line.strip()
                    break
    except OSError:
        pass
    return f"{platform.machine()}|{features}"


class OpBuilder:
    NAME = "base"
    SOURCES: List[str] = []
    EXTRA_FLAGS: List[str] = []

    _cache = {}

    def absolute_sources(self) -> List[str]:
        return [os.path.normpath(os.path.join(_CSRC, s)) for s in self.SOURCES]

    def _compile_flags(self) -> List[str]:
        return (["-O3", "-shared", "-fPIC", "-std=c++17", "-fopenmp",
                 "-march=native"] + self.EXTRA_FLAGS)

    def build_key(self) -> str:
        """Hash of everything the built object depends on: source bytes,
        compile flags, and — because of ``-march=native`` — this CPU."""
        h = hashlib.sha256()
        for src in self.absolute_sources():
            with open(src, "rb") as f:
                h.update(f.read())
        h.update(" ".join(self._compile_flags()).encode())
        h.update(_machine_tag().encode())
        return h.hexdigest()[:16]

    def so_path(self) -> str:
        return os.path.join(_BUILD, f"{self.NAME}-{self.build_key()}.so")

    def build(self) -> str:
        os.makedirs(_BUILD, exist_ok=True)
        so = self.so_path()
        # build beside the target and rename: a concurrent loader never
        # sees a half-written object under the keyed name
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = (["g++"] + self._compile_flags()
               + self.absolute_sources() + ["-o", tmp])
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except (subprocess.CalledProcessError, FileNotFoundError) as e:
            # -march=native can be unsupported in exotic environments; retry
            stderr = getattr(e, "stderr", str(e))
            try:
                cmd = [c for c in cmd if c != "-march=native"]
                subprocess.run(cmd, check=True, capture_output=True, text=True)
            except Exception:
                raise RuntimeError(
                    f"building native op {self.NAME} failed:\n{stderr}") from e
        os.replace(tmp, so)
        return so

    def is_compatible(self) -> bool:
        try:
            self.load()
            return True
        except Exception:
            return False

    def load(self) -> ctypes.CDLL:
        if self.NAME in OpBuilder._cache:
            return OpBuilder._cache[self.NAME]
        if os.environ.get("DS_SKIP_NATIVE_BUILD"):
            raise RuntimeError("native builds disabled by DS_SKIP_NATIVE_BUILD")
        so = self.so_path()
        if not os.path.exists(so):
            logger.info(f"building native op {self.NAME} ...")
            self.build()
        lib = ctypes.CDLL(so)
        self._declare(lib)
        OpBuilder._cache[self.NAME] = lib
        return lib

    def _declare(self, lib: ctypes.CDLL) -> None:
        """Subclasses set argtypes/restypes here."""


class CPUAdamBuilder(OpBuilder):
    """≅ reference op_builder/cpu_adam.py."""

    NAME = "ds_cpu_adam"
    SOURCES = ["cpu_adam.cpp"]

    def _declare(self, lib):
        f32p = ctypes.POINTER(ctypes.c_float)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.ds_adam_step.argtypes = [
            f32p, f32p, f32p, f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_float, ctypes.c_int, ctypes.c_float, ctypes.c_float]
        lib.ds_adam_step.restype = None
        lib.ds_adagrad_step.argtypes = [
            f32p, f32p, f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float]
        lib.ds_adagrad_step.restype = None
        lib.ds_f32_to_bf16.argtypes = [u16p, f32p, ctypes.c_int64]
        lib.ds_f32_to_bf16.restype = None
        lib.ds_has_nonfinite.argtypes = [f32p, ctypes.c_int64]
        lib.ds_has_nonfinite.restype = ctypes.c_int


class AsyncIOBuilder(OpBuilder):
    """≅ reference op_builder/async_io.py:12."""

    NAME = "ds_aio"
    SOURCES = ["aio.cpp"]

    def _declare(self, lib):
        lib.ds_aio_create.argtypes = [ctypes.c_int, ctypes.c_int64,
                                      ctypes.c_int64, ctypes.c_int]
        lib.ds_aio_create.restype = ctypes.c_void_p
        lib.ds_aio_destroy.argtypes = [ctypes.c_void_p]
        lib.ds_aio_destroy.restype = None
        for fn in (lib.ds_aio_pread, lib.ds_aio_pwrite):
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_int64, ctypes.c_int64]
            fn.restype = ctypes.c_int64  # completion ticket
        lib.ds_aio_wait.argtypes = [ctypes.c_void_p]
        lib.ds_aio_wait.restype = ctypes.c_int64
        lib.ds_aio_wait_ticket.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.ds_aio_wait_ticket.restype = ctypes.c_int64
        lib.ds_aio_pending.argtypes = [ctypes.c_void_p]
        lib.ds_aio_pending.restype = ctypes.c_int64
        lib.ds_aio_probe_o_direct.argtypes = [ctypes.c_char_p]
        lib.ds_aio_probe_o_direct.restype = ctypes.c_int


ALL_OPS = {
    CPUAdamBuilder.NAME: CPUAdamBuilder,
    AsyncIOBuilder.NAME: AsyncIOBuilder,
}


def available_ops():
    """{op name: built/compatible} — feeds ds_report (env_report.py)."""
    out = {}
    for name, cls in ALL_OPS.items():
        out[name] = cls().is_compatible()
    # Pallas kernels need no building; report them by import health
    try:
        from ..attention import flash_attention  # noqa: F401

        out["pallas_flash_attention"] = True
    except Exception:
        out["pallas_flash_attention"] = False
    try:
        from ..sparse_attention import sparse_self_attention  # noqa: F401

        out["pallas_sparse_attention"] = True
    except Exception:
        out["pallas_sparse_attention"] = False
    return out
