"""What the harness needs from the machine: the device JAX reports (and a
refusal when it is no TPU), the peaks of that device, compile requests
counted over a window, peak memory, and a profiler trace with the Python
tracer off. Imports JAX lazily: importing this module touches no backend."""

from __future__ import annotations

import json
import os
import shutil
import sys
from typing import Optional

EXIT_FAILED = 1
EXIT_NO_ACCELERATOR = 4
EXIT_NO_PROGRAM = 5


def open_device(chips: int, rehearsal: bool = False) -> dict:
    """First touch of JAX. Names what it found and stops the run, with no
    result line, unless that is a TPU with at least ``chips`` chips."""
    import jax

    devices = jax.devices()
    first = devices[0]
    found = {"platform": first.platform, "kind": first.device_kind,
             "count": len(devices)}
    if rehearsal:
        return found
    if first.platform != "tpu" or len(devices) < chips:
        print(f"perf/run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} device(s) of platform {first.platform!r} "
              f"(device_kind {first.device_kind!r}). A device metric comes "
              f"only from the chip; perf/tools/rehearse.py walks the same "
              f"code on the CPU and prints no metric.", file=sys.stderr)
        sys.exit(EXIT_NO_ACCELERATOR)
    return found


def peaks_for(kind: str, table: dict) -> dict:
    """The published peaks of this device_kind. A device that is not in
    perf/peaks.json is an error, never a default."""
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in perf/peaks.json "
                       f"(it has {sorted(table)}); add its published peaks "
                       f"with their source before measuring on it")
    return table[kind]


class CompileRequests:
    """Counts compile requests while ``active``. (Copied from chip_smoke.py:
    the recompile watchdog listens for backend compiles, and a program found
    in a warm persistent cache is loaded without one; this event fires either
    way, so a warm cache cannot hide a program that first appears inside the
    measured window.)"""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


def memory_peak_bytes(devices) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest device (None where the backend
    reports no memory statistics, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def annotate(name: str, **kwargs):
    """A host span on the profiler's own clock (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name, **kwargs)


class DeviceTrace:
    """One profiler trace of a steady part of the window, written under a
    fixed directory inside the checkout and removed once reduced."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.running = False
        self.done = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # no event per Python call
        options.host_tracer_level = 1        # our TraceAnnotations only
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.running = True

    def stop(self) -> None:
        import jax

        if self.running:
            jax.profiler.stop_trace()
            self.running = False
            self.done = True

    def reduce(self) -> Optional[dict]:
        from perf import trace_reduce

        path = trace_reduce.find_xplane(self.trace_dir)
        if path is None:
            return None
        try:
            reduced = trace_reduce.reduce_trace(
                trace_reduce.load_xplane(path))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        if reduced is not None:
            # the whole reduction of the last traced run stays beside the
            # (removed) trace for the builder to read: the result line holds
            # only its ten largest entries
            with open(self.trace_dir + ".reduced.json", "w") as f:
                json.dump(reduced, f)
        return reduced
