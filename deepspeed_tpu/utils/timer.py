"""Wall-clock and throughput timers.

Capability parity with reference ``deepspeed/utils/timer.py`` —
``SynchronizedWallClockTimer`` (:33) and ``ThroughputTimer`` (:153).

**Dispatch vs compute.** JAX dispatch is asynchronous: a jitted call
returns as soon as the program is enqueued, so a host-side timer around
it measures *dispatch*, not compute. The accelerator's bare
``synchronize()`` (no tensors) only round-trips a tiny transfer, which
does NOT wait for enqueued compute — the reference's
``cuda.synchronize()`` has no cheap TPU analogue. Timers that wrap
jitted calls must therefore pass the call's outputs to
``stop(block_on=...)``, which ``jax.block_until_ready``-s them before
reading the clock. Construct the timer with ``barrier=True`` to make
that mandatory: a ``stop()`` without ``block_on`` then raises instead
of silently recording a dispatch time.

``SynchronizedWallClockTimer.publish(registry)`` drains every timer's
recorded intervals into ``timer/{name}_ms`` histograms on a
:class:`~deepspeed_tpu.telemetry.MetricsRegistry`.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from .logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


class SynchronizedWallClockTimer:
    class Timer:
        def __init__(self, name: str, barrier: bool = False):
            self.name_ = name
            self.barrier = barrier
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = 0.0
            self.records: List[float] = []

        def _sync(self):
            from ..accelerator import get_accelerator

            try:
                get_accelerator().synchronize()
            except Exception:
                pass

        def start(self):
            # no device round trip before the clock read: the bare
            # synchronize() waits for no enqueued compute (module doc),
            # and what bounds the interval is stop()
            assert not self.started_, f"{self.name_} timer has already been started"
            self.start_time = time.time()
            self.started_ = True

        def stop(self, reset: bool = False, record: bool = True,
                 block_on=None):
            """Stop the timer. ``block_on`` takes the jitted call's
            outputs (any pytree) and waits for them to actually exist
            before reading the clock — without it, async dispatch makes
            the recorded interval a dispatch time (see module doc).
            ``barrier=True`` timers refuse to record without it."""
            assert self.started_, "timer is not started"
            if block_on is not None:
                import jax

                jax.block_until_ready(block_on)
            elif self.barrier and record:
                raise RuntimeError(
                    f"timer '{self.name_}' was constructed with "
                    f"barrier=True: stop() needs block_on=<jitted "
                    f"outputs>, otherwise it times dispatch, not compute")
            else:
                self._sync()
            elapsed = time.time() - self.start_time
            if reset:
                self.elapsed_ = elapsed
            else:
                self.elapsed_ += elapsed
            if record:
                self.records.append(elapsed * 1000.0)
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset: bool = True) -> float:
            started = self.started_
            if started:
                self.stop(record=False)
            elapsed = self.elapsed_
            if reset:
                self.reset()
            if started:
                self.start()
            return elapsed

        def mean(self) -> float:
            return sum(self.records) / len(self.records) if self.records else 0.0

    def __init__(self):
        self.timers: Dict[str, SynchronizedWallClockTimer.Timer] = {}

    def __call__(self, name: str,
                 barrier: bool = False) -> "SynchronizedWallClockTimer.Timer":
        if name not in self.timers:
            self.timers[name] = self.Timer(name, barrier=barrier)
        return self.timers[name]

    def publish(self, registry, clear: bool = True) -> int:
        """Drain every timer's recorded intervals into ``timer/{name}_ms``
        histograms on a telemetry ``MetricsRegistry``; returns the number
        of observations moved (drained so repeat publishes never
        double-count)."""
        moved = 0
        for name, timer in self.timers.items():
            if not timer.records:
                continue
            hist = registry.histogram(f"timer/{name}_ms")
            for ms in timer.records:
                hist.observe(ms)
            moved += len(timer.records)
            if clear:
                del timer.records[:]
        return moved

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True,
            memory_breakdown: bool = False, ranks: Optional[List[int]] = None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed:.2f}"
        log_dist(string, ranks=ranks or [0])

    def get_mean(self, names: List[str], normalizer: float = 1.0, reset: bool = True):
        assert normalizer > 0.0
        return {name: self.timers[name].mean() / normalizer
                for name in names if name in self.timers}


class ThroughputTimer:
    """samples/sec tracker (reference utils/timer.py:153)."""

    def __init__(self, batch_size: int, start_step: int = 2, steps_per_output: int = 50,
                 monitor_memory: bool = False, logging_fn=None):
        self.start_time = 0.0
        self.end_time = 0.0
        self.started = False
        self.batch_size = max(batch_size, 1)
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.step_elapsed_time = 0.0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or (lambda msg: log_dist(msg, ranks=[0]))
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            # as Timer.start: stop(block_on=...) bounds the interval
            self.start_time = time.time()

    def stop(self, global_step: bool = False, report_speed: bool = True,
             block_on=None):
        """``block_on`` — the step's jitted outputs; waits for compute to
        finish before reading the clock (see module doc on dispatch)."""
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if global_step:
            self.global_step_count += 1
        if self.start_time > 0 and self.global_step_count > self.start_step:
            if block_on is not None:
                import jax

                jax.block_until_ready(block_on)
            else:
                from ..accelerator import get_accelerator

                try:
                    get_accelerator().synchronize()
                except Exception:
                    pass
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration
            if global_step and report_speed and \
                    self.global_step_count % self.steps_per_output == 0:
                self.logging(
                    f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                    f"global_step={self.global_step_count}, "
                    f"RunningAvgSamplesPerSec={self.avg_samples_per_sec():.2f}, "
                    f"CurrSamplesPerSec={self.batch_size / duration:.2f}")
                self.step_elapsed_time = 0.0

    def avg_samples_per_sec(self) -> float:
        if self.global_step_count > self.start_step and self.total_elapsed_time > 0:
            samples = self.batch_size * (self.global_step_count - self.start_step)
            return samples / self.total_elapsed_time
        return 0.0
