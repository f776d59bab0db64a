"""Profiler range annotation.

Capability parity with reference ``deepspeed/utils/nvtx.py:9
instrument_w_nvtx`` — wraps a function in a named range. The range is a
span of the process-wide tracer (``telemetry.default_tracer().span``: an
event in its ring, and a ``jax.profiler.TraceAnnotation`` that a running
profiler session shows in xprof/perfetto). Code traced inside it also
runs under ``jax.named_scope``, which names the HLO ops for the flops
profiler's per-module attribution.
"""

from __future__ import annotations

import contextlib
import functools


def instrument_w_nvtx(func):
    """Decorator: execute ``func`` inside a named trace range."""
    name = getattr(func, "__qualname__", getattr(func, "__name__", "fn"))

    @functools.wraps(func)
    def wrapped(*args, **kwargs):
        with trace_range(name):
            return func(*args, **kwargs)

    return wrapped


def range_push(name: str) -> None:
    """Eager range begin — reference signature (range_pop takes no args);
    delegates to the accelerator's stack-managed implementation."""
    from ..accelerator import get_accelerator

    get_accelerator().range_push(name)


def range_pop() -> None:
    from ..accelerator import get_accelerator

    get_accelerator().range_pop()


@contextlib.contextmanager
def trace_range(name: str):
    """with trace_range("phase"): ... — a span of the process-wide tracer
    that is ALSO a jax.named_scope, so ops traced inside attribute to this
    name in the flops profiler's per-module tree (same visibility as
    ``instrument_w_nvtx``)."""
    import jax

    from ..telemetry import default_tracer

    with default_tracer().span(name), jax.named_scope(name):
        yield
