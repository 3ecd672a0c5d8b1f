"""Stage-span tracing bridged into the JAX/XLA profiler.

The reference's only tracing is wall-clock spans recorded into a stats
actor (reference: shuffle.py:204-263, stats.py:68-246); device time is
invisible to it. Here every hot stage (map, reduce, consume, convert,
transfer, train step) is wrapped in a ``jax.profiler.TraceAnnotation`` so
a captured trace shows the host pipeline stages on the same timeline as
XLA device ops — the stall analysis the reference can't do: you SEE
whether the device waits on the loader or vice versa.

Zero-cost by default: annotations are no-ops until a trace is active.
Capture is explicit (:func:`profile_trace`) or env-driven
(``RSDL_PROFILE_DIR=/tmp/trace python ...`` via :func:`maybe_profile`);
view with TensorBoard's profile plugin or Perfetto.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional


@contextlib.contextmanager
def trace_span(name: str, kind: Optional[str] = None,
               epoch: Optional[int] = None, task: Optional[int] = None,
               batch: Optional[int] = None) -> Iterator[None]:
    """Named host span, visible in captured profiler traces. No-op cheap
    when no trace is active; safe to call from worker threads.

    With ``kind`` set, the span is ALSO recorded as a structured
    flight-recorder event (runtime/telemetry.py) carrying the given
    correlation ids — one annotation, two consumers: the XLA profiler
    timeline and the online bottleneck attribution.
    """
    # Imported here, not at module top: pure-host code paths (pool
    # workers) pay for jax only when they open their first span.
    from jax.profiler import TraceAnnotation
    if kind is None:
        with TraceAnnotation(name):
            yield
        return
    from ray_shuffling_data_loader_tpu.runtime import telemetry
    with telemetry.span(kind, epoch=epoch, task=task, batch=batch):
        with TraceAnnotation(name):
            yield


def step_span(step: int):
    """Train-step marker: lets the profiler group device ops per step.
    Returns a context manager."""
    from jax.profiler import StepTraceAnnotation
    return StepTraceAnnotation("train", step_num=step)


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Capture a JAX profiler trace (host spans + device timeline) into
    ``log_dir`` for the duration of the block."""
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def maybe_profile(env_var: str = "RSDL_PROFILE_DIR") -> Iterator[None]:
    """Capture a trace iff the env var names a directory — the zero-code
    way to profile any run: ``RSDL_PROFILE_DIR=/tmp/tr python bench.py``."""
    log_dir: Optional[str] = os.environ.get(env_var)
    if not log_dir:
        yield
        return
    with profile_trace(log_dir):
        yield
