"""Capturing a JAX/XLA profiler trace, and the trainer's step marker.

The program's stage spans are ``runtime/telemetry.span`` /
``span_begin`` / ``span_end``: one call lands an interval in the flight
recorder and, under its fixed ``rsdl.*`` name
(``telemetry.SPAN_NAMES``), in the profiler's trace, so a captured trace
shows the host pipeline stages on the same timeline as the XLA device
operations. This module only captures: explicitly
(:func:`profile_trace`) or env-driven
(``RSDL_PROFILE_DIR=/tmp/trace python ...`` via :func:`maybe_profile`);
view with TensorBoard's profile plugin or Perfetto.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

from ray_shuffling_data_loader_tpu.runtime import telemetry


def step_span(step: int):
    """Train-step marker: lets the profiler group device ops per step.
    Returns a context manager. Profiler-only (the consumer's loop records
    the ``train_step`` stage)."""
    from jax.profiler import StepTraceAnnotation
    return StepTraceAnnotation(telemetry.STEP_ANNOTATION, step_num=step)


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
    way to profile a run whose driver enters this context."""
    log_dir: Optional[str] = os.environ.get(env_var)
    if not log_dir:
        yield
        return
    with profile_trace(log_dir):
        yield
