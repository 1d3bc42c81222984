"""Host spans the program adds to a JAX profiler trace.

The step programs name their layers with ``jax.named_scope`` (``embed``,
``layers``, ``attention`` with ``kv_write`` and ``attend`` inside it,
``mlp`` / ``moe`` / ``mamba``, ``head``, ``sample``); those names reach the
trace as each device op's ``op_name``.  What the host does between device
programs is named here.
"""
from __future__ import annotations

import contextlib
import gc
from typing import Iterator

from jax.profiler import TraceAnnotation

GC_SPAN = "gc"


@contextlib.contextmanager
def gc_spans() -> Iterator[None]:
    """While entered, record each Python garbage collection as a host span
    named ``gc``, on the thread that ran it."""
    open_span = []

    def on_gc(phase, info):
        if phase == "start":
            span = TraceAnnotation(GC_SPAN)
            span.__enter__()
            open_span.append(span)
        elif open_span:
            open_span.pop().__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
        while open_span:
            open_span.pop().__exit__(None, None, None)
