"""Host spans in the profiler's trace.

`span(name, **stats)` marks a piece of host work. While a trace is being
taken (`jax.profiler.start_trace`) it lands on the trace's host line, on
the same clock as the device's operations, with `stats` as the event's
stats; a stat known only at the end is added with `set_metadata` on the
span. With no trace running a span costs about a microsecond.

The serving engine's spans are named `serve.*` (serve/engine.py);
`install_gc_spans` adds a `serve.gc` span around every Python garbage
collection, so that a collection pause shows as what it is.
"""
from __future__ import annotations

import gc

import jax


def span(name: str, **stats):
    """A host span; use as a context manager."""
    return jax.profiler.TraceAnnotation(name, **stats)


_gc_open = None          # the span of the collection under way


def _gc_span(phase: str, info: dict):
    # a collection starts and stops on one thread, under the interpreter
    # lock, and never nests, so one open span is all there can be
    global _gc_open
    if phase == "start":
        _gc_open = span("serve.gc", generation=info["generation"])
        _gc_open.__enter__()
    elif _gc_open is not None:
        _gc_open.__exit__(None, None, None)
        _gc_open = None


def install_gc_spans():
    """Open a `serve.gc` span for each garbage collection of this process
    (once per process; later calls do nothing)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
