"""Host spans on the profiler's own clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` whose keyword arguments become stats of the trace event
(``uid`` ties the spans of one request together). While a profiler trace is
running the span lands in the same ``.xplane.pb`` as the device's
operations, on the same clock; otherwise entering and leaving it records
nothing. There is no other switch.

Spans nest by time: a child opens and closes inside its parent on the same
thread. ``NAMES`` lists every span the program opens.
"""
from __future__ import annotations

import jax

NAMES = (
    "serve.submit",         # uid: a request enters the admission queue
    "serve.step",           # one engine step: admissions, decode block, bookkeeping
    "serve.admit",          # uid, prompt_len, bucket: prefill + insert of one request
    "serve.prefill",        # uid: batch-1 prefill, first-token sample and its sync
    "serve.insert",         # uid: page reservation and the cache splice
    "serve.generate",       # steps, active: one fused decode block
    "serve.generate.wait",  # the host's wait for the block's emitted tokens
    "serve.bookkeep",       # per-slot outputs, finished requests, page releases
)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A context manager timing ``repro.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(f"repro.{name}", **ids)
