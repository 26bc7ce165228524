"""Host spans of the serving path, on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: under ``jax.profiler.start_trace`` it writes one host
event, with ``meta`` as its stats, beside the device's operations; with no
trace running the profiler's own check makes it a no-op (about 1–2 µs).
Spans wrap dispatches and the phases inside them, never single requests.
Nesting on one thread gives a span's parent.

    repro.batcher.idle        waiting for a group's first request
    repro.batcher.coalesce    first request to the group's close
    repro.batcher.dispatch    one dispatch, hand-over to the last future set
      repro.engine.query      one engine call (regime, bucket, queries)
        repro.engine.prepare  input checks, host-side edge padding
        repro.plane.stage     the H2D staging route
        repro.engine.compile  a compile on a cache miss (regime, bucket, k)
        repro.engine.execute  the executable, through block_until_ready
        repro.engine.fetch    device-to-host copy of ids and distances
      repro.batcher.resolve   setting the group's futures

``scoped(name)`` names the device side: every operation a function stages
out carries ``name`` in its op name (``jax.named_scope``), so the fusions
XLA makes from it can be told apart in a trace.
"""
from __future__ import annotations

import functools

import jax
from jax.profiler import TraceAnnotation

PREFIX = "repro."


def span(name: str, **meta) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **meta)


def scoped(name: str):
    """Decorator: trace the function inside ``jax.named_scope(name)``.  A
    fresh scope per call, so threads tracing at once keep their own name
    stacks (a named scope used as a decorator is one shared object)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
