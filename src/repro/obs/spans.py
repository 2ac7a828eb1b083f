"""Host spans on the profiler's clock (DESIGN §8).

A span is a ``jax.profiler.TraceAnnotation``: while a ``jax.profiler``
trace is being taken it lands on the host plane of that trace, on the
same clock as the device's ops; otherwise it costs about a microsecond.
The engine opens :func:`increment` around each
``StreamingEngine.run_increment``, and every :func:`span` opened inside
it carries the same ``inc`` (the engine's ``stream_pos``), so that all
spans of one increment share an identifier.
"""
from __future__ import annotations

import contextlib
import contextvars

from jax.profiler import TraceAnnotation

_INC = contextvars.ContextVar("repro_increment", default=None)


@contextlib.contextmanager
def increment(inc: int, edges: int):
    """``repro.increment`` around one increment of ``edges`` edges; the
    spans opened inside carry ``inc``."""
    token = _INC.set(inc)
    try:
        with TraceAnnotation("repro.increment", inc=inc, edges=edges):
            yield
    finally:
        _INC.reset(token)


def span(name: str, inc: int | None = None) -> TraceAnnotation:
    """A host span named ``name``, tagged with ``inc``, or with the
    enclosing :func:`increment`'s where ``inc`` is not given."""
    inc = _INC.get() if inc is None else inc
    return (TraceAnnotation(name) if inc is None
            else TraceAnnotation(name, inc=inc))
