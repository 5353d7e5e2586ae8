"""The program's own observability: named spans at its layer boundaries.

``span(name)`` is a ``jax.profiler.TraceAnnotation("repro." + name)``, so
the program's spans land in the profiler's trace beside the device ops, on
one clock.  They are recorded while :func:`enabled`: whenever a JAX
profiler trace is being collected, or after ``enable(True)``.  Otherwise
``span`` returns one shared null context and costs a flag test.  Counters
that need a timestamp per item (the packer's queue wait) follow the same
switch.

Spans: ``serve.guard``, ``serve.pack``, ``serve.decode``, ``serve.launch``,
``serve.harvest`` (``repro.serve.engine``), ``data.decode``
(``repro.data.pipeline``) and ``learn.commit``
(``repro.core.controller.OnlineLearner.train_batch``).
"""

from __future__ import annotations

import contextlib

import jax

_NULL = contextlib.nullcontext()
_on = False
# True while a profiler session collects (a static method of the
# annotation's base class, bound once).
_profiling = jax.profiler.TraceAnnotation.is_enabled


def enable(on: bool = True) -> None:
    """Record spans and timestamped counters even with no profiler
    collecting (``True``), or only while one collects (``False``, the
    default)."""
    global _on
    _on = bool(on)


def enabled() -> bool:
    return _on or _profiling()


def span(name: str):
    """A context manager that records ``repro.<name>`` while enabled."""
    if _on or _profiling():
        return jax.profiler.TraceAnnotation("repro." + name)
    return _NULL
