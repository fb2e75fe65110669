"""Spans and executable names on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``.  While a profiler trace is recorded it lands in that
trace beside the device's timeline, on the same clock, with ``meta`` as
its stats; otherwise it records nothing and costs about a microsecond
(metadata is encoded only while a trace is active).

``named(fn, name)`` gives a function the name jit gives its executable:
``jit_<name>`` in the lowered text and on the device's module timeline.
"""
import jax

PREFIX = "repro."


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def named(fn, name: str):
    fn.__name__ = fn.__qualname__ = name
    return fn
