"""Named host spans for the JAX profiler.

``with span("fastpath.submit", round=n): ...`` opens a host span named
``corais.fastpath.submit`` with ``round`` as an argument. The span is
written into the profiler's own trace, on the same clock as the device's
operations, so a trace taken with ``jax.profiler.trace`` shows what the
host was doing while the device ran or sat idle. With no profiler session
active a span records nothing and costs about a microsecond.
"""
from __future__ import annotations

import jax


def span(name: str, **args):
    """Context manager: the host span ``corais.<name>`` carrying ``args``."""
    return jax.profiler.TraceAnnotation("corais." + name, **args)
