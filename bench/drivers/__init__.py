"""General drivers of the benchmark's traffic mixes, one module per kind
of mix; ``bench/traffic/<mix>.json`` names its driver."""

import contextlib


def annotation(name: str, on: bool):
    """A ``jax.profiler.TraceAnnotation`` called ``name`` in a traced
    window, nothing otherwise."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)
