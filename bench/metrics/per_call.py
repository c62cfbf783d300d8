"""Arithmetic shared by the readers of the program's own spans and
counters, which a program without them leaves unread."""

from __future__ import annotations


def span_ms(r, *names):
    """Milliseconds per window call spent in the ``repro.obs`` spans
    ``names``, summed; nothing where the window made no call or one of
    the spans never ran."""
    calls = r.info.get("calls")
    secs = [r.span_seconds(name) for name in names]
    if not calls or not all(secs):
        return None
    return 1e3 * sum(secs) / calls


def xla_compiles(r):
    """XLA executables compiled or loaded from the persistent cache
    inside the window: the increase of ``backend.xla.compiles``, all
    labels; nothing where the program never registered that counter."""
    if not r._rows("backend.xla.compiles"):
        return None
    return r.counter("backend.xla.compiles")
