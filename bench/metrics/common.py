"""Arithmetic shared by the per-layer readers."""

from __future__ import annotations


def idle_share(r):
    """Percent of the traced window in which no operation ran on the
    device; nothing where the trace holds no device or no window."""
    t = r.trace
    if not t.n_devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def program_seconds(r, pattern: str):
    """(device seconds, runs) of the programs whose name matches
    ``pattern`` in the traced window; nothing where none ran."""
    secs, runs = r.trace.module_seconds(pattern)
    return (secs, runs) if runs else None
