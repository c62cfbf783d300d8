"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, device time per program and per
operation, and the idle gaps named by what the host was doing.

The host side is the benchmark's own ``jax.profiler.TraceAnnotation``
spans, all named ``bench.*``; ``bench.window`` spans the measured window.
The device side is each TPU plane's ``XLA Ops`` line (busy intervals and
operations) and its ``XLA Modules`` line (one event per program run).
Busy time is the union of operation intervals inside the window, averaged
over the devices that ran anything.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
#: Label of an idle gap during which no ``bench.*`` span but the window
#: was open.
BETWEEN = "bench.window (between calls)"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                  # mean over devices that ran anything
    n_devices: int
    ops: dict[str, float]          # operation -> device seconds per device
    modules: dict[str, list]       # program -> [device seconds, runs]
    gaps: dict[str, float]         # host activity -> idle seconds per device

    def top_ops(self, k: int = 10) -> list:
        return [[n, s] for n, s in sorted(self.ops.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10) -> list:
        return [[n, s] for n, s in sorted(self.gaps.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def module_seconds(self, pattern: str) -> tuple[float, int]:
        """Device seconds and runs of every program whose name matches
        ``pattern`` (a regular expression, searched)."""
        rx = re.compile(pattern)
        secs = runs = 0
        for name, (s, n) in self.modules.items():
            if rx.search(name):
                secs += s
                runs += n
        return secs, runs


def find_xplane(directory) -> pathlib.Path:
    """The newest ``.xplane.pb`` under ``directory``."""
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _program(name: str) -> str:
    """``jit_runner(1234)`` -> ``jit_runner``: runs of one program share
    a name."""
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: a TPU trace
    names each operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class _Host:
    """The ``bench.*`` spans (without the window) of the host, sorted by
    start, to name what the host was doing at a time."""

    #: How far back to look for an enclosing span: the harness nests its
    #: spans two deep at most.
    DEPTH = 8

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]

    def at(self, t) -> str:
        """The innermost span open at ``t``."""
        i = bisect.bisect_right(self.starts, t)
        for name, s, e in reversed(self.spans[max(0, i - self.DEPTH):i]):
            if s <= t < e:
                return name
        return BETWEEN

    def split(self, lo, hi):
        """``[lo, hi)`` cut where a span starts or ends, each piece named
        by :meth:`at`: ``[(name, seconds), ...]``."""
        i = bisect.bisect_left(self.starts, lo)
        cuts = {lo, hi}
        for name, s, e in self.spans[max(0, i - self.DEPTH):]:
            if s >= hi:
                break
            cuts.update(x for x in (s, e) if lo < x < hi)
        cuts = sorted(cuts)
        return [(self.at(0.5 * (a + b)), (b - a) * 1e-9)
                for a, b in zip(cuts, cuts[1:])]


def reduce(profile) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` (or a path to an
    ``.xplane.pb``) to a :class:`Summary`."""
    if isinstance(profile, (str, pathlib.Path)):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(str(profile))
    spans, window = [], None
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("bench."):
                        continue
                    if ev.name == WINDOW:
                        window = window or (ev.start_ns, ev.end_ns)
                    else:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(_op(e.name), e.start_ns, e.end_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(_program(e.name), e.start_ns, e.end_ns)
                               for e in line.events]
            if ops or modules:
                devices.append((ops, modules))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = window
    host = _Host(spans)
    n_dev = len(devices)
    ops_s: dict[str, float] = {}
    mod_s: dict[str, list] = {}
    gaps: dict[str, float] = {}
    busy = 0.0
    for ops, modules in devices:
        for name, s, e in ops:
            for cs, ce in _clip([(s, e)], lo, hi):
                ops_s[name] = ops_s.get(name, 0.0) + (ce - cs) * 1e-9 / n_dev
        for name, s, e in modules:
            clipped = _clip([(s, e)], lo, hi)
            if clipped:
                cs, ce = clipped[0]
                entry = mod_s.setdefault(name, [0.0, 0])
                entry[0] += (ce - cs) * 1e-9 / n_dev
                entry[1] += 1
        merged = _merge(_clip([(s, e) for _, s, e in ops] or
                              [(s, e) for _, s, e in modules], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                for name, secs in host.split(gs, ge):
                    gaps[name] = gaps.get(name, 0.0) + secs / n_dev
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy / n_dev if n_dev else 0.0, n_devices=n_dev,
                   ops=ops_s, modules=mod_s, gaps=gaps)
