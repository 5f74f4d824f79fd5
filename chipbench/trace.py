"""Reduce a profiler trace of the measured window to device metrics.

The window is the host span ``window`` that the harness writes with
``jax.profiler.TraceAnnotation``.  Inside it:

  * busy time: the union of the intervals in which a program ran on a
    chip (the ``XLA Modules`` line of each ``/device:TPU:<n>`` plane),
    averaged over the chips.  The per-op line (``XLA Ops``) is not read:
    an insert scan puts millions of op events a second there, and on
    v5e the union of its intervals agrees with the programs' to within
    a few microseconds a second;
  * device time per jitted program, keyed by its jit name, as
    ``jit(<function>)``;
  * idle gaps: the parts of the window in which no operation ran, each
    charged to the host span (``insert``, ``lookup_batched``, ``fetch``)
    that covers most of it.

Events are plain :class:`Event` tuples, so the reduction is checked on
hand-made lists; :func:`load` turns an ``.xplane.pb`` file into them.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW = "window"
HOST_SPANS = ("insert", "lookup_batched", "fetch")
NO_SPAN = "between_spans"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """What the reduction reads from a trace: each chip's programs, and
    the host spans."""
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def jit_name(module: str) -> str:
    """``jit_foo(123)`` or ``jit_foo.4`` -> ``jit(foo)``."""
    m = re.match(r"jit[_(]([A-Za-z0-9_]+?)(?:[.(].*)?$", module)
    return f"jit({m.group(1)})" if m else module


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(merge((o.start_ns, o.end_ns)
                                            for o in events), lo, hi))


def gaps(events: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi]."""
    out, t = [], lo
    for s, e in clip(merge((o.start_ns, o.end_ns) for o in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Tuple[float, float], spans: List[Event]) -> str:
    """The host span that covers most of ``gap``."""
    best, best_ns = NO_SPAN, 0.0
    for sp in spans:
        ov = min(gap[1], sp.end_ns) - max(gap[0], sp.start_ns)
        if ov > best_ns:
            best, best_ns = sp.name, ov
    return best


def window_of(host: List[Event]) -> Tuple[float, float]:
    w = [e for e in host if e.name == WINDOW]
    if len(w) != 1:
        raise ValueError(f"the trace holds {len(w)} '{WINDOW}' spans, not 1")
    return w[0].start_ns, w[0].end_ns


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over chips
    by_jit: Dict[str, float]            # seconds, summed over chips
    idle_by_span: Dict[str, float]      # seconds, mean over chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(tr: Trace, span_names=HOST_SPANS) -> Reduced:
    lo, hi = window_of(tr.host)
    chips = sorted(tr.modules)
    if not chips:
        raise ValueError("the trace holds no device plane")
    spans = [e for e in tr.host if e.name in span_names]
    by_jit: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for chip in chips:
        busy += busy_ns(tr.modules[chip], lo, hi)
        for ev in tr.modules[chip]:
            ov = min(ev.end_ns, hi) - max(ev.start_ns, lo)
            if ov > 0:
                by_jit[jit_name(ev.name)] += ov * 1e-9
        for g in gaps(tr.modules[chip], lo, hi):
            idle[label(g, spans)] += (g[1] - g[0]) * 1e-9 / len(chips)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / len(chips),
                   by_jit=dict(by_jit), idle_by_span=dict(idle))


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, s] for n, s in sorted(d.items(), key=lambda x: -x[1])[:k]]


def load(path: str, host_names: Optional[Iterable[str]] = None) -> Trace:
    """Read an ``.xplane.pb`` file: the device planes' programs, and the
    host events named in ``host_names`` (the window and the harness's
    spans)."""
    import jax
    names = set(host_names or (WINDOW,) + HOST_SPANS)
    tr = Trace()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Modules":
                tr.modules[plane.name] = [
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                tr.host += [Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in names]
    return tr

