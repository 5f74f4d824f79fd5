"""The program's own spans in a profiler trace: each span name's self
time inside the window, and the window's idle time charged to the
deepest client-thread span at each instant.

The program writes its spans with ``jax.profiler.TraceAnnotation``:
``lookup.*`` inside ``ShardedShortcutEH.lookup_batched``, ``insert.*``
inside ``ShortcutEH.insert``, ``mapper.*`` on the mapper's thread.  They
share the clock of the device planes that :mod:`chipbench.trace` reads.
Every Python thread's line in the host plane has the same name, so a
span's thread is its line's place in its plane; the client thread is
the line that holds the harness's ``window`` span.

Spans are plain :class:`Span` tuples, so the reduction is checked on
hand-made lists; :func:`load` reads them from an ``.xplane.pb`` file.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

from chipbench import trace as tracing

PROGRAM_PREFIXES = ("lookup.", "insert.", "mapper.")
#: every span the program writes, by the thread that writes it
CLIENT_SPANS = ("lookup.bucketize", "lookup.gate", "lookup.operands",
                "lookup.dispatch", "lookup.wait", "lookup.scatter",
                "insert.scan", "insert.lock", "insert.publish",
                "insert.touched", "insert.submit")
MAPPER_SPANS = ("mapper.snapshot", "mapper.replay", "mapper.populate",
                "mapper.discover", "mapper.remap")
HARNESS_SPANS = (tracing.WINDOW,) + tracing.HOST_SPANS


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    line: Tuple[str, int]          # (plane, index of the line): one thread
    args: Tuple = ()               # the span's keyword args, (name, value)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Node(NamedTuple):
    span: Span
    children: List["Node"]


def load(path: str) -> List[Span]:
    """The harness's and the program's spans of every host thread in an
    ``.xplane.pb`` file."""
    import jax
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [Span(e.name, e.start_ns, e.duration_ns, (plane.name, i),
                         tuple(e.stats))
                    for e in line.events if e.name in HARNESS_SPANS
                    or e.name.startswith(PROGRAM_PREFIXES)]
    return out


def client_line(spans: List[Span]) -> Tuple[str, int]:
    w = [s for s in spans if s.name == tracing.WINDOW]
    if len(w) != 1:
        raise ValueError(f"the spans hold {len(w)} '{tracing.WINDOW}' "
                         f"spans, not 1")
    return w[0].line


def forest(spans: List[Span]) -> List[Node]:
    """The nesting of one thread's spans: a span is the child of the
    innermost span that holds it."""
    roots: List[Node] = []
    stack: List[Node] = []
    for sp in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        while stack and (sp.end_ns > stack[-1].span.end_ns
                         or sp.start_ns >= stack[-1].span.end_ns):
            stack.pop()
        node = Node(sp, [])
        (stack[-1].children if stack else roots).append(node)
        stack.append(node)
    return roots


def _walk(nodes: List[Node]):
    for n in nodes:
        yield n
        yield from _walk(n.children)


def _covered(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def self_times(spans: List[Span], lo: Optional[float] = None,
               hi: Optional[float] = None) -> Dict[str, float]:
    """Seconds per span name, summed over threads: each span's time
    inside ``[lo, hi]`` (the window, by default) less what its child
    spans cover there.  Needs no device plane."""
    if lo is None or hi is None:
        lo, hi = tracing.window_of(spans)
    by_line: Dict[tuple, List[Span]] = defaultdict(list)
    for sp in spans:
        by_line[sp.line].append(sp)
    out: Dict[str, float] = defaultdict(float)
    for line_spans in by_line.values():
        for n in _walk(forest(line_spans)):
            s = n.span
            own = _covered(s.start_ns, s.end_ns, lo, hi) - sum(
                _covered(c.span.start_ns, c.span.end_ns, lo, hi)
                for c in n.children)
            out[s.name] += own * 1e-9
    return dict(out)


def _charge(gap: Tuple[float, float], nodes: List[Node], name: str,
            out: Dict[str, float], scale: float) -> None:
    """Charge each part of ``gap`` to the deepest span that covers it:
    the parts under ``nodes`` go down into them, the rest to ``name``."""
    left = gap[1] - gap[0]
    for n in nodes:
        lo = max(gap[0], n.span.start_ns)
        hi = min(gap[1], n.span.end_ns)
        if hi > lo:
            _charge((lo, hi), n.children, n.span.name, out, scale)
            left -= hi - lo
    if left > 0:
        out[name] += left * scale


def idle_by_span(tr: tracing.Trace, spans: List[Span]) -> Dict[str, float]:
    """The window's idle time, as :func:`chipbench.trace.reduce` finds
    its gaps, with each part of a gap charged to the deepest
    client-thread span that covers it: a gap from one request's kernel
    to the next one's crosses many spans, and each gets its own part.
    Spans of other threads never label a gap.  Seconds, mean over
    chips; the sum is the window less the busy time."""
    lo, hi = tracing.window_of(tr.host)
    chips = sorted(tr.modules)
    if not chips:
        raise ValueError("the trace holds no device plane")
    client = client_line(spans)
    mine = [s for s in spans if s.line == client
            and s.name != tracing.WINDOW]
    roots = forest(mine)
    idle: Dict[str, float] = defaultdict(float)
    for chip in chips:
        for g in tracing.gaps(tr.modules[chip], lo, hi):
            _charge(g, roots, tracing.NO_SPAN, idle, 1e-9 / len(chips))
    return dict(idle)
