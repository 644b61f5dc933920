"""Reductions of the program's own host spans.

The serving engine marks each phase of `ServeEngine.step` with a `serve.*`
span (`repro.profiling.span`, serve/engine.py), inside the benchmark's
`serve.step`. `bench.trace.load` reads them with the benchmark's spans, on
the device's clock:

* host work: the spans in which the host does work that the device may
  wait for: admission, the preps, the dispatches (the enqueue), the
  harvests and the retire; one `serve.admit` opens each step;
* idle attribution: each device idle gap is cut at every span edge, and
  each piece goes to the innermost host span open over it, the one that
  started last (a garbage-collection span first, while it is open), or to
  "none";
* dispatch stats: the dispatch spans carry the lanes computed and
  committed as event stats, which `bench.trace.load` does not keep;
  `load_spans` reads them.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from bench.trace import SPAN_PREFIXES, WINDOW_SPAN, Event, idle_gaps, \
    in_window

HOST_WORK = frozenset({
    "serve.admit", "serve.chunk.prep", "serve.chunk.dispatch",
    "serve.chunk.harvest", "serve.decode.prep", "serve.decode.dispatch",
    "serve.decode.harvest", "serve.retire"})
GC = "serve.gc"
STEP = "serve.admit"
NONE = "none"


@dataclass(frozen=True)
class Span(Event):
    stats: Dict[str, Any] = field(default_factory=dict, hash=False)


def load_spans(path: str) -> List[Span]:
    """The host spans of a profiler trace with their stats."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(Span(e.start_ns, e.duration_ns, e.name,
                                dict(e.stats))
                           for e in line.events
                           if e.name.startswith(SPAN_PREFIXES))
    return out


def window_spans(summary, names: Iterable[str]) -> List[Event]:
    """Spans named in `names` that start inside the summary's window."""
    names = frozenset(names)
    return [s for s in in_window(summary.trace.spans, summary.lo, summary.hi)
            if s.name in names]


def host_ms_per_step(summary) -> Optional[float]:
    """Host-work milliseconds per engine step in the window."""
    spans = window_spans(summary, HOST_WORK)
    steps = sum(1 for s in spans if s.name == STEP)
    if not steps:
        return None
    return 1e-6 * sum(s.dur_ns for s in spans) / steps


def _owner(open_spans: Sequence[Event]) -> str:
    if not open_spans:
        return NONE
    gc = [s for s in open_spans if s.name == GC]
    return max(gc or open_spans, key=lambda s: (s.start_ns, -s.dur_ns)).name


def split_gap(gap: Tuple[float, float],
              spans: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds of `gap` under each innermost span."""
    a, b = gap
    over = [s for s in spans if s.start_ns < b and s.end_ns > a]
    cuts = sorted({a, b} | {t for s in over for t in (s.start_ns, s.end_ns)
                            if a < t < b})
    out: Dict[str, float] = {}
    for p, q in zip(cuts, cuts[1:]):
        name = _owner([s for s in over if s.start_ns <= p and s.end_ns >= q])
        out[name] = out.get(name, 0.0) + q - p
    return out


def attributed_gaps(summary) -> List[Tuple[Tuple[float, float],
                                           Dict[str, float]]]:
    """Every idle gap of every device in the window, with its split."""
    spans = sorted((s for s in summary.trace.spans
                    if s.name != WINDOW_SPAN), key=lambda s: s.start_ns)
    out = []
    for d in summary.trace.devices.values():
        i, open_spans = 0, []
        for g in sorted(idle_gaps(d.ops, summary.lo, summary.hi)):
            while i < len(spans) and spans[i].start_ns < g[1]:
                open_spans.append(spans[i])
                i += 1
            open_spans = [s for s in open_spans if s.end_ns > g[0]]
            out.append((g, split_gap(g, open_spans)))
    return out


def idle_by_span(summary) -> Dict[str, float]:
    """Idle seconds under each innermost span, averaged over devices."""
    out: Dict[str, float] = {}
    for _, split in attributed_gaps(summary):
        for name, ns in split.items():
            out[name] = out.get(name, 0.0) + ns
    n = max(1, summary.n_devices)
    return {k: v * 1e-9 / n for k, v in out.items()}


def idle_host_share(summary) -> Optional[float]:
    """Share of the window, in %, in which the device is idle while the
    innermost host span is host work or a garbage collection."""
    if not window_spans(summary, HOST_WORK | {GC}):
        return None
    idle = idle_by_span(summary)
    return 100.0 * sum(v for k, v in idle.items()
                       if k in HOST_WORK or k == GC) / summary.window_s


def stat_share(spans: Iterable[Span], name: str, part: str,
               whole: str) -> Optional[float]:
    """Sum of stat `part` over sum of stat `whole`, in %, over the spans
    called `name`."""
    spans = [s for s in spans if s.name == name]
    total = sum(s.stats.get(whole, 0) for s in spans)
    if not total:
        return None
    return 100.0 * sum(s.stats.get(part, 0) for s in spans) / total


def lane_use(spans: Iterable[Span]) -> Optional[float]:
    """Decode lanes committed over lanes computed, in %."""
    return stat_share(spans, "serve.decode.dispatch", "committed", "lanes")


def chunk_fill(spans: Iterable[Span]) -> Optional[float]:
    """Prompt tokens in the chunks over tokens computed, in %."""
    return stat_share(spans, "serve.chunk.dispatch", "tokens_valid",
                      "tokens_computed")
