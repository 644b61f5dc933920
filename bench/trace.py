"""Host spans, the profiler trace, and its reduction to device metrics.

The benchmark puts its own host spans (`span`) around its calls into the
program: `train.feed`, `train.dispatch`, `train.block`, `serve.generate`,
`serve.step`, and `bench.window` around the measured window. With tracing
on they land in the profiler's trace next to the device's operations.

`load` reads the `.xplane.pb` the JAX profiler writes into plain events;
everything after that works on plain events, so the tests can hand-build
a trace:

* busy time is the union of the intervals in which a synchronous device
  operation ran (the "XLA Ops" line of each `/device:TPU:*` plane), inside
  the window, averaged over the devices;
* idle gaps are the holes in that union, each labelled by the benchmark
  span that overlaps it most on the host;
* device time per name sums operation (or program) durations.

Host and device clocks in one trace agree to about a millisecond, so a
label is trustworthy for gaps longer than that.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIXES = ("train.", "serve.", "bench.")
WINDOW_SPAN = "bench.window"
# control-flow ops whose span holds the ops of their body, which the line
# lists too: left out of the breakdown so that no time counts twice
CONTAINERS = frozenset({"while", "conditional", "call"})


def span(name: str):
    """A host span in the profiler's trace (costs about a microsecond when
    no trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclass(frozen=True)
class Event:
    start_ns: float
    dur_ns: float
    name: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class DeviceLines:
    ops: List[Event] = field(default_factory=list)        # synchronous ops
    async_ops: List[Event] = field(default_factory=list)  # start..done spans
    modules: List[Event] = field(default_factory=list)    # whole programs


@dataclass
class Trace:
    devices: Dict[str, DeviceLines]
    spans: List[Event]                  # the benchmark's host spans


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a profiler trace into device lines and benchmark spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, DeviceLines] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dl = devices.setdefault(plane.name, DeviceLines())
            for line in plane.lines:
                target = {"XLA Ops": dl.ops, "Async XLA Ops": dl.async_ops,
                          "XLA Modules": dl.modules}.get(line.name)
                if target is None:
                    continue
                target.extend(Event(e.start_ns, e.duration_ns, e.name)
                              for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.start_ns, e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return Trace(devices, spans)


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

def window_of(trace: Trace) -> Tuple[float, float]:
    """The measured window in trace time: the `bench.window` span, else the
    extent of every device operation."""
    w = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if w:
        return w[0].start_ns, w[0].end_ns
    evs = [e for d in trace.devices.values() for e in d.ops]
    if not evs:
        raise ValueError("trace holds no device operation")
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pieces."""
    pieces = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                    if b > lo and a < hi)
    out: List[Tuple[float, float]] = []
    for a, b in pieces:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merge(((e.start_ns, e.end_ns)
                                        for e in events), lo, hi))


def idle_gaps(events: Sequence[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The holes in the busy union inside [lo, hi], longest first."""
    busy = merge(((e.start_ns, e.end_ns) for e in events), lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def label_gap(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The benchmark span overlapping the gap most ('none' where none
    does; the window span itself never labels)."""
    best, label = 0.0, "none"
    for s in spans:
        if s.name == WINDOW_SPAN:
            continue
        ov = min(gap[1], s.end_ns) - max(gap[0], s.start_ns)
        if ov > best:
            best, label = ov, s.name
    return label


_OP = re.compile(r"^%?([^\s=]+)")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`;
    `jit_step(1234)` -> `jit_step`."""
    m = _OP.match(event_name)
    name = m.group(1) if m else event_name
    return name.split("(")[0]


def base_name(event_name: str) -> str:
    """Op name without its numeric instance suffix: `fusion.12` -> `fusion`."""
    return _SUFFIX.sub("", op_name(event_name))


def in_window(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events that start inside the window."""
    return [e for e in events if lo <= e.start_ns < hi]


def time_by_name(events: Sequence[Event], lo: float,
                 hi: float) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in in_window(events, lo, hi):
        k = base_name(e.name)
        out[k] = out.get(k, 0.0) + e.dur_ns
    return out


@dataclass
class Summary:
    """What the readers get from a trace."""
    trace: Trace
    lo: float
    hi: float

    @property
    def n_devices(self) -> int:
        return len(self.trace.devices)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the devices."""
        if not self.trace.devices:
            return 0.0
        return sum(busy_ns(d.ops, self.lo, self.hi)
                   for d in self.trace.devices.values()) \
            * 1e-9 / self.n_devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_by_device(self, pattern: str,
                      line: str = "ops") -> List[List[Event]]:
        """Per device, its window events whose op base name matches."""
        rx = re.compile(pattern)
        return [[e for e in in_window(getattr(d, line), self.lo, self.hi)
                 if rx.fullmatch(base_name(e.name))]
                for d in self.trace.devices.values()]

    def ops(self, pattern: str, line: str = "ops") -> List[Event]:
        """Window events of every device whose op base name matches."""
        return [e for evs in self.ops_by_device(pattern, line) for e in evs]

    def breakdown(self, top: int = 10) -> dict:
        """Longest device operations (seconds summed over the window,
        averaged over the devices) and longest idle gaps with the host
        span open in each."""
        tot: Dict[str, float] = {}
        for d in self.trace.devices.values():
            for k, v in time_by_name(d.ops, self.lo, self.hi).items():
                if k not in CONTAINERS:
                    tot[k] = tot.get(k, 0.0) + v
        n = max(1, self.n_devices)
        ops = sorted(((k, v * 1e-9 / n) for k, v in tot.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.trace.devices.values():
            gaps.extend(idle_gaps(d.ops, self.lo, self.hi))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[label_gap(g, self.trace.spans),
                               (g[1] - g[0]) * 1e-9] for g in gaps]}


def summarize(trace: Trace, window: Optional[Tuple[float, float]] = None
              ) -> Summary:
    lo, hi = window if window is not None else window_of(trace)
    return Summary(trace, lo, hi)
