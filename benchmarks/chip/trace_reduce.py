"""Reduction of a profiler trace to device busy time, per-program device
time and idle gaps attributed to the host's spans.

A traced run records one window with `jax.profiler.start_trace`.  The
profiler writes an `.xplane.pb`; `load` reads it with
`jax.profiler.ProfileData` into plain events: (plane, line, name,
start_ns, duration_ns).  Device planes are `/device:TPU:<i>`; on them the
line "XLA Ops" holds one event per operation that ran and "XLA Modules"
one per program execution.  Host threads carry the benchmark's own
`TraceAnnotation` spans (`bench.*`).  Everything after `load` works on
the plain events, so the reduction is tested on a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_SPAN = re.compile(r"^bench\.")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> list[Event]:
    """Device events of the op and module lines, and the benchmark's
    host spans, from an `.xplane.pb`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not HOST_SPAN.match(ev.name):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return out


def save_events(events: list[Event], path: Path):
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: Path) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def union(intervals) -> list[tuple[int, int]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced."""
    window: tuple[int, int]            # host span bench.window, ns
    devices: list[str]
    events: list[Event]                # device events inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, plane: str) -> list[tuple[int, int]]:
        iv = (_clip(e.start_ns, e.end_ns, *self.window) for e in self.events
              if e.plane == plane and e.line == OPS_LINE)
        return union(x for x in iv if x is not None)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for d in self.devices for s, e in self._busy(d))
        return tot / len(self.devices) / 1e9

    def matching(self, pattern: str, line: str = MODULES_LINE):
        rx = re.compile(pattern)
        return [e for e in self.events
                if e.line == line and rx.search(e.name)]

    def device_s(self, pattern: str, line: str = MODULES_LINE) -> float:
        """Device seconds of the events whose name matches `pattern`,
        clipped to the window, summed over devices."""
        tot = 0
        for e in self.matching(pattern, line):
            iv = _clip(e.start_ns, e.end_ns, *self.window)
            if iv is not None:
                tot += iv[1] - iv[0]
        return tot / 1e9

    def count(self, pattern: str, line: str = MODULES_LINE) -> int:
        return len(self.matching(pattern, line))

    def top_ops(self, n: int = 10):
        """[name, seconds] of the `n` operations with the most self time
        on the first device: an operation's time less that of the
        operations nested in it (a loop's body ops are events of their
        own), clipped to the window; names shortened by `short`."""
        if not self.devices:
            return []
        ops = sorted((e for e in self.events if e.line == OPS_LINE
                      and e.plane == self.devices[0]),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        self_ns: dict[str, int] = {}
        stack: list[list] = []          # [event, time covered by children]
        lo, hi = self.window

        def close(item):
            ev, child = item
            iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if iv is not None:
                k = short(ev.name)
                self_ns[k] = self_ns.get(k, 0) + max(
                    0, iv[1] - iv[0] - child)
        for e in ops:
            while stack and stack[-1][0].end_ns <= e.start_ns:
                close(stack.pop())
            if stack:
                iv = _clip(e.start_ns, e.end_ns, lo, hi)
                if iv is not None:
                    stack[-1][1] += iv[1] - iv[0]
            stack.append([e, 0])
        while stack:
            close(stack.pop())
        return sorted(([k, v / 1e9] for k, v in self_ns.items()),
                      key=lambda kv: -kv[1])[:n]

    def closing_modules(self, spans: list[Event], span_name: str,
                        pattern: str) -> set[str]:
        """Names of the modules matching `pattern` that end last inside
        each host span named `span_name`: the program that the span's
        blocking fetch waited for."""
        rx = re.compile(pattern)
        mods = [e for e in self.events
                if e.line == MODULES_LINE and rx.search(e.name)]
        out = set()
        for sp in spans:
            if sp.name != span_name:
                continue
            inside = [m for m in mods
                      if sp.start_ns <= m.end_ns <= sp.end_ns]
            if inside:
                out.add(max(inside, key=lambda m: m.end_ns).name)
        return out

    def device_s_of(self, names: set[str]) -> float:
        """Device seconds of the module executions named in `names`."""
        tot = 0
        for e in self.events:
            if e.line == MODULES_LINE and e.name in names:
                iv = _clip(e.start_ns, e.end_ns, *self.window)
                if iv is not None:
                    tot += iv[1] - iv[0]
        return tot / 1e9

    def idle_gaps(self, host_spans: list[Event], n: int = 10):
        """The `n` longest gaps with no operation on the first device,
        each named by the innermost benchmark span that covers the most
        of it ("host:untraced" where none does): [name, seconds]."""
        if not self.devices:
            return []
        busy = self._busy(self.devices[0])
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            out.append([_attribute(host_spans, s, e), (e - s) / 1e9])
        return out


_HLO_OP = re.compile(r"^(%[\w.\-]+) = .*?\b([a-z][\w\-]*)\(")


def short(name: str) -> str:
    """An HLO op's instruction name and opcode ("%fusion.12 fusion"),
    without its shapes and operands; other names as they are."""
    m = _HLO_OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _attribute(spans: list[Event], s: int, e: int) -> str:
    best, best_key = "host:untraced", (0, 0)
    for sp in spans:
        if sp.name == "bench.window":
            continue
        iv = _clip(sp.start_ns, sp.end_ns, s, e)
        if iv is None:
            continue
        # most overlap first, then the shortest (innermost) span
        key = (iv[1] - iv[0], -sp.dur_ns)
        if key > best_key:
            best, best_key = sp.name, key
    return best


def reduce(events: list[Event]) -> tuple[Reduced, list[Event]]:
    """Split events into the window's device events and the host spans;
    the window is the benchmark's `bench.window` span."""
    spans = [e for e in events if not DEVICE_PLANE.match(e.plane)]
    windows = [e for e in spans if e.name == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w = max(windows, key=lambda e: e.dur_ns)
    window = (w.start_ns, w.end_ns)
    dev = [e for e in events if DEVICE_PLANE.match(e.plane)
           and _clip(e.start_ns, e.end_ns, *window) is not None]
    devices = sorted({e.plane for e in events
                      if DEVICE_PLANE.match(e.plane)})
    return Reduced(window, devices, dev), spans

