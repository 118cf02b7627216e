"""What the program itself writes into a traced run: its `litune.*` host
spans with their ids, and the scope path of each device operation.

`trace_reduce.load` keeps neither: it reads the benchmark's `bench.*`
spans and each operation's name and times.  The program's spans are
read here with `jax.profiler.ProfileData`, like the rest.  An
operation's scope path (its `op_name` metadata: the `jax.named_scope`s
it was traced under) is, in a TPU trace, the `tf_op` stat of the
operation's event *metadata*; the events themselves carry only their
offset and duration (TPU v5e), and `ProfileData` does not expose
metadata stats.  So `scope_paths` reads them from the `.xplane.pb`'s
protobuf wire format: `XSpace.planes` -> `XPlane.event_metadata` (id ->
`XEventMetadata` {name, stats}) and `XPlane.stat_metadata` (id -> stat
name).  A metadata name is the operation's event name on the "XLA Ops"
line, unique within one program only, so paths are keyed by (program
id, name); an operation's program is the module execution around it,
named `jit_<fn>(<program id>)` on the "XLA Modules" line.

Readers call `of(ctx)`.  A run's trace is `.runs/trace` beside this file
(`run.py`); it is read only if its `bench.window` span is the window of
`ctx.trace`, and once per run.  A test puts a `ProgramTrace` on the
context as `ctx.program` instead (`load_slice`).  On a trace of a
program that writes no spans or scopes, readers find nothing and
return None.
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

import trace_reduce as TR

RUN_TRACE = Path(__file__).resolve().parent / ".runs" / "trace"
SPAN = re.compile(r"^litune\.")
PROGRAM_ID = re.compile(r"\((\d+)\)$")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    dur_ns: int
    ids: dict

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class ProgramTrace:
    spans: list                    # litune.* host spans
    scopes: dict                   # (program id, op name) -> scope path
    _ops: list | None = None       # cached by `ops`

    def span_s(self, window, *names) -> float:
        """Seconds of the spans named `names`, clipped to `window`."""
        tot = 0
        for sp in self.spans:
            if sp.name in names:
                iv = TR._clip(sp.start_ns, sp.end_ns, *window)
                if iv is not None:
                    tot += iv[1] - iv[0]
        return tot / 1e9

    def span_count(self, window, name) -> int:
        lo, hi = window
        return sum(sp.name == name and lo <= sp.start_ns < hi
                   for sp in self.spans)

    def ops(self, red: TR.Reduced) -> list:
        """(scope segments, self ns, event) of each operation on the
        first device, clipped to the window: an operation's time less
        that of the operations nested in it, as `Reduced.top_ops`."""
        if self._ops is None:
            self._ops = _self_times(red, self.scopes)
        return self._ops

    def scoped_s(self, red: TR.Reduced, *segments) -> float | None:
        """Self seconds of the operations whose scope path holds every
        one of `segments`; None where no operation does."""
        hits = [ns for segs, ns, _ in self.ops(red)
                if all(s in segs for s in segments)]
        return sum(hits) / 1e9 if hits else None

    def scoped_count(self, red: TR.Reduced, pattern: str,
                     *segments) -> int:
        """Operations named like `pattern` under every one of
        `segments`."""
        rx = re.compile(pattern)
        return sum(1 for segs, _, e in self.ops(red)
                   if rx.search(e.name) and all(s in segs
                                                for s in segments))

    def scoped_modules(self, red: TR.Reduced, *segments) -> set:
        """Names of the module executions holding an operation under
        every one of `segments`."""
        mods = _modules(red)
        out = set()
        for segs, _, e in self.ops(red):
            if all(s in segs for s in segments):
                m = _module_at(mods, e.start_ns)
                if m is not None:
                    out.add(m.name)
        return out


def _segments(path: str) -> tuple:
    """A `tf_op` ("jit(f)/pool_tick/while/body/add:") as its segments."""
    head, sep, _ = path.rpartition(":")
    return tuple((head if sep else path).split("/"))


def _modules(red: TR.Reduced) -> list:
    if not red.devices:
        return []
    return sorted((e for e in red.events if e.line == TR.MODULES_LINE
                   and e.plane == red.devices[0]),
                  key=lambda e: e.start_ns)


def _module_at(mods: list, t: int):
    """The module execution holding time `t` (modules do not overlap on
    one device)."""
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid].start_ns <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1].start_ns <= t < mods[lo - 1].end_ns:
        return mods[lo - 1]
    return None


def _self_times(red: TR.Reduced, scopes: dict) -> list:
    if not red.devices or not scopes:
        return []
    mods = _modules(red)
    ops = sorted((e for e in red.events if e.line == TR.OPS_LINE
                  and e.plane == red.devices[0]),
                 key=lambda e: (e.start_ns, -e.dur_ns))
    lo, hi = red.window
    out, stack = [], []              # stack: [event, time of children]

    def close(item):
        ev, child = item
        iv = TR._clip(ev.start_ns, ev.end_ns, lo, hi)
        if iv is None:
            return
        m = _module_at(mods, ev.start_ns)
        pid = PROGRAM_ID.search(m.name).group(1) if m is not None else ""
        path = scopes.get((pid, ev.name))
        segs = _segments(path) if path else ()
        out.append((segs, max(0, iv[1] - iv[0] - child), ev))
    for e in ops:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            close(stack.pop())
        if stack:
            iv = TR._clip(e.start_ns, e.end_ns, lo, hi)
            if iv is not None:
                stack[-1][1] += iv[1] - iv[0]
        stack.append([e, 0])
    while stack:
        close(stack.pop())
    return out


# ------------------------------------------------------------ reading
def _varint(buf: bytes, i: int):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: ints
    for varints, bytes for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, v


def _map_entry(buf: bytes):
    key = val = None
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def scope_paths(xplane: Path) -> dict:
    """(program id, op name) -> `tf_op` of every operation metadata on
    the device planes of an `.xplane.pb`."""
    out = {}
    data = Path(xplane).read_bytes()
    for num, _, plane in _fields(data):
        if num != 1:                                  # XSpace.planes
            continue
        name, event_md, stat_names = "", [], {}
        for pnum, _, v in _fields(plane):
            if pnum == 2:                             # XPlane.name
                name = v.decode()
            elif pnum == 4:                           # event_metadata
                event_md.append(v)
            elif pnum == 5:                           # stat_metadata
                _, md = _map_entry(v)
                sid, sname = 0, ""
                for snum, _, sv in _fields(md or b""):
                    if snum == 1:
                        sid = sv
                    elif snum == 2:
                        sname = sv.decode()
                stat_names[sid] = sname
        if not TR.DEVICE_PLANE.match(name):
            continue
        want = {k for k, v in stat_names.items()
                if v in ("tf_op", "program_id")}
        if not want:
            continue
        for entry in event_md:
            _, md = _map_entry(entry)
            op, stats = "", {}
            for mnum, _, mv in _fields(md or b""):
                if mnum == 2:                         # XEventMetadata.name
                    op = mv.decode(errors="replace")
                elif mnum == 5:                       # XEventMetadata.stats
                    sid, val = 0, None
                    for xnum, _, xv in _fields(mv):
                        if xnum == 1:
                            sid = xv
                        elif xnum == 5:               # str_value
                            val = xv.decode(errors="replace")
                        elif xnum in (3, 4):          # uint64 / int64
                            val = xv
                        elif xnum == 7:               # ref_value
                            val = stat_names.get(xv, "")
                    if sid in want:
                        stats[stat_names[sid]] = val
            if stats.get("tf_op"):
                out[(str(stats.get("program_id", "")), op)] = \
                    stats["tf_op"]
    return out


def host_spans(xplane: Path) -> tuple[list, list]:
    """The program's `litune.*` spans, and the benchmark's
    `bench.window` spans, of the host planes."""
    from jax.profiler import ProfileData
    spans, windows = [], []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if TR.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if SPAN.match(ev.name):
                    spans.append(Span(ev.name, int(ev.start_ns),
                                      int(ev.duration_ns), dict(ev.stats)))
                elif ev.name == "bench.window":
                    windows.append((int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns)))
    return spans, windows


def read(trace_dir: Path, window) -> ProgramTrace:
    """The program's spans and scopes of the trace in `trace_dir`; empty
    unless its longest `bench.window` span is `window`."""
    try:
        xplane = TR.find_xplane(trace_dir)
    except FileNotFoundError:
        return ProgramTrace([], {})
    spans, windows = host_spans(xplane)
    longest = max(windows, key=lambda w: w[1] - w[0], default=None)
    if longest != tuple(window):
        return ProgramTrace([], {})
    return ProgramTrace(spans, scope_paths(xplane))


def of(ctx) -> ProgramTrace:
    pt = getattr(ctx, "program", None)
    if pt is None:
        pt = read(RUN_TRACE, ctx.trace.window)
        ctx.program = pt
    return pt


# ------------------------------------------------------ recorded slices
def save_slice(trace_dir: Path, path: Path, ops_s: float = 0.4):
    """A small slice of a traced run, for tests: every host span and
    module execution of the window, and the operations that start in
    `ops_s` seconds around the first probe-kernel call of the serving
    tick, with their scope paths.  Names are shortened by
    `trace_reduce.short`, but for the probe kernel's."""
    xplane = TR.find_xplane(trace_dir)
    events = TR.load(xplane)
    red, spans = TR.reduce(events)
    pt = read(trace_dir, red.window)
    mid = min(e.start_ns for segs, _, e in pt.ops(red)
              if "pool_tick" in segs and "probe" in segs
              and "tpu_custom_call" in e.name)
    lo = mid - int(ops_s / 2 * 1e9)
    hi = lo + int(ops_s * 1e9)
    mods = _modules(red)

    def name(n):
        return n if "tpu_custom_call" in n else TR.short(n)
    kept = [e for e in red.events
            if e.line == TR.MODULES_LINE
            or (e.line == TR.OPS_LINE and lo <= e.start_ns < hi)]
    rows = [[e.plane, e.line, name(e.name), e.start_ns, e.dur_ns]
            for e in spans + kept]
    used = {(PROGRAM_ID.search(m.name).group(1), e.name)
            for e in kept if e.line == TR.OPS_LINE
            for m in [_module_at(mods, e.start_ns)] if m is not None}
    scopes = [[pid, name(op), pt.scopes[(pid, op)]]
              for pid, op in sorted(used) if (pid, op) in pt.scopes]
    with open(path, "w") as f:
        json.dump({"events": rows,
                   "spans": [[s.name, s.start_ns, s.dur_ns, s.ids]
                             for s in pt.spans],
                   "scopes": scopes}, f)


def load_slice(path: Path):
    """(events, ProgramTrace) of a slice written by `save_slice`."""
    with open(path) as f:
        d = json.load(f)
    events = [TR.Event(*row) for row in d["events"]]
    spans = [Span(n, s, dur, ids) for n, s, dur, ids in d["spans"]]
    scopes = {(pid, op): path for pid, op, path in d["scopes"]}
    return events, ProgramTrace(spans, scopes)
