"""The trace reduction: busy-interval union, events matched by pattern,
idle gaps attributed to host spans; on synthetic events and on a small
slice of a trace recorded on a TPU v5e (`data/trace_slice.json`)."""
import json

import pytest

from conftest import BENCH

DEV = "/device:TPU:0"


def _ev(line, name, start, dur, plane=DEV):
    import trace_reduce as TR
    return TR.Event(plane, line, name, start, dur)


def test_union_merges_overlaps():
    import trace_reduce as TR
    assert TR.union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 13)]) == \
        [(0, 4), (5, 10), (12, 13)]


def test_busy_idle_and_attribution():
    import trace_reduce as TR
    host = "/host:CPU"
    events = [
        _ev("python", "bench.window", 0, 1000, host),
        _ev("python", "bench.step", 0, 600, host),
        _ev("python", "bench.submit", 600, 300, host),
        _ev("XLA Ops", "while.1", 100, 300),        # holds the next two
        _ev("XLA Ops", "fusion.1", 120, 200),
        _ev("XLA Ops", "fusion.2", 320, 80),
        _ev("XLA Ops", "custom-call", 700, 100),
        _ev("XLA Modules", "jit_step(1)", 100, 300),
        _ev("XLA Modules", "jit_step(1)", 700, 100),
        _ev("XLA Ops", "fusion.3", 2000, 100),      # after the window
    ]
    red, spans = TR.reduce(events)
    assert red.window == (0, 1000)
    assert red.busy_s == pytest.approx(400e-9)       # [100,400) + [700,800)
    assert red.window_s == pytest.approx(1e-6)
    assert red.count(r"^jit_step") == 2
    assert red.device_s(r"^jit_step") == pytest.approx(400e-9)
    gaps = red.idle_gaps(spans, 10)
    # gaps: [400,700) 300 ns, [800,1000) 200, [0,100) 100
    assert [round(s * 1e9) for _, s in gaps] == [300, 200, 100]
    assert gaps[0][0] == "bench.step"       # 200 ns of step, 100 of submit
    assert gaps[1][0] == "bench.submit"
    top = dict(red.top_ops(10))          # self time: nested ops excluded
    assert top == pytest.approx({"fusion.1": 200e-9, "custom-call": 100e-9,
                                 "fusion.2": 80e-9, "while.1": 20e-9})
    assert red.closing_modules(spans, "bench.step", r"^jit_") == \
        {"jit_step(1)"}


def test_recorded_trace_slice():
    """A slice of a traced window of `alex-mix-readheavy-batch` on one
    TPU v5e: the device plane, its op and module lines, and the host's
    spans are read as the metric readers expect."""
    import trace_reduce as TR
    path = BENCH / "tests" / "data" / "trace_slice.json"
    events = TR.load_events(path)
    red, spans = TR.reduce(events)
    assert red.devices == [DEV]
    assert 0 < red.busy_s <= red.window_s
    assert any(s.name == "bench.step" for s in spans)
    meta = json.loads((path.parent / "trace_slice.meta.json").read_text())
    for pattern, (line, count) in meta["counts"].items():
        assert red.count(pattern, line) == count, pattern
    # the probe kernel's pattern and the tick's are those the readers use
    import spec
    probe = spec.load_reader("probe_roofline")
    assert meta["counts"][probe.PATTERN][1] >= 1
    tick = spec.load_reader("tick_device_ms_per_step")
    assert sorted(red.closing_modules(spans, "bench.step", tick.FAMILY)) \
        == meta["tick_modules"]
    assert all(n.startswith("jit_scan_core(") for n in meta["tick_modules"])
