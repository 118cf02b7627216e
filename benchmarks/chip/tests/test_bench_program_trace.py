"""What the program writes into a traced run (`program_trace.py`) and
the five readers of it: the scope paths read from an `.xplane.pb`'s wire
format, self time by scope, host spans, on synthetic events and on a
slice of a traced window recorded on a TPU v5e
(`data/trace_slice_scoped.json`); on a trace without the program's
marks, such as the parent's, every new reader reads None."""
import json

import pytest

from conftest import BENCH

DEV = "/device:TPU:0"
HOST = "/host:CPU"
NEW = ("env_build_device_ms_per_step", "env_reads_device_ms_per_step",
       "env_inserts_device_ms_per_step", "probe_whole_roofline",
       "host_ms_per_tick")
KERNEL = ('%probe_ranks.3 = s32[8,768]{1,0} custom-call(f32[8,512]{1,0} '
          '%a), custom_call_target="tpu_custom_call"')


# ------------------------------------------------- protobuf, by hand
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num: int, v: int) -> bytes:
    return _varint(num << 3) + _varint(v)


def _bytes(num: int, v) -> bytes:
    v = v.encode() if isinstance(v, str) else v
    return _varint(num << 3 | 2) + _varint(len(v)) + v


def _plane(name, ops, tf_as_ref=False):
    """An XPlane: stat metadata 1 "tf_op", 2 "program_id" (and 3, the
    path itself, where `tf_as_ref`), one event metadata per op."""
    stat_md = [(1, "tf_op"), (2, "program_id")]
    if tf_as_ref:
        stat_md.append((3, ops[0][2]))
    body = _bytes(2, name) + _bytes(3, _bytes(2, "XLA Ops"))   # a line
    for sid, sname in stat_md:
        md = _int(1, sid) + _bytes(2, sname)
        body += _bytes(5, _int(1, sid) + _bytes(2, md))
    for i, (pid, op, path) in enumerate(ops, 1):
        tf = (_int(1, 1) + _int(7, 3)) if tf_as_ref else \
            (_int(1, 1) + _bytes(5, path))
        md = (_int(1, i) + _bytes(2, op) + _bytes(4, op[:9])
              + _bytes(5, tf) + _bytes(5, _int(1, 2) + _int(4, pid)))
        body += _bytes(4, _int(1, i) + _bytes(2, md))
    return _bytes(1, body)


def test_scope_paths_from_the_wire_format(tmp_path):
    import program_trace as PT
    ops = [(42, "%fusion.1 = f32[8]{0} fusion(%p)",
            "jit(scan_core)/pool_tick/while/body/env_build/add:"),
           (43, "%fusion.1 = f32[8]{0} fusion(%p)",
            "jit(core)/env_reset/env_build/add:")]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane(DEV, ops)
                     + _plane("/device:TPU:1", ops[:1], tf_as_ref=True)
                     + _plane(HOST, ops) + _bytes(2, "an error"))
    got = PT.scope_paths(path)
    # one name in two programs: two paths; the host plane adds nothing
    assert got == {("42", ops[0][1]): ops[0][2],
                   ("43", ops[1][1]): ops[1][2]}
    assert PT._segments(ops[0][2])[-3:] == ("body", "env_build", "add")


# -------------------------------------------------- synthetic events
def _synthetic():
    """One window: a reset module (program 7) and a tick module
    (program 9) on the device; host spans of one step and one tick."""
    import program_trace as PT
    import trace_reduce as TR

    def op(name, s, d):
        return TR.Event(DEV, "XLA Ops", name, s, d)
    events = [
        TR.Event(HOST, "python", "bench.window", 0, 10_000),
        TR.Event(HOST, "python", "bench.step", 0, 9_000),
        TR.Event(DEV, "XLA Modules", "jit_core(7)", 100, 900),
        op("%r.1 fusion", 100, 900),                 # reset, env_build
        TR.Event(DEV, "XLA Modules", "jit_scan_core(9)", 1_000, 6_000),
        op("%while.2 while", 1_000, 5_000),          # no tf_op
        op("%b.3 fusion", 1_000, 1_000),             # env_build
        op("%q.4 fusion", 2_000, 800),               # reads, probe
        op(KERNEL, 2_800, 200),                      # reads, probe
        op("%x.5 fusion", 3_000, 500),               # reads, not probe
        op("%i.6 fusion", 3_500, 1_500),             # inserts
        op("%o.7 fusion", 6_000, 600),               # env_observe
        op("%c.8 copy", 6_600, 400),                 # no tf_op
    ]
    tick = "jit(scan_core)/pool_tick/while/body/"
    scopes = {("7", "%r.1 fusion"): "jit(core)/env_reset/env_build/a:",
              ("9", "%b.3 fusion"): tick + "env_build/scatter-add:",
              ("9", "%q.4 fusion"): tick + "env_reads/probe/gather:",
              ("9", KERNEL): tick + "env_reads/probe/probe_ranks:",
              ("9", "%x.5 fusion"): tick + "env_reads/mul:",
              ("9", "%i.6 fusion"): tick + "env_inserts/jit(search)/lt:",
              ("9", "%o.7 fusion"): tick + "env_observe/reduce_sum:"}
    spans = [PT.Span("litune.step", 0, 8_000, {}),
             PT.Span("litune.admit.sync", 500, 600, {}),
             PT.Span("litune.tick", 900, 7_000,
                     {"k": 8, "slots": 4, "active": 4}),
             PT.Span("litune.tick.fetch", 1_100, 6_000, {}),
             PT.Span("litune.step", 20_000, 1_000, {})]   # after window
    red, bench_spans = TR.reduce(events)
    return red, bench_spans, PT.ProgramTrace(spans, scopes)


class _Ctx:
    def __init__(self, red, spans, program, steps=2):
        self.trace = red
        self.spans = spans
        self.program = program
        self.counters = {"steps": steps, "ticks": 1}
        self.shapes = {"n_keys": 1000, "reads": 500}
        self.peaks = type("P", (), {"hbm_bw": 1e9})()


def _read(name, ctx):
    import spec
    return spec.load_reader(name).read(ctx)


def test_self_time_by_scope_on_synthetic_events():
    red, spans, pt = _synthetic()
    # the while loop's own time is what its body ops leave: 0 here
    assert pt.scoped_s(red, "pool_tick") == pytest.approx(4_600e-9)
    assert pt.scoped_s(red, "pool_tick", "env_build") == \
        pytest.approx(1_000e-9)                      # not the reset's
    assert pt.scoped_s(red, "env_build") == pytest.approx(1_900e-9)
    assert pt.scoped_s(red, "pool_tick", "probe") == pytest.approx(1e-6)
    assert pt.scoped_s(red, "pool_tick", "agent") is None
    assert pt.scoped_modules(red, "pool_tick") == {"jit_scan_core(9)"}
    assert pt.scoped_modules(red, "pool_tick") == red.closing_modules(
        spans, "bench.step", r"^jit_(scan_)?core\(")

    ctx = _Ctx(red, spans, pt)
    assert _read("env_build_device_ms_per_step", ctx) == \
        pytest.approx(1e3 * 1_000e-9 / 2)
    assert _read("env_reads_device_ms_per_step", ctx) == \
        pytest.approx(1e3 * 1_500e-9 / 2)
    assert _read("env_inserts_device_ms_per_step", ctx) == \
        pytest.approx(1e3 * 1_500e-9 / 2)
    # 4n + 8m = 8,000 bytes at 1 GB/s = 8 us over one 1-us whole probe
    assert _read("probe_whole_roofline", ctx) == pytest.approx(800.0)
    # 8,000 ns of step less 600 + 6,000 of waits, over one tick
    assert _read("host_ms_per_tick", ctx) == pytest.approx(1.4e-3)


def test_new_readers_read_none_without_the_programs_marks():
    import program_trace as PT
    red, spans, _ = _synthetic()
    ctx = _Ctx(red, spans, PT.ProgramTrace([], {}))
    assert {n: _read(n, ctx) for n in NEW} == dict.fromkeys(NEW)


def test_run_trace_is_read_for_its_own_window_only(tmp_path):
    """Spans and their ids come back from a trace recorded here, and
    only for the context's window: a reader never reads an earlier
    run's trace."""
    import jax

    import program_trace as PT
    import trace_reduce as TR
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("litune.tick", k=8,
                                              slots=4):
                pass
    _, windows = PT.host_spans(TR.find_xplane(tmp_path))
    window, = windows
    pt = PT.read(tmp_path, window)
    assert [(s.name, s.ids) for s in pt.spans] == \
        [("litune.tick", {"k": 8, "slots": 4})]
    assert PT.read(tmp_path, (window[0], window[1] + 1)).spans == []
    assert PT.read(tmp_path / "none", window).spans == []
    ctx = type("C", (), {"program": pt})()
    assert PT.of(ctx) is pt


# ---------------------------------------------------- recorded slices
def _slice():
    import program_trace as PT
    import trace_reduce as TR
    path = BENCH / "tests" / "data" / "trace_slice_scoped.json"
    events, pt = PT.load_slice(path)
    red, spans = TR.reduce(events)
    meta = json.loads(path.with_suffix(".meta.json").read_text())
    return red, spans, pt, meta


def _peaks():
    import peaks
    return peaks.peaks("TPU v5 lite")


def test_new_readers_on_the_recorded_slice():
    red, spans, pt, meta = _slice()
    ctx = _Ctx(red, spans, pt, steps=meta["steps"])
    ctx.shapes = meta["shapes"]
    ctx.peaks = _peaks()
    assert meta["kernel_calls"] >= 1
    assert set(meta["expected"]) == set(NEW)
    for name, want in meta["expected"].items():
        assert _read(name, ctx) == pytest.approx(want, rel=1e-9), name


def test_pool_tick_scope_finds_the_heuristics_modules():
    """On the chip's trace the modules whose operations carry
    `pool_tick` are the modules that `tick_device_ms_per_step`'s
    heuristic finds: the last to end inside each `bench.step`."""
    red, spans, pt, meta = _slice()
    tick = __import__("spec").load_reader("tick_device_ms_per_step")
    heuristic = red.closing_modules(spans, "bench.step", tick.FAMILY)
    assert pt.scoped_modules(red, "pool_tick") <= heuristic
    assert sorted(heuristic) == meta["tick_modules"]


def test_new_readers_read_none_on_the_old_slice():
    """The slice recorded before the program wrote scopes and spans."""
    import program_trace as PT
    import trace_reduce as TR
    events = TR.load_events(BENCH / "tests" / "data" / "trace_slice.json")
    red, spans = TR.reduce(events)
    ctx = _Ctx(red, spans, PT.ProgramTrace([], {}), steps=152)
    ctx.shapes = {"n_keys": 1 << 20, "reads": 786_432}
    ctx.peaks = _peaks()
    assert {n: _read(n, ctx) for n in NEW} == dict.fromkeys(NEW)
