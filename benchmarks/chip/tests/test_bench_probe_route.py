"""`probe_route_device_ms_per_step`: the self time of the operations under
`pool_tick` and `probe_route`, per useful step, on synthetic events; None
on a trace whose operations carry no `probe_route` scope (the program
before the routing was scoped) and on one without the program's marks."""
import pytest

DEV = "/device:TPU:0"
HOST = "/host:CPU"
NAME = "probe_route_device_ms_per_step"


def _trace(route_scoped: bool = True):
    """One window: a reset (program 7) that routes too, and a tick
    (program 9) whose probe routes, groups and runs the kernel."""
    import program_trace as PT
    import trace_reduce as TR

    def op(name, s, d):
        return TR.Event(DEV, "XLA Ops", name, s, d)
    events = [
        TR.Event(HOST, "python", "bench.window", 0, 10_000),
        TR.Event(HOST, "python", "bench.step", 0, 9_000),
        TR.Event(DEV, "XLA Modules", "jit_core(7)", 100, 900),
        op("%r.1 fusion", 100, 700),                 # reset, route
        TR.Event(DEV, "XLA Modules", "jit_scan_core(9)", 1_000, 6_000),
        op("%while.2 while", 1_000, 5_000),          # no tf_op
        op("%b.3 fusion", 1_000, 1_000),             # env_build
        op("%rt.4 fusion", 2_000, 300),              # route
        op("%rt.5 fusion", 2_300, 60),               # route (clip)
        op("%s.6 sort", 2_360, 240),                 # grouping
        op("%k.7 custom-call", 2_600, 200),          # kernel
        op("%x.8 fusion", 2_800, 500),               # reads, not probe
        op("%c.9 copy", 6_600, 400),                 # no tf_op
        TR.Event(DEV, "XLA Modules", "jit_scan_core(9)", 9_900, 300),
        op("%rt.10 fusion", 9_990, 40),              # route, 10 ns in
    ]
    tick = "jit(scan_core)/pool_tick/while/body/env_reads/probe/"
    route = "jit(probe_ranks)/probe_route/" if route_scoped else \
        "jit(probe_ranks)/"
    scopes = {("7", "%r.1 fusion"):
              "jit(core)/env_reset/env_reads/probe/" + route + "le:",
              ("9", "%b.3 fusion"): "jit(scan_core)/pool_tick/while/body/"
                                    "env_build/scatter-add:",
              ("9", "%rt.4 fusion"): tick + route + "reduce_sum:",
              ("9", "%rt.5 fusion"): tick + route + "jit(clip)/min:",
              ("9", "%s.6 sort"): tick + "jit(probe_ranks)/sort:",
              ("9", "%k.7 custom-call"): tick + "jit(probe_ranks)/probe:",
              ("9", "%x.8 fusion"): "jit(scan_core)/pool_tick/while/body/"
                                    "env_reads/mul:",
              ("9", "%rt.10 fusion"): tick + route + "reduce_sum:"}
    red, spans = TR.reduce(events)
    return red, spans, PT.ProgramTrace([], scopes)


class _Ctx:
    def __init__(self, red, spans, program, steps):
        self.trace = red
        self.spans = spans
        self.program = program
        self.counters = {"steps": steps, "ticks": 1}


def _read(ctx):
    import spec
    return spec.load_reader(NAME).read(ctx)


@pytest.mark.parametrize("steps", [1, 4])
def test_reads_the_routes_self_time_per_step(steps):
    red, spans, pt = _trace()
    # the tick's 300 + 60 ns and the 10 ns of the last op inside the
    # window; not the reset's route, the sort or the kernel
    assert pt.scoped_s(red, "pool_tick", "probe_route") == \
        pytest.approx(370e-9)
    assert _read(_Ctx(red, spans, pt, steps)) == \
        pytest.approx(1e3 * 370e-9 / steps)


def test_reads_none_without_the_scope():
    import program_trace as PT
    red, spans, pt = _trace(route_scoped=False)
    assert pt.scoped_s(red, "pool_tick", "probe") is not None
    assert _read(_Ctx(red, spans, pt, 2)) is None
    assert _read(_Ctx(red, spans, PT.ProgramTrace([], {}), 2)) is None
    red, spans, pt = _trace()
    assert _read(_Ctx(red, spans, pt, 0)) is None


def test_declared_as_the_benchmark_lists_it():
    import spec
    r = spec.load_reader(NAME)
    entry, = [m for m in spec.load_json(spec.ROOT / "BENCHMARK.json")
              ["per_layer"] if m["name"] == NAME]
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry["workloads"] == ["alex-mix-readheavy-batch"]
