"""`leaf_fit_device_ms_per_step` and `probe_overflow_device_ms_per_step`:
the self time of the operations under `pool_tick` and `leaf_fit` (the
exact least-squares fits of the index build) or `probe_overflow` (the
probe's overflow chunks), per useful step, on synthetic events; None on
a trace whose operations carry neither scope (the program before they
were scoped) and on one without the program's marks."""
import pytest

DEV = "/device:TPU:0"
HOST = "/host:CPU"
LEAF_FIT = "leaf_fit_device_ms_per_step"
OVERFLOW = "probe_overflow_device_ms_per_step"


def _trace(scoped: bool = True):
    """One window: a reset (program 7) that fits too, and a tick
    (program 9) that builds, fits, probes and answers the overflow in a
    loop whose body ops carry the scope."""
    import program_trace as PT
    import trace_reduce as TR

    def op(name, s, d):
        return TR.Event(DEV, "XLA Ops", name, s, d)
    events = [
        TR.Event(HOST, "python", "bench.window", 0, 10_000),
        TR.Event(HOST, "python", "bench.step", 0, 9_000),
        TR.Event(DEV, "XLA Modules", "jit_core(7)", 100, 900),
        op("%r.1 fusion", 100, 700),                 # reset, fit
        TR.Event(DEV, "XLA Modules", "jit_scan_core(9)", 1_000, 6_000),
        op("%while.2 while", 1_000, 5_000),          # no tf_op
        op("%b.3 fusion", 1_000, 200),               # env_build, decode
        op("%f.4 scatter", 1_200, 500),              # fit
        op("%f.5 fusion", 1_700, 100),               # fit
        op("%e.6 fusion", 1_800, 200),               # segment errors
        op("%k.7 custom-call", 2_600, 200),          # kernel
        op("%o.8 while", 2_800, 400),                # overflow loop
        op("%o.9 fusion", 2_850, 300),               # overflow body
        op("%x.10 fusion", 3_200, 500),              # reads, not probe
        op("%c.11 copy", 6_600, 400),                # no tf_op
        TR.Event(DEV, "XLA Modules", "jit_scan_core(9)", 9_900, 300),
        op("%o.12 fusion", 9_990, 40),               # overflow, 10 ns in
    ]
    body = "jit(scan_core)/pool_tick/while/body/"
    fit = "leaf_fit/" if scoped else ""
    over = "probe_overflow/" if scoped else ""
    probe = body + "env_reads/probe/jit(probe_ranks)/"
    scopes = {("7", "%r.1 fusion"): "jit(core)/env_reset/env_build/" + fit
              + "scatter-add:",
              ("9", "%b.3 fusion"): body + "env_build/mul:",
              ("9", "%f.4 scatter"): body + "env_build/" + fit
              + "scatter-add:",
              ("9", "%f.5 fusion"): body + "env_build/" + fit + "div:",
              ("9", "%e.6 fusion"): body + "env_build/abs:",
              ("9", "%k.7 custom-call"): probe + "probe:",
              ("9", "%o.9 fusion"): probe + over + "while/body/gather:",
              ("9", "%x.10 fusion"): body + "env_reads/mul:",
              ("9", "%o.12 fusion"): probe + over + "while/body/gather:"}
    red, spans = TR.reduce(events)
    return red, spans, PT.ProgramTrace([], scopes)


class _Ctx:
    def __init__(self, red, spans, program, steps):
        self.trace = red
        self.spans = spans
        self.program = program
        self.counters = {"steps": steps, "ticks": 1}


def _read(name, ctx):
    import spec
    return spec.load_reader(name).read(ctx)


@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("name,scope,ns", [
    # the tick's two fit ops; not the reset's fit, the decode or the
    # error bounds
    (LEAF_FIT, "leaf_fit", 600),
    # the loop body's 300 ns and the 10 ns of the last op inside the
    # window; not the kernel or the rest of the reads
    (OVERFLOW, "probe_overflow", 310)])
def test_reads_the_scopes_self_time_per_step(name, scope, ns, steps):
    red, spans, pt = _trace()
    assert pt.scoped_s(red, "pool_tick", scope) == pytest.approx(ns * 1e-9)
    assert _read(name, _Ctx(red, spans, pt, steps)) == \
        pytest.approx(1e3 * ns * 1e-9 / steps)


@pytest.mark.parametrize("name", [LEAF_FIT, OVERFLOW])
def test_reads_none_without_the_scope(name):
    import program_trace as PT
    red, spans, pt = _trace(scoped=False)
    assert pt.scoped_s(red, "pool_tick", "env_build") is not None
    assert _read(name, _Ctx(red, spans, pt, 2)) is None
    assert _read(name, _Ctx(red, spans, PT.ProgramTrace([], {}), 2)) is None
    red, spans, pt = _trace()
    assert _read(name, _Ctx(red, spans, pt, 0)) is None


@pytest.mark.parametrize("name,cells", [
    (LEAF_FIT, ["alex-mix-readheavy-batch", "carmi-osm-readheavy-batch"]),
    (OVERFLOW, ["carmi-osm-readheavy-batch"])])
def test_declared_as_the_benchmark_lists_it(name, cells):
    import spec
    r = spec.load_reader(name)
    entry, = [m for m in spec.load_json(spec.ROOT / "BENCHMARK.json")
              ["per_layer"] if m["name"] == name]
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry["workloads"] == cells
