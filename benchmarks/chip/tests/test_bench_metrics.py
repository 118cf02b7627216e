"""The metric arithmetic: the probe's work from the cell's shapes
against the peak table, and a rate over a window that closes on a tick
boundary."""
import time

import pytest

from conftest import BENCH


def test_probe_bytes_from_shapes_against_the_peak_table():
    import peaks
    import spec
    mod = spec.load_reader("probe_roofline")
    n, m = 1 << 20, 786_432
    assert mod.min_bytes(n, m) == 4 * n + 8 * m == 10_485_760
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e.hbm_bw == 819e9
    least = mod.min_bytes(n, m) / v5e.hbm_bw            # ~12.8 us
    assert mod.share(n, m, v5e.hbm_bw, least) == pytest.approx(100.0)
    assert mod.share(n, m, v5e.hbm_bw, 10 * least) == pytest.approx(10.0)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_probe_reader_reads_the_trace():
    import spec
    import trace_reduce as TR
    mod = spec.load_reader("probe_roofline")
    dev = "/device:TPU:0"
    kernel = ('%probe_ranks.10 = s32[2048,768]{1,0} custom-call(f32[2048,512]'
              '{1,0} %a), custom_call_target="tpu_custom_call"')
    other = ('%custom-call.7 = f32[8]{0} custom-call(f32[4]{0} %b), '
             'custom_call_target="ConcatBitcast"')
    ev = [TR.Event("/host:CPU", "python", "bench.window", 0, 1_000_000),
          TR.Event(dev, "XLA Ops", kernel, 100, 1000),
          TR.Event(dev, "XLA Ops", kernel, 5000, 3000),
          TR.Event(dev, "XLA Ops", other, 9000, 500)]
    red, _ = TR.reduce(ev)

    class Ctx:
        trace = red
        shapes = {"n_keys": 1000, "reads": 500}
        peaks = type("P", (), {"hbm_bw": 1e9})()
    # 8,000 bytes at 1 GB/s = 8 us against a mean call of 2 us
    assert mod.read(Ctx) == pytest.approx(400.0)
    Ctx.trace, _ = TR.reduce(ev[:1] + ev[3:])
    assert mod.read(Ctx) is None                 # nothing to read


class _FakeService:
    """Each tick takes 50 ms and does 4 steps; request ids retire at
    once."""
    def __init__(self):
        self.results = {}
        self.rid = 0

    def submit(self, *a, **kw):
        self.rid += 1
        return self.rid

    def step(self):
        time.sleep(0.05)
        for r in range(1, self.rid + 1):
            self.results[r] = {}
        return 4


def test_rate_over_a_window_that_ends_on_a_tick_boundary():
    import numpy as np

    import cell as C
    import traffic as T
    ring = [T.Request(0, np.zeros(4), np.zeros(2), np.zeros(2))]
    loop = C.ClosedLoop(_FakeService(), ring, budget=1)
    win = C.run_window(loop, outstanding=2, seconds=0.12)
    # the third tick crosses 0.12 s; the window closes at its end
    assert win.ticks == 3 and win.steps == 12
    assert 0.15 <= win.seconds < 0.3
    assert len(win.completed) >= 2
