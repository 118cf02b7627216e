"""The probe kernel's share of its roofline, in percent.

The work is what the algorithm needs, counted from the cell's shapes and
never from the implementation: a probe of m reads against n sorted
float32 keys reads the n keys and the m reads and writes m int32 ranks,
4n + 8m bytes; its m * log2(n) compares are negligible beside that.  The
least time is those bytes over the chip's HBM bandwidth; the share is
that least time over the measured device time of one kernel call (the
mean over the window's calls).  A later kernel that replaces this one is
judged on the same work.  One call per environment step (`run_reads`);
the insert path's predecessor search does not go through the kernel.

The time is that of the Pallas custom call alone.  The probe's routing
of reads to tiles runs before the call, in `predecessor_positions`, as
fused operations that the program names nowhere, so it is not counted:
a change that moves that routing into the kernel lowers this share
while the end-to-end rate gains, and a faster routing never shows here.
Once the program puts a named scope on `predecessor_positions`, the
whole probe (routing and kernel) is to be timed against the same bytes.
"""
PATTERN = r"^%[\w.]*probe[\w.]* = .*custom_call_target=\"tpu_custom_call\""
LINE = "XLA Ops"
UNIT = "%"
LAYER = "probe kernel"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"


def min_bytes(n_keys: int, reads: int) -> int:
    return 4 * n_keys + 8 * reads


def share(n_keys: int, reads: int, hbm_bw: float, call_s: float) -> float:
    return 100.0 * min_bytes(n_keys, reads) / hbm_bw / call_s


def read(ctx):
    calls = ctx.trace.count(PATTERN, LINE)
    if calls == 0 or ctx.peaks is None:
        return None
    call_s = ctx.trace.device_s(PATTERN, LINE) / calls
    return share(ctx.shapes["n_keys"], ctx.shapes["reads"],
                 ctx.peaks.hbm_bw, call_s)
