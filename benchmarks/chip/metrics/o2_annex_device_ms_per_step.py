"""Device milliseconds of the O2 annex per useful tuning step: the
device time of the annex's programs inside the window, over the steps
the window completed.  The annex's programs: the offline fine-tune round
(`core.o2._finetune_program`, `jit_run`), the replay ring's page writes
and batch gathers, the capture extract, and the pooled assessments'
carry build and K-step programs (unfused, `jit_scan_core`, which in this
cell only the assessments run).  The assessments' resets share their
program with admission's and are counted in
`reset_device_ms_per_request`."""
PATTERN = (r"^jit_(run|_page_write|gather|_extract_episode_core|build|"
           r"scan_core)\(")
LINE = "XLA Modules"
UNIT = "ms/step"
LAYER = "O2 annex"
MOVES = "tune_steps_per_s"
SOURCE = "device_trace"


def read(ctx):
    steps = ctx.counters["steps"]
    if steps <= 0 or ctx.trace.count(PATTERN, LINE) == 0:
        return None
    return 1e3 * ctx.trace.device_s(PATTERN, LINE) / steps
