"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole run (agent, service, warm-up, window, the
reference check) at 4,096 keys on the CPU, skipping only the look for a
chip, with one fault of `faults.py` planted in the program for the
length of the test: a step that returns its state unchanged, half of
the batch left out with the mean taken over the rest, an answer altered
where it is produced.  The exchange between chips does not exist in
these one-chip cells.  A last test runs the control, the float64
reference's arithmetic in bfloat16 in the program's place, over the
answers of a sound run.
"""
import json

import jax
import pytest

from conftest import ROOT, tiny
from faults import FAULTS

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _fresh_programs():
    """Drop every compiled serving program, so the next run traces the
    (patched or restored) environment again."""
    from repro.launch.serving import programs
    for f in vars(programs).values():
        if hasattr(f, "cache_clear"):
            f.cache_clear()
    jax.clear_caches()


def _run(cell, seed=987654321987):
    import run as RUN
    _fresh_programs()
    return RUN.run(cell, seed, 2.0, False, require_tpu=False,
                   log=lambda s: None)


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    out = _run(tiny(cell_name))
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
@pytest.mark.parametrize("cell_name", CELLS)
def test_fault_is_not_correct(cell_name, fault, monkeypatch):
    from repro.index import env as E
    name, broken = FAULTS[fault](E)
    monkeypatch.setattr(E, name, broken)
    try:
        out = _run(tiny(cell_name))
    finally:
        monkeypatch.undo()
        _fresh_programs()
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    """The control's numbers over the answers of four served requests
    fail a limit.  At 65,536 keys: below that the bfloat16 keys collapse
    less, and the control's widest read-heavy gap can stay under that
    cell's limit, which is set at the timed size."""
    import cell as C
    import check as K
    cell = tiny(cell_name, 65536)
    cfg, tr = cell.config, cell.traffic
    _fresh_programs()
    seed = 424242
    svc = C.make_service(C.make_agent(cfg), cfg, tr, seed,
                         jax.devices()[:1])
    ring = C.make_requests(cfg, tr, seed)
    loop = C.ClosedLoop(svc, ring, cfg["budget_steps"])
    for _ in range(4):
        loop.submit()
    loop.drain()
    res = {r: svc.results[r] for r in loop.done}
    reqs = {r: ring[i] for r, i in loop.done.items()}
    limits = {k: v for k, v in cell.limits.items()
              if k in ("gap_max", "gap_max_two_point", "best_mismatch")}
    served, _ = K.judge(K.numbers(res, reqs, cfg["index_type"]), limits)
    control, checks = K.judge(K.numbers(res, reqs, cfg["index_type"],
                                        dtype="bfloat16", served=False),
                              limits)
    assert served
    assert not control, checks
