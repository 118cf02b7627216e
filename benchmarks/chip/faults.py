"""Faults planted in the timed path, to show that `correct` sees them.

Each fault takes the program's environment module (`repro.index.env`)
and returns the name of the function it replaces and the broken
function.  Planted before the serving programs are traced, it breaks
every evaluation that the window drives:

  state_unchanged  a step that returns its state unchanged (the
                   environment reports the previous runtime instead of
                   evaluating the action);
  half_batch       half of the batch left out, the mean taken over the
                   rest (each evaluation runs the first half of the
                   reads and of the inserts);
  altered_answer   an answer altered where it is produced (every
                   runtime 5% high).

The exchange between chips does not exist in one-chip cells.  Used by
`tests/test_bench_faults.py` at a test's size and by `control.py
--fault` on the chip at the cell's own size.
"""
from __future__ import annotations


def state_unchanged(E):
    core = E.step_core

    def step_core(cfg, env_state, action):
        new, obs, r, done, info = core(cfg, env_state, action)
        info = dict(info, runtime_ns=env_state["r_prev"])
        return env_state, obs, r, done, info
    return "step_core", step_core


def half_batch(E):
    evaluate = E.evaluate_params

    def evaluate_params(cfg, params_raw, data_keys, workload, wr_ratio):
        half = {k: v[: v.shape[0] // 2] for k, v in workload.items()}
        return evaluate(cfg, params_raw, data_keys, half, wr_ratio)
    return "evaluate_params", evaluate_params


def altered_answer(E):
    evaluate = E.evaluate_params

    def evaluate_params(*a, **kw):
        runtime, pieces, viol = evaluate(*a, **kw)
        return runtime * 1.05, pieces, viol
    return "evaluate_params", evaluate_params


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  altered_answer)}
