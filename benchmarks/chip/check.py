"""Whether what the timed path served is correct.

Every request the window completed carries its answer: the runtime of
the default parameters (`r0_ns`, set at admission), and for each tuning
step the agent's action and the runtime the environment reported for
it, plus the best parameters and best runtime.  A sample of those
requests, drawn from the seed, is computed again by the plain reference
(`reference.py`, float64) over the same keys and operations, taking the
served actions as given, as a served model's tokens are taken when its
logits are checked.  The numbers compared:

  gap_median     median relative gap |served - reference| / reference
                 over the sampled answers (every r0 and every step);
  gap_max        the widest such gap;
  gap_max_two_point
                 the widest gap over the ALEX answers whose leaves take
                 the two-point (approximate) fit: the program fits the
                 other leaves by one-pass float32 least squares, which
                 departs from the reference by up to ~1.4e-2 (PERF.md,
                 Open questions), while on these answers the two agree
                 to ~1e-5, so a fault that moves an answer by less than
                 gap_max's limit still shows here.  Infinite where the
                 sample holds no such answer; absent for CARMI, which
                 has no two-point fit;
  best_mismatch  requests whose best runtime is not min(r0, runtimes) or
                 whose best parameters are not the decoded action of
                 their fastest step (exact: limit 0);
and under O2:
  o2_updates     offline fine-tune updates completed inside the window;
  o2_assessed    windows assessed inside the window;
  o2_nonfinite   parameter trees of the tenant with a non-finite leaf.

Each cell's limits are in `limits/<cell>.json`, with the readings they
were set from in PERF.md.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference as R


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def sample(rids: list[int], seed: int, n: int) -> list[int]:
    """`n` of the completed requests, drawn from the seed."""
    rids = sorted(rids)
    rng = np.random.default_rng(seed)
    k = min(n, len(rids))
    return sorted(rids[i] for i in rng.choice(len(rids), k, replace=False))


def answers(result: dict, index_type: str):
    """(params, served runtime) for r0 and every step of one result."""
    out = [(R.DEFAULTS[index_type], result["r0_ns"])]
    for a, rt in zip(result["actions"], result["runtimes"]):
        out.append((R.decode(index_type, a), rt))
    return out


def two_point(index_type: str, params: dict) -> bool:
    """Whether the answer's leaves take ALEX's two-point fit."""
    return index_type == "alex" and params["approx_model_computation"] > 0.5


def best_consistent(result: dict, index_type: str) -> bool:
    rts = result["runtimes"]
    if result["best_runtime_ns"] != min(result["r0_ns"], min(rts)):
        return False
    best = R.decode(index_type, result["actions"][int(np.argmin(rts))])
    return best == result["best_params"]


def gaps(results: dict, requests: dict, index_type: str,
         dtype: str = "float64", served: bool = True,
         threads: int = 4) -> list[float]:
    """Relative gaps of every sampled answer against the float64
    reference.  With `served` the gap is the served runtime's; without,
    it is the reference's own at `dtype` (the control: the reference put
    in the program's place at a lower precision)."""
    jobs = []
    for rid, res in results.items():
        req = requests[rid]
        for params, rt in answers(res, index_type):
            jobs.append((req, params, rt))

    def one(job):
        req, params, rt = job
        want = R.runtime_ns(index_type, params, req.keys, req.reads,
                            req.inserts)
        got = rt if served else R.runtime_ns(
            index_type, params, req.keys, req.reads, req.inserts, dtype)
        return rel(got, want)

    with ThreadPoolExecutor(threads) as ex:
        return list(ex.map(one, jobs))


def numbers(results: dict, requests: dict, index_type: str,
            **kw) -> dict:
    return summarize(gaps(results, requests, index_type, **kw), results,
                     index_type)


def summarize(g: list[float], results: dict, index_type: str) -> dict:
    """The compared numbers from the gaps of `gaps`, which come in the
    order of `results` and of each result's `answers`."""
    n = len(g)
    params = [p for r in results.values() for p, _ in answers(r, index_type)]
    g = g or [float("inf")]             # nothing compared: not correct
    out = {
        "gap_median": float(np.median(g)),
        "gap_max": float(np.max(g)),
        "best_mismatch": sum(not best_consistent(r, index_type)
                             for r in results.values()),
        "answers": n,
    }
    if index_type == "alex":
        tp = [x for x, p in zip(g, params) if two_point(index_type, p)]
        out["gap_max_two_point"] = float(max(tp, default=float("inf")))
        out["answers_two_point"] = len(tp)
    return out


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """`limits` maps a number's name to [op, limit], op "<=" or ">=".
    Returns (all held, {name: {value, limit, op}})."""
    out, ok = {}, True
    for name, (op, lim) in limits.items():
        v = values[name]
        held = v <= lim if op == "<=" else v >= lim
        ok &= bool(held)
        out[name] = {"value": v, "op": op, "limit": lim}
    return ok, out
