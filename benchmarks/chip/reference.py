"""Plain reference of the tuning environment's answer: ns per operation.

A tuning step answers "how fast does this index run this workload under
these parameters": it decodes the agent's action into raw parameters,
builds an ALEX or CARMI index over the request's keys, runs the reads
and the inserts, and reports the mean cost per operation.  This module
computes that number again in plain NumPy, written from the simulators'
description (LITune, arXiv:2502.05001, Table 2 parameter spaces; the
repository's `index/alex.py`, `index/carmi.py`, `index/linear_model.py`,
`index/cost.py` and `core/spaces.py` as they stand at this benchmark's
first commit).  It imports nothing of the program and takes nothing the
program made: the keys and operations come from the benchmark's own
generator, the actions are the served answers under test.

`dtype` is the precision every array is held in.  float64 is the
reference; bfloat16 is the control (the nearest precision below the
program's float32): each value is rounded to it after every operation,
and sums accumulate in float64 before they are rounded, as an
accelerator's low-precision reduction does.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

# index/cost.py
MODEL_EVAL_NS = 5.0
PROBE_STEP_NS = 3.0
SHIFT_NS = 2.0
SLOT_INIT_NS = 0.5
RETRAIN_PER_KEY_NS = 10.0
QUERY_BASE_NS = 20.0
BUFFER_CMP_NS = 1.0
CACHE_LINE_NS = 60.0
CACHE_LINE_PREFETCHED_NS = 8.0
KEYS_PER_LINE = 8

# (name, kind, low, high): the agent's action axes in order
ALEX_SPACE = [
    ("density_init", "cont", 0.5, 0.95),
    ("density_upper", "cont", 0.6, 0.99),
    ("expected_insert_frac", "cont", 0.0, 1.0),
    ("split_balance", "cont", 0.3, 0.7),
    ("cost_w_traverse", "cont", 0.0, 1.0),
    ("approx_model_computation", "bool", 0, 1),
    ("approx_cost_computation", "bool", 0, 1),
    ("allow_splitting_upwards", "bool", 0, 1),
    ("max_node_size_log2", "int", 8, 16),
    ("kmax_ood_keys_log2", "int", 2, 14),
    ("ood_tolerance_factor", "int", 1, 50),
    ("max_fanout_log2", "int", 4, 9),
    ("fanout_selection_method", "choice", 0, 1),
    ("splitting_policy_method", "choice", 0, 2),
]
ALEX_DEFAULTS = {
    "density_init": 0.7, "density_upper": 0.8, "expected_insert_frac": 1.0,
    "split_balance": 0.5, "cost_w_traverse": 0.5,
    "approx_model_computation": 0, "approx_cost_computation": 0,
    "allow_splitting_upwards": 0, "max_node_size_log2": 14,
    "kmax_ood_keys_log2": 4, "ood_tolerance_factor": 2,
    "max_fanout_log2": 7, "fanout_selection_method": 0,
    "splitting_policy_method": 0,
}
ALEX_MAX_LEAVES = 512

CARMI_SPACE = [
    ("alpha_visit", "cont", 0.1, 4.0),
    ("alpha_scan", "cont", 0.1, 4.0),
    ("prefetch_aggr", "cont", 0.0, 1.0),
    ("leaf_density", "cont", 0.5, 0.95),
    ("split_ratio", "cont", 0.3, 0.7),
    ("w_read", "cont", 0.0, 2.0),
    ("w_write", "cont", 0.0, 2.0),
    ("ood_tolerance", "cont", 0.0, 1.0),
    ("rebuild_threshold", "cont", 0.05, 1.0),
    ("root_lr_scale", "cont", 0.25, 4.0),
    ("leaf_lines_log2", "int", 1, 7),
    ("root_fanout_log2", "int", 4, 10),
    ("lambda_spacetime", "hybrid", 0.0, 1.0),
]
CARMI_DEFAULTS = {
    "alpha_visit": 1.0, "alpha_scan": 1.0, "prefetch_aggr": 0.0,
    "leaf_density": 0.75, "split_ratio": 0.5, "w_read": 1.0, "w_write": 1.0,
    "ood_tolerance": 0.2, "rebuild_threshold": 0.5, "root_lr_scale": 1.0,
    "leaf_lines_log2": 3, "root_fanout_log2": 8, "lambda_spacetime": 0.5,
}
CARMI_MAX_LEAVES = 1024

SPACES = {"alex": ALEX_SPACE, "carmi": CARMI_SPACE}
DEFAULTS = {"alex": ALEX_DEFAULTS, "carmi": CARMI_DEFAULTS}
DTYPES = {"float64": np.float64, "bfloat16": ml_dtypes.bfloat16}


def decode(index_type: str, action) -> dict:
    """An action in [-1, 1]^d to raw parameters: each axis maps linearly
    onto [low, high]; booleans are "above the midpoint", integers and
    choices round to the nearest whole value.  The raw parameters are
    float32 values: the map is evaluated in float32 arithmetic, so that
    both sides build the index under the same parameters."""
    f32 = np.float32
    a = np.asarray(action, f32)
    a01 = (np.clip(a, f32(-1.0), f32(1.0)) + f32(1.0)) * f32(0.5)
    out = {}
    for i, (name, kind, lo, hi) in enumerate(SPACES[index_type]):
        if kind == "bool":
            x = f32(a01[i] > 0.5)
        else:
            x = a01[i] * f32(hi - lo) + f32(lo)
            if kind in ("int", "choice"):
                x = np.round(x)
        out[name] = float(x)
    return out


class _Num:
    """Arithmetic in one precision: `r` rounds a value to it."""

    def __init__(self, dtype):
        self.dt = DTYPES[dtype] if isinstance(dtype, str) else dtype
        self.exact = self.dt == np.float64

    def r(self, x):
        x = np.asarray(x, np.float64)
        return x if self.exact else x.astype(self.dt).astype(np.float64)

    def seg_sum(self, seg, w, n):
        return self.r(np.bincount(seg, weights=np.broadcast_to(
            np.asarray(w, np.float64), seg.shape), minlength=n)[:n])

    def total(self, x):
        return self.r(np.sum(x, dtype=np.float64))


def _fit_exact(num: _Num, keys, seg, n_segs):
    """Least-squares fit of key -> local rank per segment (with the
    segments' key counts)."""
    n = keys.shape[0]
    cnt = num.seg_sum(seg, 1.0, n_segs)
    starts = num.r(np.cumsum(cnt) - cnt)
    pos = num.r(np.arange(n, dtype=np.float64) - starts[seg])
    sx = num.seg_sum(seg, keys, n_segs)
    sy = num.seg_sum(seg, pos, n_segs)
    sxx = num.seg_sum(seg, num.r(keys * keys), n_segs)
    sxy = num.seg_sum(seg, num.r(keys * pos), n_segs)
    c = np.maximum(cnt, 1.0)
    var = num.r(sxx - num.r(num.r(sx * sx) / c))
    cov = num.r(sxy - num.r(num.r(sx * sy) / c))
    slope = np.where(var > 1e-18, num.r(cov / np.maximum(var, 1e-18)), 0.0)
    intercept = num.r(num.r(sy - num.r(slope * sx)) / c)
    return slope, intercept, cnt


def _fit_approx(num: _Num, keys, seg, n_segs):
    """Two-point (first and last key) fit per segment."""
    kmin = np.full(n_segs, np.inf)
    np.minimum.at(kmin, seg, keys)
    kmax = np.full(n_segs, -np.inf)
    np.maximum.at(kmax, seg, keys)
    cnt = num.seg_sum(seg, 1.0, n_segs)
    with np.errstate(invalid="ignore"):
        rng = np.maximum(num.r(kmax - kmin), 1e-18)
    rng = np.where(np.isnan(rng), 1e-18, rng)
    slope = np.where(cnt > 1, num.r((cnt - 1.0) / rng), 0.0)
    intercept = num.r(-slope * np.where(np.isfinite(kmin), kmin, 0.0))
    return slope, intercept


def _root_fit(num: _Num, keys):
    slope, intercept, _ = _fit_exact(
        num, keys, np.zeros(keys.shape, np.int64), 1)
    return float(slope[0]), float(intercept[0])


def _pred_pos(keys, q):
    """Predecessor position: the last key <= q, clipped into the array."""
    n = keys.shape[0]
    return np.clip(np.searchsorted(keys, q, side="right") - 1, 0, n - 1)


def _leaf_of(num: _Num, n, n_leaves):
    ranks = np.arange(n, dtype=np.float64)
    return np.minimum(num.r(num.r(ranks * n_leaves) / n),
                      n_leaves - 1.0).astype(np.int64)


# ------------------------------------------------------------------ ALEX
def _alex_build(num: _Num, keys, p):
    n = keys.shape[0]
    max_fanout = 2.0 ** p["max_fanout_log2"]
    node_keys = num.r(2.0 ** p["max_node_size_log2"] * p["density_init"])
    n_leaves = float(np.clip(np.ceil(num.r(n / max(node_keys, 16.0))),
                             1.0, min(max_fanout, ALEX_MAX_LEAVES)))
    kmin, kmax = keys[0], keys[-1]
    width = max(float(num.r(kmax - kmin)), 1e-12)
    equi_width = p["fanout_selection_method"] > 0.5
    if equi_width:
        seg = np.clip(num.r(num.r(num.r(keys - kmin) / width) * n_leaves),
                      0.0, n_leaves - 1.0).astype(np.int64)
    else:
        seg = _leaf_of(num, n, n_leaves)
    slope, intercept, cnt = _fit_exact(num, keys, seg, ALEX_MAX_LEAVES)
    if p["approx_model_computation"] > 0.5:
        slope, intercept = _fit_approx(num, keys, seg, ALEX_MAX_LEAVES)
    if equi_width:
        root_slope = float(num.r(n_leaves / width))
        root_icpt = float(num.r(-root_slope * kmin))
    else:
        rs, ri = _root_fit(num, keys)
        root_slope = float(num.r(num.r(rs * n_leaves) / n))
        root_icpt = float(num.r(num.r(ri * n_leaves) / n))
    slots = num.r(num.r(cnt / max(p["density_init"], 0.05))
                  * num.r(1.0 + 0.5 * p["expected_insert_frac"]))
    slots = np.where(cnt > 0, np.maximum(slots, cnt + 1.0), 0.0)
    return {"keys": keys, "seg": seg, "n_leaves": n_leaves,
            "slope": slope, "intercept": intercept, "cnt": cnt,
            "slots": slots, "root_slope": root_slope,
            "root_icpt": root_icpt, "kmin": kmin, "kmax": kmax}


def _alex_locate(num: _Num, idx, q, pos):
    pred = np.clip(num.r(num.r(idx["root_slope"] * q) + idx["root_icpt"]),
                   0.0, idx["n_leaves"] - 1.0)
    leaf = idx["seg"][pos]
    root_err = num.r(np.abs(pred - leaf))
    cost = num.r(MODEL_EVAL_NS
                 + num.r(PROBE_STEP_NS * num.r(np.log2(1.0 + root_err))))
    return leaf, cost


def _alex_reads(num: _Num, idx, reads):
    pos = _pred_pos(idx["keys"], reads)
    leaf, root_cost = _alex_locate(num, idx, reads, pos)
    cnt = np.maximum(idx["cnt"], 1.0)
    starts = num.r(np.cumsum(idx["cnt"]) - idx["cnt"])
    local = num.r(pos - starts[leaf])
    pred_local = np.clip(num.r(num.r(idx["slope"][leaf] * reads)
                               + idx["intercept"][leaf]), 0.0, cnt[leaf])
    density = np.clip(num.r(idx["cnt"] / np.maximum(idx["slots"], 1.0)),
                      0.01, 1.0)
    dist = num.r(num.r(np.abs(pred_local - local)) / density[leaf])
    probe = num.r(MODEL_EVAL_NS + num.r(PROBE_STEP_NS * num.r(
        1.0 + num.r(2.0 * num.r(np.log2(1.0 + dist))))))
    per_q = num.r(num.r(QUERY_BASE_NS + root_cost) + probe)
    return num.total(per_q)


def _alex_inserts(num: _Num, idx, inserts, p):
    in_dom = inserts <= idx["kmax"]
    n_ood = float(np.sum(~in_dom))
    q_in = np.where(in_dom, inserts, idx["kmin"])
    leaf, root_cost = _alex_locate(num, idx, q_in,
                                   _pred_pos(idx["keys"], q_in))
    w_in = in_dom.astype(np.float64)
    add = num.seg_sum(leaf, w_in, ALEX_MAX_LEAVES)
    cnt0, slots0 = idx["cnt"], np.maximum(idx["slots"], 1.0)
    occ0 = np.clip(num.r(cnt0 / slots0), 0.0, 0.999)
    occ1 = np.clip(num.r(num.r(cnt0 + add) / slots0), 0.0, 0.999)
    occ_mid = num.r(0.5 * num.r(occ0 + occ1))
    disp = num.r(occ_mid / num.r(1.0 - occ_mid))
    per_leaf = num.r(add * num.r(num.r(MODEL_EVAL_NS + num.r(SHIFT_NS * disp))
                                 + PROBE_STEP_NS * 2.0))
    cnt1 = num.r(cnt0 + add)

    over = (num.r(cnt1 / slots0) > p["density_upper"]) & (cnt0 > 0)
    node_cap = 2.0 ** p["max_node_size_log2"]
    want_expand = over & (num.r(slots0 / p["density_init"]) <= node_cap)
    # the expand-or-split misprediction is a hash of the count, defined
    # on float32 values: evaluated on them here as well
    h = np.sin((np.float32(1.0) * cnt1.astype(np.float32)
                * np.float32(12.9898)).astype(np.float64))
    flip = (p["approx_cost_computation"] > 0.5) & \
        (np.abs(h) < 0.15 + 0.2 * p["cost_w_traverse"])
    want_split = (over & ~want_expand) | (want_expand & flip)
    want_expand = over & ~want_split

    new_slots = np.where(want_expand, num.r(cnt1 / p["density_init"]),
                         slots0)
    expand_ns = np.where(want_expand, num.r(
        num.r(new_slots * SLOT_INIT_NS) + num.r(cnt1 * RETRAIN_PER_KEY_NS)),
        0.0)
    can_split = (idx["n_leaves"] < 2.0 ** p["max_fanout_log2"]) or \
        (p["allow_splitting_upwards"] > 0.5)
    do_split = want_split & can_split
    bal = min(max(p["split_balance"], 0.05), 0.95)
    imb = float(num.r(1.0 + abs(bal - 0.5) * 2.0))
    split_ns = np.where(do_split, num.r(num.r(
        cnt1 * (RETRAIN_PER_KEY_NS + SHIFT_NS)) * imb), 0.0)
    policy = p["splitting_policy_method"]
    post_density = 0.5 if policy < 0.5 else (
        p["density_init"] if policy < 1.5 else 0.65)
    cascade = 50.0 if post_density >= p["density_upper"] - 0.02 else 1.0
    split_ns = num.r(split_ns * cascade)
    new_slots = np.where(do_split, num.r(cnt1 / max(post_density, 0.05)),
                         new_slots)
    mega = want_split & ~can_split
    new_slots = np.where(mega, num.r(cnt1 / 0.99), new_slots)

    limit = 2.0 ** p["kmax_ood_keys_log2"] * p["ood_tolerance_factor"]
    buf1 = n_ood
    retrain_ns = 0.0
    if buf1 > limit:
        retrain_ns = float(num.r(num.r((idx["keys"].shape[0] + buf1)
                                       * RETRAIN_PER_KEY_NS)
                                 + num.r(num.total(new_slots)
                                         * SLOT_INIT_NS)))
    ood_ns = float(num.r(n_ood * num.r(QUERY_BASE_NS + num.r(
        BUFFER_CMP_NS * buf1 * 0.5))))
    parts = [num.total(per_leaf), num.total(expand_ns), num.total(split_ns),
             retrain_ns, ood_ns, num.r(num.total(w_in) * QUERY_BASE_NS),
             num.total(num.r(root_cost * w_in))]
    total = 0.0
    for x in parts:
        total = float(num.r(total + x))
    return total


# ----------------------------------------------------------------- CARMI
def _carmi_build(num: _Num, keys, p):
    n = keys.shape[0]
    lam = p["lambda_spacetime"]
    if lam < 0.05:
        density = 0.5
    else:
        density = float(np.clip(num.r(p["leaf_density"] + 0.2 * lam),
                                0.5, 0.98))
    keys_per_leaf = float(num.r(2.0 ** p["leaf_lines_log2"] * KEYS_PER_LINE
                                * density))
    fanout = float(np.clip(num.r(2.0 ** p["root_fanout_log2"]
                                 * p["root_lr_scale"]), 2.0,
                           CARMI_MAX_LEAVES))
    n_leaves = float(np.clip(np.ceil(num.r(n / max(keys_per_leaf, 4.0))),
                             1.0, fanout))
    seg = _leaf_of(num, n, n_leaves)
    slope, intercept, cnt = _fit_exact(num, keys, seg, CARMI_MAX_LEAVES)
    rs, ri = _root_fit(num, keys)
    slots = np.where(cnt > 0, num.r(cnt / max(density, 0.05)), 0.0)
    return {"keys": keys, "seg": seg, "n_leaves": n_leaves,
            "slope": slope, "intercept": intercept, "cnt": cnt,
            "slots": slots,
            "root_slope": float(num.r(num.r(rs * n_leaves) / n)),
            "root_icpt": float(num.r(num.r(ri * n_leaves) / n)),
            "kmin": keys[0], "kmax": keys[-1]}


def _carmi_lines(num: _Num, idx, q, p):
    """Cost of one lookup's cache-line touches, its search distance and
    its leaf."""
    pred_leaf = np.clip(num.r(num.r(idx["root_slope"] * q)
                              + idx["root_icpt"]), 0.0,
                        idx["n_leaves"] - 1.0)
    pos = _pred_pos(idx["keys"], q)
    leaf = idx["seg"][pos]
    root_err = num.r(np.abs(pred_leaf - leaf))
    root_lines = num.r(1.0 + num.r(np.log2(1.0 + root_err)))
    starts = num.r(np.cumsum(idx["cnt"]) - idx["cnt"])
    local = num.r(pos - starts[leaf])
    pred_local = np.clip(num.r(num.r(idx["slope"][leaf] * q)
                               + idx["intercept"][leaf]),
                         0.0, np.maximum(idx["cnt"][leaf], 1.0))
    dist = num.r(np.abs(pred_local - local))
    leaf_lines = num.r(1.0 + num.r(dist / KEYS_PER_LINE))
    hit = num.r(np.exp(num.r(-dist / (KEYS_PER_LINE * 2.0))))
    pa = p["prefetch_aggr"]
    eff = num.r(num.r(num.r(pa * hit) * CACHE_LINE_PREFETCHED_NS)
                + num.r(num.r(1.0 - num.r(pa * hit)) * CACHE_LINE_NS))
    waste = num.r(num.r(pa * num.r(1.0 - hit)) * CACHE_LINE_NS * 0.5)
    ns = num.r(num.r(num.r(p["alpha_visit"] * root_lines) * CACHE_LINE_NS)
               + num.r(num.r(p["alpha_scan"] * leaf_lines) * eff))
    ns = num.r(num.r(ns + waste) + num.r(dist * (PROBE_STEP_NS * 0.25)))
    return ns, leaf


def _carmi_reads(num: _Num, idx, reads, p):
    ns, _ = _carmi_lines(num, idx, reads, p)
    per_q = num.r(QUERY_BASE_NS + num.r(ns / max(p["w_read"], 0.1)))
    return num.total(per_q)


def _carmi_inserts(num: _Num, idx, inserts, p):
    in_dom = inserts <= idx["kmax"]
    n_ood = float(np.sum(~in_dom))
    q_in = np.where(in_dom, inserts, idx["kmin"])
    ns, leaf = _carmi_lines(num, idx, q_in, p)
    w_in = in_dom.astype(np.float64)
    add = num.seg_sum(leaf, w_in, CARMI_MAX_LEAVES)
    cnt1 = num.r(idx["cnt"] + add)
    slots = np.maximum(idx["slots"], 1.0)
    occ = np.clip(num.r(cnt1 / slots), 0.0, 0.999)
    shift_lines = num.r(num.r(occ / num.r(1.0 - occ)) / KEYS_PER_LINE)
    full = (occ > 0.95) & (idx["cnt"] > 0)
    split_ns = np.where(full, num.r(num.r(cnt1 * RETRAIN_PER_KEY_NS) * (
        1.0 + abs(p["split_ratio"] - 0.5))), 0.0)
    buf1 = n_ood
    limit = 64.0 * (1.0 + 63.0 * p["ood_tolerance"])
    retrain_ns = 0.0
    if buf1 > limit * p["rebuild_threshold"] * 4.0:
        retrain_ns = float(num.r((idx["keys"].shape[0] + buf1)
                                 * RETRAIN_PER_KEY_NS))
    per_ins = num.r(num.r(num.r(QUERY_BASE_NS + ns)
                          + num.r(shift_lines[leaf] * CACHE_LINE_NS))
                    / max(p["w_write"], 0.1))
    parts = [num.total(num.r(per_ins * w_in)), num.total(split_ns),
             retrain_ns, float(num.r(n_ood * num.r(
                 QUERY_BASE_NS + BUFFER_CMP_NS * buf1 * 0.25)))]
    total = 0.0
    for x in parts:
        total = float(num.r(total + x))
    return total


def runtime_ns(index_type: str, params: dict, keys, reads, inserts,
               dtype="float64") -> float:
    """Mean ns per operation of `reads` then `inserts` on an index built
    over the sorted `keys` under raw `params`."""
    num = _Num(dtype)
    keys, reads, inserts = (num.r(np.asarray(x, np.float64))
                            for x in (keys, reads, inserts))
    p = {k: float(num.r(v)) for k, v in params.items()}
    if index_type == "alex":
        idx = _alex_build(num, keys, p)
        total = num.r(_alex_reads(num, idx, reads)
                      + _alex_inserts(num, idx, inserts, p))
    elif index_type == "carmi":
        idx = _carmi_build(num, keys, p)
        total = num.r(_carmi_reads(num, idx, reads, p)
                      + _carmi_inserts(num, idx, inserts, p))
    else:
        raise ValueError(f"unknown index type {index_type!r}")
    return float(num.r(total / (reads.shape[0] + inserts.shape[0])))
