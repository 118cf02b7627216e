"""The fused K-ladder tick (`kernels/fused_tick` + the serving step
program's `capture=True` variant):

* the Pallas capture kernel is bitwise against the jnp oracle (pure data
  movement — property-based across shapes/offsets);
* a fused-tick service stream is bitwise-identical to the unfused
  scan-of-steps + standalone-capture path on the CPU reference path —
  results, stats-visible decisions, and replay-ring contents;
* the fused variant lives in the same resident program cache: a second
  identically-shaped stream binds zero new step programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

import repro.launch.serving.programs as programs
from repro.core.litune import LITune, LITuneConfig
from repro.index.workloads import sample_keys, wr_workload
from repro.kernels.dispatch import KernelConfig
from repro.kernels.fused_tick.ops import fused_capture
from repro.kernels.fused_tick.ref import FIELD_ORDER, fused_capture_ref
from repro.launch.serving.config import ServeConfig
from repro.launch.serving.o2_runtime import O2ServiceConfig
from repro.launch.serving.service import TuningService


# --------------------------------------------------------- kernel parity
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 6))
def test_fused_capture_interpret_matches_ref(seed, k_steps, b):
    """The Pallas append equals the jnp oracle bitwise: same packing
    order, same rows touched, untouched rows preserved."""
    key = jax.random.PRNGKey(seed)
    h = 16
    dims = {"obs": 3, "next_obs": 3, "h_a": 2, "c_a": 2, "h_q": 2,
            "c_q": 2}
    wide = sum(dims.values())
    ks = jax.random.split(key, len(FIELD_ORDER) + 2)
    new = {f: jax.random.normal(ks[i], (k_steps, b, dims[f]), jnp.float32)
           for i, f in enumerate(FIELD_ORDER)}
    cap = jax.random.normal(ks[-2], (b, h, wide), jnp.float32)
    # offsets past h - k_steps exercise the clamp both sides share
    offsets = jax.random.randint(ks[-1], (b,), 0, h + 2)
    got = fused_capture(cap, new, offsets, mode="interpret")
    want = fused_capture(cap, new, offsets, mode="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the oracle really is the historical _capture_write body
    direct = fused_capture_ref(cap, new, offsets.astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(direct))


def test_fused_capture_interpret_at_serving_width():
    """The serving shape the chip compiles: 4 slots, the paper agent's
    128-wide LSTM (wide 564), a K=8 rung of a 16-step horizon."""
    key = jax.random.PRNGKey(0)
    b, h, k_steps = 4, 16, 8
    dims = {"obs": 26, "next_obs": 26, "h_a": 128, "c_a": 128,
            "h_q": 128, "c_q": 128}
    ks = jax.random.split(key, len(FIELD_ORDER) + 1)
    new = {f: jax.random.normal(ks[i], (k_steps, b, dims[f]), jnp.float32)
           for i, f in enumerate(FIELD_ORDER)}
    cap = jax.random.normal(ks[-1], (b, h, sum(dims.values())), jnp.float32)
    offsets = jnp.asarray([0, 3, h - k_steps, h], jnp.int32)
    got = fused_capture(cap, new, offsets, mode="interpret")
    want = fused_capture(cap, new, offsets, mode="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_capture_field_order_matches_replay():
    """The capture feature axis must slice back out in replay order."""
    from repro.core.replay import WIDE_FIELDS
    assert FIELD_ORDER == WIDE_FIELDS


# ------------------------------------------------- service-level parity
def _stream(kernel: KernelConfig, n_req: int = 4):
    cfg = LITuneConfig(index_type="alex", episode_len=8, lstm_hidden=16,
                       mlp_hidden=32)
    svc = TuningService(LITune(cfg, seed=0), config=ServeConfig(
        slots=2, horizon_cap=8, seed=0,
        o2=O2ServiceConfig(enabled=True), kernel=kernel))
    key = jax.random.PRNGKey(1)
    for i in range(n_req):
        k = jax.random.fold_in(key, i)
        data = sample_keys(k, 512, "mix")
        wl, _ = wr_workload(jax.random.fold_in(k, 1), data, 1.0,
                            total=512, dist="mix")
        svc.submit(data, wl, 1.0, budget_steps=8)
    res = svc.run()
    svc.flush_o2()
    return svc, res


def _ring_arrays(replay):
    """Every array leaf hanging off the replay ring, keyed by attr."""
    out = {}
    for name, val in replay.__dict__.items():
        leaves = [x for x in jax.tree.leaves(val) if hasattr(x, "shape")]
        if leaves:
            out[name] = leaves
    return out


def test_fused_tick_bitwise_equals_scan_of_steps():
    """The acceptance anchor: a fused-tick O2 stream (default
    KernelConfig) is bitwise-equal to the unfused scan-of-steps +
    standalone-capture path — per-request results AND the replay ring
    the capture buffers feed."""
    svc_f, res_f = _stream(KernelConfig())               # fused default
    svc_u, res_u = _stream(KernelConfig(fused_tick=False))
    assert set(res_f) == set(res_u)
    for rid in res_f:
        a, b = res_f[rid], res_u[rid]
        assert a["episode_return"] == b["episode_return"]
        assert a["runtimes"] == b["runtimes"]
        assert all(np.array_equal(x, y)
                   for x, y in zip(a["actions"], b["actions"]))
    for it in svc_f.tenants:
        rf = _ring_arrays(svc_f.tenants[it].replay)
        ru = _ring_arrays(svc_u.tenants[it].replay)
        assert set(rf) == set(ru)
        for name in rf:
            for x, y in zip(rf[name], ru[name]):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=(it, name))


def test_fused_variant_zero_new_binds_after_warmup():
    """The fused program rides the same resident ladder cache: a second
    identically-shaped stream re-uses every executable — zero new step
    programs, zero cache misses."""
    svc, _ = _stream(KernelConfig())
    resident0 = programs._step_program.cache_info().currsize
    misses0 = svc.program_misses
    key = jax.random.PRNGKey(9)
    for i in range(3):
        k = jax.random.fold_in(key, i)
        data = sample_keys(k, 512, "mix")
        wl, _ = wr_workload(jax.random.fold_in(k, 1), data, 1.0,
                            total=512, dist="mix")
        svc.submit(data, wl, 1.0, budget_steps=8)
    svc.run()
    svc.flush_o2()
    assert programs._step_program.cache_info().currsize == resident0
    assert svc.program_misses == misses0
