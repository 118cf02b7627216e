"""The kernel-dispatch seam (`kernels/dispatch.py`): per-process mode
resolution, the env-var override, `KernelConfig` validation, and the
three kernel ops routing through one seam — plus the env hot path
(`alex`/`carmi` `run_reads`) staying numerically equal under Pallas
probe modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index import alex, carmi
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelConfig

on_cpu = jax.default_backend() not in ("gpu", "tpu")


# ---------------------------------------------------------- resolution
def test_resolve_concrete_modes_pass_through():
    for m in ("compiled", "interpret", "ref"):
        assert dispatch.resolve(m) == m


def test_resolve_rejects_unknown_mode():
    with pytest.raises(ValueError, match="kernel mode"):
        dispatch.resolve("fast")


def test_auto_mode_backend_rule():
    """auto/None resolve to ref on CPU, compiled on accelerators —
    and the answer is cached (one posture per process)."""
    got = dispatch.resolve(None)
    assert got == ("ref" if on_cpu else "compiled")
    assert dispatch.resolve("auto") == got
    assert dispatch._auto_mode() is dispatch._auto_mode()


def test_env_var_override(monkeypatch):
    monkeypatch.setenv(dispatch._ENV_VAR, "interpret")
    dispatch._auto_mode.cache_clear()
    try:
        assert dispatch.resolve(None) == "interpret"
        monkeypatch.setenv(dispatch._ENV_VAR, "bogus")
        dispatch._auto_mode.cache_clear()
        with pytest.raises(ValueError):
            dispatch.resolve(None)
    finally:
        monkeypatch.delenv(dispatch._ENV_VAR)
        dispatch._auto_mode.cache_clear()


def test_interpret_flag():
    assert dispatch.interpret_flag("interpret") is True
    assert dispatch.interpret_flag("compiled") is False


# --------------------------------------------------------- KernelConfig
def test_kernel_config_validation():
    with pytest.raises(ValueError):
        KernelConfig(mode="pallas")
    with pytest.raises(ValueError):
        KernelConfig(probe_tile=100)        # not a pow2
    with pytest.raises(ValueError):
        KernelConfig(probe_tile=-8)
    assert KernelConfig(probe_tile=256).probe_tile == 256
    assert KernelConfig().resolved() == dispatch.resolve(None)


def test_kernel_config_hashes_by_value():
    """Two equal configs are one program-cache key (frozen dataclass)."""
    assert KernelConfig() == KernelConfig()
    assert hash(KernelConfig()) == hash(KernelConfig())
    assert KernelConfig(mode="interpret") != KernelConfig()


# ----------------------------------------------- ops route through modes
def test_mha_mode_routing(rng_key):
    """flash_attention's op takes the one `mode` arg: interpret runs the
    kernel body, ref the oracle — same numbers either way."""
    from repro.kernels.flash_attention.ops import mha
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 16), jnp.float32)
    got = mha(q, k, v, mode="interpret")
    want = mha(q, k, v, mode="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        mha(q, k, v, mode="bogus")


def test_mamba_scan_mode_routing(rng_key):
    from repro.kernels.mamba_scan.ops import scan
    ks = jax.random.split(rng_key, 4)
    u = jax.random.normal(ks[0], (1, 64, 16), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 64, 16)))
    b_mat = jax.random.normal(ks[2], (1, 64, 4), jnp.float32)
    c_mat = jax.random.normal(ks[3], (1, 64, 4), jnp.float32)
    a = -jnp.exp(jax.random.normal(rng_key, (16, 4)) * 0.3)
    got = scan(u, dt, b_mat, c_mat, a, mode="interpret", chunk=64)
    want = scan(u, dt, b_mat, c_mat, a, mode="ref", chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------- env hot path
def _alex_params():
    return {k: jnp.float32(v) for k, v in alex.DEFAULTS.items()}


def _carmi_params():
    return {k: jnp.float32(v) for k, v in carmi.DEFAULTS.items()}


def test_alex_run_reads_kernel_mode_parity(rng_key):
    """run_reads under the Pallas probe gate returns numbers equal to
    the default searchsorted reference path (the probe is exact)."""
    keys = jnp.sort(jax.random.uniform(rng_key, (2048,)))
    reads = jax.random.uniform(jax.random.fold_in(rng_key, 1), (256,)) \
        * 1.4 - 0.2                          # includes out-of-range
    idx = alex.build(keys, _alex_params())
    ns_ref, m_ref = alex.run_reads(idx, reads)
    ns_k, m_k = alex.run_reads(idx, reads,
                               kernel=KernelConfig(mode="interpret"))
    np.testing.assert_array_equal(np.asarray(ns_ref), np.asarray(ns_k))
    for f in m_ref:
        np.testing.assert_array_equal(np.asarray(m_ref[f]),
                                      np.asarray(m_k[f]), err_msg=f)


def test_alex_run_reads_kernel_mode_parity_ragged_keys(rng_key):
    """A key count with no power-of-two tile (2000 = 16 * 125) runs the
    padded kernel and still equals the reference path bitwise."""
    keys = jnp.sort(jax.random.uniform(rng_key, (2000,)))
    reads = jax.random.uniform(jax.random.fold_in(rng_key, 1), (256,)) \
        * 1.4 - 0.2
    idx = alex.build(keys, _alex_params())
    ns_ref, m_ref = alex.run_reads(idx, reads)
    ns_k, m_k = alex.run_reads(idx, reads,
                               kernel=KernelConfig(mode="interpret"))
    np.testing.assert_array_equal(np.asarray(ns_ref), np.asarray(ns_k))
    for f in m_ref:
        np.testing.assert_array_equal(np.asarray(m_ref[f]),
                                      np.asarray(m_k[f]), err_msg=f)


def test_carmi_run_reads_kernel_mode_parity(rng_key):
    p = _carmi_params()
    keys = jnp.sort(jax.random.uniform(rng_key, (2048,)))
    reads = jax.random.uniform(jax.random.fold_in(rng_key, 1), (256,)) \
        * 1.4 - 0.2
    idx = carmi.build(keys, p)
    ns_ref, m_ref = carmi.run_reads(idx, reads, p)
    ns_k, m_k = carmi.run_reads(idx, reads, p,
                                kernel=KernelConfig(mode="interpret"))
    np.testing.assert_array_equal(np.asarray(ns_ref), np.asarray(ns_k))
    for f in m_ref:
        np.testing.assert_array_equal(np.asarray(m_ref[f]),
                                      np.asarray(m_k[f]), err_msg=f)


def test_env_config_threads_kernel(rng_key):
    """evaluate_params carries EnvConfig.kernel into run_reads: the
    probe-gated env step equals the default bitwise."""
    import dataclasses

    from repro.index.env import EnvConfig, evaluate_params
    from repro.index.workloads import wr_workload
    cfg = EnvConfig(index_type="alex")
    assert cfg.kernel == KernelConfig()
    keys = jnp.sort(jax.random.uniform(rng_key, (2048,)))
    wl, _ = wr_workload(jax.random.fold_in(rng_key, 7), keys, 0.7,
                        total=512)
    p = _alex_params()
    r0, _, _ = evaluate_params(cfg, p, keys, wl, 0.7)
    cfg_k = dataclasses.replace(cfg, kernel=KernelConfig(mode="interpret"))
    r1, _, _ = evaluate_params(cfg_k, p, keys, wl, 0.7)
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
