"""HLO parser validation: scan-based totals must match XLA's own
cost_analysis on an unrolled twin, and trip counts must come from the
trip_scope markers."""
import types

import jax
import jax.numpy as jnp
import pytest

from repro.models.module import trip_scope
from repro.runtime import hlo_analysis as ha


L, D, F, B = 6, 128, 256, 16


def _body(x, ws):
    a, b = ws
    h = jax.nn.relu(jnp.einsum("bd,df->bf", x, a))
    return jnp.einsum("bf,fd->bd", h, b), None


def _scan_fn(w1, w2, x):
    with trip_scope(L, "layers"):
        out, _ = jax.lax.scan(_body, x, (w1, w2))
    return out.sum()


def _unroll_fn(w1, w2, x):
    for i in range(L):
        x, _ = _body(x, (w1[i], w2[i]))
    return x.sum()


@pytest.fixture(scope="module")
def compiled_pair():
    w1 = jax.ShapeDtypeStruct((L, D, F), jnp.float32)
    w2 = jax.ShapeDtypeStruct((L, F, D), jnp.float32)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    scan = jax.jit(_scan_fn).lower(w1, w2, x).compile()
    unroll = jax.jit(_unroll_fn).lower(w1, w2, x).compile()
    return scan, unroll


def test_trip_count_from_scope(compiled_pair):
    scan, _ = compiled_pair
    res = ha.analyze(scan.as_text())
    assert list(res.while_trips.values()) == [L]
    assert not res.warnings


def test_scan_flops_match_unrolled_cost_analysis(compiled_pair):
    scan, unroll = compiled_pair
    res_scan = ha.analyze(scan.as_text())
    res_unroll = ha.analyze(unroll.as_text())
    xla_unroll = float(ha.xla_cost_analysis(unroll)["flops"])
    analytic = L * 2 * (2 * B * D * F)
    # parser on scan == parser on unroll == XLA on unroll == analytic (±5%)
    for val in (res_scan.flops, res_unroll.flops, xla_unroll):
        assert abs(val - analytic) / analytic < 0.05, val


def test_xla_cost_analysis_undercounts_scan(compiled_pair):
    """The reason this module exists: XLA counts while bodies once."""
    scan, _ = compiled_pair
    xla_scan = float(ha.xla_cost_analysis(scan)["flops"])
    res_scan = ha.analyze(scan.as_text())
    assert xla_scan < res_scan.flops / 2


def test_bytes_sane(compiled_pair):
    scan, _ = compiled_pair
    res = ha.analyze(scan.as_text())
    weight_bytes = L * 2 * D * F * 4
    io_bytes = B * D * 4
    # at least one read of all weights + activations; at most ~10x slack
    assert res.bytes_accessed > weight_bytes + io_bytes
    assert res.bytes_accessed < 10 * (weight_bytes + 4 * L * B * F * 4)


def test_roofline_terms():
    pk = ha.peaks("TPU v5 lite")
    a = ha.HLOAnalysis(flops=197e12, bytes_accessed=819e9,
                       collective_bytes=pk.ici_bw)
    t = ha.roofline(a, "TPU v5 lite", model_flops_per_device=98.5e12)
    assert abs(t.compute_s - 1.0) < 1e-6
    assert abs(t.memory_s - 1.0) < 1e-6
    assert abs(t.collective_s - 1.0) < 1e-6
    assert t.useful_ratio == pytest.approx(0.5)
    assert t.dominant in ("compute", "memory", "collective")
    with pytest.raises(KeyError, match="no published peaks"):
        ha.roofline(a, "cpu")
    # CPU-lowered HLO is modelled for an explicit kind; an accelerator's
    # own kind is used as is, so an unknown one raises
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert ha.modelled_kind(cpu) == ha.CPU_MODELLED_KIND in ha.PEAKS
    gpu = types.SimpleNamespace(platform="gpu", device_kind="H100")
    with pytest.raises(KeyError, match="no published peaks"):
        ha.roofline(a, ha.modelled_kind(gpu))


def test_collective_parsing_small_mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (covered by dry-run)")
