"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler ships with jaxlib's TPU plugin, so a program can be
compiled for a v5e that is described, not attached.  These compiles hold
the main path's kernels to what Mosaic accepts at serving sizes — the
probe kernel at 2^20 keys under each paper mix's read count, the fused
capture at serving widths — and hold one env step at 2^20 keys to the
chip's 16 GiB of HBM.  Interpret mode cannot show either: it runs any
block shape and any amount of memory.

The topology is described inside a fixture (never at import), so every
xdist worker collects the same tests and only the worker that runs this
file loads the TPU library.  The persistent compilation cache is off
around the compiles: an executable compiled for a described chip is
written to it but cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.replay import wide_dim
from repro.index import env as E
from repro.index.features import STATE_DIM
from repro.kernels.dispatch import KernelConfig
from repro.kernels.fused_tick.ops import fused_capture_core
from repro.kernels.fused_tick.ref import FIELD_ORDER
from repro.kernels.index_probe.ops import predecessor_positions

N_KEYS = 1 << 20
HBM_BYTES = 16 * 1024 ** 3                 # one v5e chip
# the paper's W/R mixes at 2^20 operations: read-heavy 1/3, balanced 1,
# write-heavy 3 -> reads = ops - int(ops * wr / (1 + wr))
READS = {"read_heavy": 786_432, "balanced": 524_288, "write_heavy": 262_144}
COMPILED = KernelConfig(mode="compiled")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU plugin / library locked
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _total_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes
            - ma.alias_size_in_bytes)


@pytest.mark.parametrize("mix", sorted(READS))
def test_probe_compiles_at_2e20(one_chip, mix):
    """The env-facing probe lowers to a Mosaic kernel at 2^20 keys, and
    its O(n + m) grouped buffers leave the chip's HBM nearly untouched."""
    fn = jax.jit(lambda k, q: predecessor_positions(k, q, kernel=COMPILED))
    compiled = fn.lower(_sds(one_chip, (N_KEYS,)),
                        _sds(one_chip, (READS[mix],))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES // 64


@pytest.mark.parametrize("k,h", [(1, 256), (8, 256), (256, 256), (8, 16)])
def test_fused_capture_compiles_at_serving_width(one_chip, k, h):
    """The capture kernel lowers at the serving widths: 4 slots, the
    paper agent's 128-wide LSTM (wide = 2*STATE_DIM + 4*128 = 564), and
    K-ladder rungs up to the default 256-step horizon."""
    wide = wide_dim(STATE_DIM, 128)
    assert wide == 564
    b = 4
    dims = {"obs": STATE_DIM, "next_obs": STATE_DIM, "h_a": 128,
            "c_a": 128, "h_q": 128, "c_q": 128}
    new = {f: _sds(one_chip, (k, b, dims[f])) for f in FIELD_ORDER}
    fn = jax.jit(lambda cap, new, off: fused_capture_core(cap, new, off,
                                                          "compiled"))
    compiled = fn.lower(_sds(one_chip, (b, h, wide)), new,
                        _sds(one_chip, (b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("index_type", ["alex", "carmi"])
def test_env_step_compiles_at_2e20_within_hbm(one_chip, index_type):
    """One read-heavy env step at 2^20 keys (the largest of the three
    mixes' read counts) with the compiled probe fits one chip's HBM."""
    cfg = E.EnvConfig(index_type=index_type, kernel=COMPILED)
    n_reads = READS["read_heavy"]
    f32 = lambda shape=(): _sds(one_chip, shape)             # noqa: E731
    env_state = {
        "data_keys": f32((N_KEYS,)), "reads": f32((n_reads,)),
        "inserts": f32((N_KEYS - n_reads,)), "wr_ratio": f32(),
        "r0": f32(), "r_prev": f32(), "r_best": f32(),
        "t": _sds(one_chip, (), jnp.int32), "cum_cost": f32()}
    action = f32((cfg.space.dim,))
    fn = jax.jit(E.step_core, static_argnames=("cfg",))
    compiled = fn.lower(cfg, env_state, action).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES
