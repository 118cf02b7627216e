"""Roofline benchmark: wraps launch/dryrun.py records into CSV rows.

NOTE: must run in a separate process from the other benchmarks when the
512-device flag is needed; benchmarks/run.py shells out for that reason.
This module also provides small-mesh (in-process, 1-device) micro-bench
rows: wall-clock us/call of the jitted smoke-scale step functions, which is
the only *measured* timing this CPU container can produce.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row
from repro.configs import ARCH_NAMES, get_config, smoke
from repro.models import model_zoo


def micro_steps(n_timing: int = 5) -> list[str]:
    rows = [csv_row("micro_step", "arch", "fn", "us_per_call")]
    key = jax.random.PRNGKey(0)
    for name in ARCH_NAMES:
        cfg = smoke(get_config(name))
        bundle = model_zoo.build(cfg, remat=False)
        params = bundle.init(key)
        B, S = 2, 64
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        labels = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        kwargs = {}
        if cfg.frontend == "vision_stub":
            kwargs["frontend_embeds"] = jnp.zeros(
                (B, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.enc_dec:
            kwargs["enc_embeds"] = jnp.zeros((B, cfg.enc_seq, cfg.d_model),
                                             jnp.bfloat16)
        fwd = jax.jit(lambda p, t, kw: bundle.loss_fn(p, t, labels, **kw))
        fwd(params, tokens, kwargs).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(n_timing):
            fwd(params, tokens, kwargs).block_until_ready()
        us = (time.perf_counter() - t0) / n_timing * 1e6
        rows.append(csv_row("micro_step", name, "loss", f"{us:.0f}"))
    return rows


def kernel_micro(n_timing: int = 3) -> list[str]:
    """us/call of the Pallas kernels in interpret mode vs their jnp refs
    (correctness-path timing only; TPU perf comes from the dry-run)."""
    import numpy as np
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.mamba_scan.kernel import selective_scan
    from repro.kernels.mamba_scan.ref import selective_scan_ref
    from repro.kernels.index_probe.ops import batched_lookup

    rows = [csv_row("kernel_micro", "kernel", "impl", "us_per_call")]
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 256, 64))
    jr = jax.jit(lambda q: attention_ref(q, q, q, causal=True))
    jr(q).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n_timing):
        jr(q).block_until_ready()
    rows.append(csv_row("kernel_micro", "flash_attention", "jnp_ref",
                        f"{(time.perf_counter()-t0)/n_timing*1e6:.0f}"))

    keys = jnp.sort(jax.random.uniform(key, (8 * 256,)))
    queries = jax.random.uniform(key, (128,))
    # explicit interpret mode: the row times the Pallas kernel *body*
    # (auto would resolve to the jnp ref on this CPU container)
    fn = jax.jit(lambda k, qq: batched_lookup(k, qq, tile=256, qcap=64,
                                              mode="interpret"))
    fn(keys, queries)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n_timing):
        fn(keys, queries)[0].block_until_ready()
    rows.append(csv_row("kernel_micro", "index_probe", "pallas_interpret",
                        f"{(time.perf_counter()-t0)/n_timing*1e6:.0f}"))

    u = jax.random.normal(key, (1, 128, 64))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 128, 64)))
    bm = jax.random.normal(key, (1, 128, 8))
    a = -jnp.exp(jax.random.normal(key, (64, 8)))
    jr2 = jax.jit(lambda *xs: selective_scan_ref(*xs))
    jr2(u, dt, bm, bm, a).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n_timing):
        jr2(u, dt, bm, bm, a).block_until_ready()
    rows.append(csv_row("kernel_micro", "mamba_scan", "jnp_ref",
                        f"{(time.perf_counter()-t0)/n_timing*1e6:.0f}"))
    return rows

def _kernel_record(name: str, shape: str, jitted, args, kwargs=None) -> dict:
    """Lower one hot-path kernel, run the HLO through the analytic
    roofline, and emit a record render_roofline.table() can consume
    (mesh="kernel" keeps these rows out of the 16x16 model tables)."""
    from repro.runtime import hlo_analysis as ha

    compiled = jitted.lower(*args, **(kwargs or {})).compile()
    analysis = ha.analyze(compiled.as_text())
    # no 6ND notion for data-movement kernels: the HLO flops *are* the
    # model flops, so useful_ratio pins at 1.0 and the interesting
    # numbers are bytes, arithmetic intensity, and the dominant term
    kind = ha.modelled_kind(jax.devices()[0])
    terms = ha.roofline(analysis, kind, analysis.flops)
    arg_bytes = sum(x.nbytes for x in jax.tree.leaves((args, kwargs))
                    if hasattr(x, "nbytes"))
    ai = (analysis.flops / analysis.bytes_accessed
          if analysis.bytes_accessed else 0.0)
    return {
        "mesh": "kernel", "arch": name, "shape": shape, "status": "ok",
        "analytic_memory": {"total": arg_bytes},
        "arithmetic_intensity": round(ai, 4),
        "hlo_analysis": analysis.as_dict(),
        "roofline": {
            "device_kind": kind,
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "collective_wire_s": terms.collective_wire_s,
            "dominant": terms.dominant,
            "model_flops_per_dev": analysis.flops,
            "hlo_flops_per_dev": analysis.flops,
            "useful_ratio": terms.useful_ratio,
            "roofline_fraction": terms.roofline_fraction,
            "step_time_s": terms.step_time_s,
        },
    }


def kernel_roofline(out: str | None = None) -> list[str]:
    """Arithmetic-intensity records for the serving hot path behind
    kernels/dispatch.py: the index_probe predecessor lookup, the
    fused-tick capture append, and the fused vs unfused K-rung serving
    tick programs.  Lowered on the host backend as a projection against
    the same TPU-v5e roofline constants the dry-run uses; write JSONL
    with ``out=`` and render with benchmarks/render_roofline.py."""
    import json

    from repro.core.litune import LITune, LITuneConfig
    from repro.index.workloads import sample_keys, wr_workload
    from repro.kernels.fused_tick.ops import fused_capture
    from repro.kernels.fused_tick.ref import FIELD_ORDER, fused_capture_ref
    from repro.kernels.index_probe.ops import auto_tile, probe_ranks
    from repro.launch.serving import (O2ServiceConfig, ServeConfig,
                                      TuningService)
    from repro.launch.serving.programs import _pow2_ladder, _step_program

    records = []
    key = jax.random.PRNGKey(0)

    # -- index_probe: the predecessor lookup under every run_reads
    n, q = 4096, 512
    keys = jnp.sort(jax.random.uniform(key, (n,)))
    queries = jax.random.uniform(jax.random.fold_in(key, 1), (q,))
    tile = auto_tile(n)
    for mode in ("ref", "interpret"):        # compiled needs a real accel
        fn = jax.jit(lambda k, qq, _m=mode: probe_ranks(
            k, qq, tile=tile, mode=_m))
        records.append(_kernel_record(
            "index_probe", f"n{n} q{q} t{tile} {mode}", fn, (keys, queries)))

    # -- fused_tick capture append (standalone dispatch = the unfused
    #    tail; the same body fuses into the step program below)
    k_steps, b, h = 1, 2, 8
    dims = {"obs": 6, "next_obs": 6, "h_a": 16, "c_a": 16, "h_q": 16,
            "c_q": 16}
    wide = sum(dims.values())
    new = {f: jax.random.normal(jax.random.fold_in(key, 2 + i),
                                (k_steps, b, dims[f]), jnp.float32)
           for i, f in enumerate(FIELD_ORDER)}
    cap = jnp.zeros((b, h, wide), jnp.float32)
    off = jnp.zeros((b,), jnp.int32)
    for mode in ("ref", "interpret"):
        records.append(_kernel_record(
            "fused_capture", f"B{b} H{h} w{wide} {mode}", fused_capture,
            (cap, new, off), {"mode": mode}))
    records.append(_kernel_record(
        "capture_write", f"B{b} H{h} w{wide} standalone",
        jax.jit(fused_capture_ref), (cap, new, off)))

    # -- the K-rung serving tick, fused vs unfused: bind the real ladder
    #    by serving a short O2 stream, then lower both resident variants
    budget, slots = 8, 2
    cfg = LITuneConfig(index_type="alex", episode_len=budget,
                       lstm_hidden=16, mlp_hidden=32)
    svc = TuningService(LITune(cfg, seed=0), config=ServeConfig(
        slots=slots, horizon_cap=budget, seed=0,
        o2=O2ServiceConfig(enabled=True)))
    for i in range(slots):
        kk = jax.random.fold_in(key, 100 + i)
        data = sample_keys(kk, 512, "mix")
        wl, _ = wr_workload(jax.random.fold_in(kk, 1), data, 1.0,
                            total=512, dist="mix")
        svc.submit(data, wl, 1.0, budget_steps=budget)
    svc.run()
    svc.flush_o2()
    pool = next(iter(svc.pools.values()))
    k = max(_pow2_ladder(budget))
    noise = pool.noise_dev()
    offs = jnp.zeros((slots,), jnp.int32)
    prog_u = _step_program(pool.slice, pool.net_cfg, pool.env_cfg,
                           pool.et_cfg, k)
    prog_f = _step_program(pool.slice, pool.net_cfg, pool.env_cfg,
                           pool.et_cfg, k, capture=True)
    records.append(_kernel_record(
        "serving_tick", f"K{k} slots{slots} unfused_scan", prog_u,
        (pool.params, pool.carry, noise)))
    records.append(_kernel_record(
        "serving_tick", f"K{k} slots{slots} fused", prog_f,
        (pool.params, pool.carry, noise, pool.ensure_cap(), offs)))

    if out:
        with open(out, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")

    rows = [csv_row("kernel_roofline", "kernel", "shape", "gflop",
                    "mbytes_hlo", "ai_flops_per_byte", "dominant",
                    "step_time_us")]
    for r in records:
        hlo = r["hlo_analysis"]
        rows.append(csv_row(
            "kernel_roofline", r["arch"], r["shape"].replace(" ", "_"),
            f"{hlo['flops'] / 1e9:.4f}",
            f"{hlo['bytes_accessed'] / 1e6:.3f}",
            f"{r['arithmetic_intensity']:.3f}",
            r["roofline"]["dominant"],
            f"{r['roofline']['step_time_s'] * 1e6:.2f}"))
    return rows


if __name__ == "__main__":
    import argparse

    from repro.runtime import compile_cache
    compile_cache.enable()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write kernel_roofline records as JSONL "
                         "(render: python -m benchmarks.render_roofline "
                         "PATH kernel)")
    cli = ap.parse_args()
    for row in kernel_roofline(out=cli.out):
        print(row)
