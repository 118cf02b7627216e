import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below happens only after the device count is pinned ---
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ARCH_NAMES, SHAPES, cell_is_runnable, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh                          # noqa: E402
from repro.launch.steps import analytic_memory, lower_cell, plan_cell                        # noqa: E402
from repro.runtime import hlo_analysis as ha                                # noqa: E402
from repro.runtime.mesh_utils import DATA_AXES as mesh_utils_DATA_AXES       # noqa: E402

"""Multi-pod dry-run: .lower().compile() for every (arch x shape x mesh).

For each cell this proves (a) the sharding config is coherent (no
divisibility / resharding errors), (b) the program fits (memory_analysis),
and (c) extracts the roofline terms (flops / bytes / collective bytes) via
runtime/hlo_analysis.py.  See EXPERIMENTS.md §Dry-run and §Roofline.
"""


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_override: dict | None = None,
             collect_hlo: bool = False, opt: bool = False) -> dict:
    import dataclasses
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    record: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "profile": "optimized" if opt else "baseline",
    }
    fused_scopes = ()
    if opt:
        # beyond-paper profile (EXPERIMENTS.md §Perf): explicit-a2a MoE,
        # sequence-parallel activations in train, kernel-projected flash
        if cfg.n_experts:
            cfg = dataclasses.replace(cfg, moe_impl="shard_map")
        if shape.kind == "decode" and not cfg.enc_dec:
            cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
            # shard weights over data too (ZeRO-inference): resident params
            # /dp at the cost of per-layer all-gathers (decode reads every
            # weight once per token anyway)
            rules_override = {**(rules_override or {}),
                              "embed": mesh_utils_DATA_AXES}
        # NOTE: a {"seq": "model"} Megatron-SP rule was tried and REFUTED:
        # GSPMD re-replicates the batch axis on the seq gather-back
        # (17GB/layer all-gathers); see EXPERIMENTS.md §Perf cell B.
        fused_scopes = ("flash_fusible",)
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = why
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    try:
        microbatches = 1
        if opt and shape.kind == "train" and not cfg.n_experts:
            # MoE-EP cells are excluded: splitting the batch shrinks the
            # all_to_all payloads into their padding floors (measured 4.3x
            # compute / 25x collective regression on qwen3 at mb=8); their
            # fit lever is the multi-pod mesh. See §Perf.
            # pick the smallest power-of-two microbatch count that brings the
            # analytic per-device residency under the 16 GiB HBM budget
            probe = plan_cell(cfg, shape, mesh, rules_override=rules_override,
                              fsdp="auto_size")
            need = analytic_memory(probe)
            fixed = need["params"] + need["opt_state"] + need["grads"] \
                + need["inputs"]
            act = need["total"] - fixed
            budget = 15 * 2**30
            while microbatches < 32 and \
                    fixed + act / microbatches > budget:
                microbatches *= 2
        record["microbatches"] = microbatches
        plan = plan_cell(cfg, shape, mesh, rules_override=rules_override,
                         fsdp="auto_size" if opt else None,
                         microbatches=microbatches)
        lowered = lower_cell(plan)
        record["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t0, 2)

        mem = compiled.memory_analysis()
        record["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        }
        record["analytic_memory"] = analytic_memory(plan)
        ca = ha.xla_cost_analysis(compiled)
        record["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }

        analysis = ha.analyze(compiled.as_text(), n_devices=n_dev,
                              fused_scopes=fused_scopes)
        model_flops_dev = plan.bundle.model_flops(shape) / n_dev
        kind = ha.modelled_kind(mesh.devices.flat[0])
        terms = ha.roofline(analysis, kind, model_flops_dev)
        record["hlo_analysis"] = analysis.as_dict()
        record["roofline"] = {
            "device_kind": kind,
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "collective_wire_s": terms.collective_wire_s,
            "dominant": terms.dominant,
            "model_flops_per_dev": model_flops_dev,
            "hlo_flops_per_dev": analysis.flops,
            "useful_ratio": terms.useful_ratio,
            "roofline_fraction": terms.roofline_fraction,
            "step_time_s": terms.step_time_s,
        }
        record["n_params"] = plan.bundle.n_params()
        record["n_active_params"] = plan.bundle.n_active_params()
        record["status"] = "ok"
        if collect_hlo:
            record["hlo_text"] = compiled.as_text()
    except Exception as e:  # a failing cell is a bug; record and surface it
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    return record


def run_litune_cell(index_type: str, multi_pod: bool,
                    meta_batch: int = 512) -> dict:
    """The paper-technique dry-run cell: lower + compile LITune's
    mesh-parallel meta-training rollout (core/parallel.py) with the tuning
    instances sharded over the data axes of the production mesh."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core.ddpg import DDPGConfig
    from repro.core.networks import NetConfig
    from repro.core import parallel as par
    from repro.core import ddpg as ddpg_mod
    from repro.index import env as E
    from repro.runtime import mesh_utils

    record = {"arch": f"litune_{index_type}", "shape": "meta_train",
              "mesh": "2x16x16" if multi_pod else "16x16",
              "profile": "paper-technique"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        env_cfg = E.EnvConfig(index_type=index_type, episode_len=8)
        net_cfg = NetConfig(obs_dim=E.obs_dim(),
                            action_dim=env_cfg.space.dim)
        ddpg_cfg = DDPGConfig()
        sds, axes = par.litune_cell_inputs(env_cfg, net_cfg, meta_batch)
        shard = {k: NamedSharding(mesh, mesh_utils.logical_to_spec(
            axes[k], sds[k].shape, mesh)) for k in sds}
        agent = ddpg_mod.init_state(jax.numpy.array([0, 0], dtype="uint32")
                                    if False else jax.random.PRNGKey(0),
                                    net_cfg, ddpg_cfg)
        params_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            agent["params"])

        def rollout(params, data_keys, reads, inserts, wr, key):
            env_states, obs = par.batched_reset(
                env_cfg, data_keys, {"reads": reads, "inserts": inserts}, wr)
            env_states, obs, traj = par.parallel_rollout.__wrapped__(
                params, env_states, obs, key, env_cfg, net_cfg, ddpg_cfg, 8)
            return traj["reward"].sum(), traj["cost"].sum()

        t0 = time.time()
        with mesh:
            lowered = jax.jit(rollout).lower(
                params_sds,
                jax.ShapeDtypeStruct(sds["data_keys"].shape, jnp.float32,
                                     sharding=shard["data_keys"]),
                jax.ShapeDtypeStruct(sds["reads"].shape, jnp.float32,
                                     sharding=shard["reads"]),
                jax.ShapeDtypeStruct(sds["inserts"].shape, jnp.float32,
                                     sharding=shard["inserts"]),
                jax.ShapeDtypeStruct(sds["wr"].shape, jnp.float32,
                                     sharding=shard["wr"]),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t0, 2)
        analysis = ha.analyze(compiled.as_text(), n_devices=mesh.size)
        record["hlo_analysis"] = analysis.as_dict()
        mem = compiled.memory_analysis()
        record["memory"] = {"temp_bytes": int(mem.temp_size_in_bytes),
                            "argument_bytes": int(mem.argument_size_in_bytes)}
        record["status"] = "ok"
        record["meta_batch"] = meta_batch
    except Exception as e:
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    return record


def fmt_row(r: dict) -> str:
    if r["status"] == "skipped":
        return (f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} SKIP "
                f"({r['reason'][:60]}...)")
    if r["status"] == "error":
        return (f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} ERROR "
                f"{r['error'][:90]}")
    rf = r["roofline"]
    mem_gb = r["analytic_memory"]["total"] / 2**30
    return (f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} ok "
            f"compile={r['compile_s']:7.1f}s mem/dev={mem_gb:6.2f}GiB "
            f"compute={rf['compute_s']:.3e}s memory={rf['memory_s']:.3e}s "
            f"coll={rf['collective_s']:.3e}s dom={rf['dominant']:10s} "
            f"useful={rf['useful_ratio']:.2f} roofline={rf['roofline_fraction']:.2f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 multi-pod mesh (default: 16x16 single pod)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper optimized profile (see §Perf)")
    ap.add_argument("--litune", action="store_true",
                    help="also lower the paper-technique meta-training cells")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_NAMES
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, multi_pod, opt=args.opt)
                records.append(r)
                print(fmt_row(r), flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(
                            {k: v for k, v in r.items() if k != "hlo_text"})
                            + "\n")
    if args.litune:
        for multi_pod in meshes:
            for index_type in ("alex", "carmi"):
                r = run_litune_cell(index_type, multi_pod)
                records.append(r)
                if r["status"] == "ok":
                    coll = r["hlo_analysis"]["collective_bytes"]
                    print(f"litune_{index_type:6s} meta_train "
                          f"{r['mesh']:8s} ok compile={r['compile_s']:.1f}s "
                          f"coll_bytes={coll:.2e}", flush=True)
                else:
                    print(f"litune_{index_type} meta_train {r['mesh']} "
                          f"ERROR {r['error'][:80]}", flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(r) + "\n")
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\n{len(records)} cells: "
          f"{sum(r['status'] == 'ok' for r in records)} ok, "
          f"{sum(r['status'] == 'skipped' for r in records)} skipped, "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
