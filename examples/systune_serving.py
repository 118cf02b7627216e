"""Beyond-paper integration (DESIGN.md §Arch-applicability): the paper's
black-box tuning loop pointed at the *serving system itself*.

The environment is the compile-time roofline model: each action picks
system knobs (attention chunk sizes, KV-cache sharding axis, microbatch),
the step lowers+compiles the serve/train program on a host mesh, and the
reward is the negative dominant roofline term -- the same
state/action/reward contract as index tuning, so the same tuner machinery
(here: the SMBO baseline; §Perf uses the full loop) applies.

NOTE: spawns its own 8-device host platform; run standalone:
    PYTHONPATH=src python examples/systune_serving.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import itertools   # noqa: E402
import time        # noqa: E402

import jax         # noqa: E402

from repro.configs import SHAPES, get_config            # noqa: E402
from repro.launch.steps import lower_cell, plan_cell    # noqa: E402
from repro.launch.train import scale_config             # noqa: E402
from repro.runtime import hlo_analysis as ha            # noqa: E402


def evaluate(cfg, shape, mesh, rules):
    plan = plan_cell(cfg, shape, mesh, rules_override=rules)
    compiled = lower_cell(plan).compile()
    analysis = ha.analyze(compiled.as_text(), n_devices=mesh.size)
    terms = ha.roofline(analysis, ha.modelled_kind(mesh.devices.flat[0]),
                        plan.bundle.model_flops(shape) / mesh.size)
    return terms


def main():
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = scale_config(get_config("llama3_8b"), "100m")
    import dataclasses
    shape = dataclasses.replace(SHAPES["decode_32k"], global_batch=8,
                                seq_len=4096)

    # knob space: KV-cache sharding axis x logits sharding
    knob_space = {
        "cache_seq": [None, "model"],
        "kv_heads": [None, "model"],
    }
    print(f"tuning serve_step system knobs for {cfg.name} on 2x4 mesh")
    best, best_rules = None, None
    for values in itertools.product(*knob_space.values()):
        rules = dict(zip(knob_space.keys(), values))
        t0 = time.time()
        try:
            terms = evaluate(cfg, shape, mesh, rules)
        except Exception as e:
            print(f"  {rules}: INVALID ({type(e).__name__})")
            continue
        step = terms.step_time_s
        print(f"  {str(rules):48s} step={step*1e6:9.1f}us "
              f"dom={terms.dominant:10s} ({time.time()-t0:.1f}s to evaluate)")
        if best is None or step < best:
            best, best_rules = step, rules
    print(f"\nbest knobs: {best_rules}  ({best*1e6:.1f}us/step roofline)")


if __name__ == "__main__":
    main()
