"""The learned-index tuning environment (the paper's RL environment).

One step = (decode action -> index params) -> rebuild index on the reservoir
sample -> execute the query workload -> metrics/state/reward.  This mirrors
LITune's working process (§3.5): the index is the environment, parameters are
actions, structural+operational metrics are states, reward follows §4.1.

The env is a pure function of its state dict -> jit / vmap / scan friendly,
which is what lets meta-training shard thousands of environments across the
mesh `data` axis (DESIGN.md §2).

Constraint costs (ET-MDP, §4.2): c_m = 1 on memory-budget violation,
c_r = 1 on runtime-budget violation; the ET-MDP wrapper (core/etmdp.py)
terminates when the cumulative cost exceeds C.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import reward as rw
from repro.core.spaces import ParamSpace, alex_space, carmi_space
from repro.index import alex, carmi
from repro.index import cost as C
from repro.index.features import STATE_DIM, state_vector, workload_stats
from repro.kernels.dispatch import KernelConfig


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    index_type: str = "alex"          # alex | carmi
    episode_len: int = 25
    mem_budget: float = C.MEM_BUDGET_BYTES
    runtime_budget: float = C.RUNTIME_BUDGET_NS
    omega: int = 1
    kappa: int = 2
    # kernel execution posture (kernels/dispatch.py): gates routing the
    # read probes through the Pallas index_probe kernel and the serving
    # tick's fused capture.  Frozen and hashable — it rides the jit
    # static args and serving program-cache keys, so two postures never
    # share an executable.  The default resolves to the bitwise jnp
    # reference on CPU and the compiled kernels on GPU/TPU
    kernel: KernelConfig = KernelConfig()

    @property
    def space(self) -> ParamSpace:
        return alex_space() if self.index_type == "alex" else carmi_space()

    def with_episode_len(self, n: int) -> "EnvConfig":
        """Same environment, different horizon — the tuning/O2/serving
        paths re-horizon per request without touching any other knob."""
        return dataclasses.replace(self, episode_len=n)


def _backend(index_type: str):
    mod = alex if index_type == "alex" else carmi
    return mod


def evaluate_params(cfg: EnvConfig, params_raw: dict, data_keys, workload,
                    wr_ratio):
    """Build + run one workload under `params_raw`.

    Returns (runtime_ns, state_pieces, violations) -- the core experiment
    primitive shared by the RL env and every baseline tuner.
    """
    mod = _backend(cfg.index_type)
    read_args = () if cfg.index_type == "alex" else (params_raw,)
    # CARMI probes for its inserts as for its reads, under the same posture
    insert_kw = {} if cfg.index_type == "alex" else {"kernel": cfg.kernel}
    with jax.named_scope("env_build"):
        idx = mod.build(data_keys, params_raw)
    with jax.named_scope("env_reads"):
        read_ns, read_m = mod.run_reads(idx, workload["reads"], *read_args,
                                        kernel=cfg.kernel)
    with jax.named_scope("env_inserts"):
        idx, ins_ns, ins_m = mod.run_inserts(idx, workload["inserts"],
                                             params_raw, **insert_kw)
    with jax.named_scope("env_observe"):
        n_ops = workload["reads"].shape[0] + workload["inserts"].shape[0]
        # avg ns per operation (paper metric)
        runtime = (read_ns + ins_ns) / n_ops
        mem = mod.memory_bytes(idx, params_raw) \
            if cfg.index_type == "alex" else mod.memory_bytes(idx)
        c_m = (mem > cfg.mem_budget).astype(jnp.float32)
        c_r = ((read_ns + ins_ns) > cfg.runtime_budget).astype(jnp.float32)
    return runtime, (idx, read_m, ins_m), {"c_m": c_m, "c_r": c_r,
                                           "memory_bytes": mem}


def reset(cfg: EnvConfig, data_keys, workload, wr_ratio,
          default_raw: dict | None = None):
    """Initial env state: evaluate the DEFAULT parameters to set R_0."""
    mod = _backend(cfg.index_type)
    default_raw = default_raw or {
        k: jnp.float32(v) for k, v in mod.DEFAULTS.items()}
    r0, (idx, read_m, ins_m), viol = evaluate_params(
        cfg, default_raw, data_keys, workload, wr_ratio)
    with jax.named_scope("env_observe"):
        ws = workload_stats(data_keys, wr_ratio)
        obs = state_vector(idx, read_m, ins_m, r0, r0, r0, ws)
    env_state = {
        "data_keys": data_keys,
        "reads": workload["reads"],
        "inserts": workload["inserts"],
        "wr_ratio": jnp.asarray(wr_ratio, jnp.float32),
        "r0": r0, "r_prev": r0, "r_best": r0,
        "t": jnp.int32(0),
        "cum_cost": jnp.float32(0.0),
    }
    return env_state, obs


def step_core(cfg: EnvConfig, env_state: dict, action: jax.Array):
    """One tuning step (un-jitted pure core). action in [-1,1]^dim.

    This is the composable form: `step` below is its jitted entry point,
    `core/etmdp.py` inlines it into the fused episode step,
    `core/parallel.py` vmaps it over the meta-batch, and the serving path
    (`launch/tune_serve.py`) `lax.map`s it over a slot axis.
    """
    space = cfg.space
    with jax.named_scope("env_build"):
        params_raw = space.decode(action)
    workload = {"reads": env_state["reads"], "inserts": env_state["inserts"]}
    runtime, (idx, read_m, ins_m), viol = evaluate_params(
        cfg, params_raw, env_state["data_keys"], workload,
        env_state["wr_ratio"])
    with jax.named_scope("env_observe"):
        r = rw.reward(runtime, env_state["r0"], env_state["r_prev"],
                      cfg.omega, cfg.kappa)
        ws = workload_stats(env_state["data_keys"], env_state["wr_ratio"])
        obs = state_vector(idx, read_m, ins_m, runtime, env_state["r_prev"],
                           env_state["r0"], ws)
    cost = viol["c_m"] + viol["c_r"]
    new_state = dict(env_state)
    new_state["r_prev"] = runtime
    new_state["r_best"] = jnp.minimum(env_state["r_best"], runtime)
    new_state["t"] = env_state["t"] + 1
    new_state["cum_cost"] = env_state["cum_cost"] + cost
    done = new_state["t"] >= cfg.episode_len
    info = {"runtime_ns": runtime, "cost": cost, **viol}
    return new_state, obs, r, done, info


step = jax.jit(step_core, static_argnames=("cfg",))


def obs_dim() -> int:
    return STATE_DIM
