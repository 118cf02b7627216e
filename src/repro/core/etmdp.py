"""ET-MDP: Early-Terminated MDP wrapper (paper §4.2, Def. 4.1/4.2).

The CMDP (S, A, H, r, c, C, T) is transformed into an unconstrained MDP with
an absorbing state s_e: when the running cost b_t = sum(c^m_tau + c^r_tau)
exceeds the budget C, the episode transitions to s_e with termination reward
r_e and stays there.  Solved by the DDPG+LSTM backbone (the LSTM is the
context model that generalizes safety across tasks).

The per-step computation (policy act -> critic hidden advance -> env step ->
ET-MDP bookkeeping) lives in one pure core, `_episode_step_core`, shared by
both execution paths:

  * `episode_step`          — jitted, unbatched: drives the serial
                              `rollout_episode` (one request at a time);
  * `batched_episode_step`  — jitted `lax.map` over a carry with a leading
                              slot axis (one service tick);
  * `batched_episode_scan`  — `lax.scan` over K ticks of the map body:
                              drives the multi-tenant
                              `launch/tune_serve.TuningService`.

Because the batched paths map the *same* traced program per slot, a slot in
a B-wide service step produces bitwise-identical rewards/runtimes/actions
to a serial episode started from the same PRNG key
(tests/test_tune_service.py asserts this).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import ddpg, networks as nets
from repro.index import env as E


@dataclasses.dataclass(frozen=True)
class ETMDPConfig:
    cost_budget: float = 1.0        # C: tolerated failures per episode
    termination_reward: float = -1.0  # r_e (small, per the paper)
    enabled: bool = True            # False -> plain (unsafe) episodes


# ------------------------------------------------------------------ carry
def init_episode_carry(key, env_state, obs, net_cfg, batch_shape=()):
    """The per-episode recurrent state threaded through `episode_step`."""
    return {
        "key": key,
        "env": env_state,
        "obs": obs,
        "h_a": nets.zero_hidden(net_cfg, batch_shape),
        "h_q": nets.zero_hidden(net_cfg, batch_shape),
        "b_t": jnp.zeros(batch_shape, jnp.float32),
    }


def _episode_step_core(params, carry, noise_scale, net_cfg,
                       env_cfg: E.EnvConfig, et_cfg: ETMDPConfig,
                       deterministic: bool):
    """One ET-MDP step for a single episode (unbatched carry).

    Returns (carry', outputs).  `outputs["reward"]` is the ET-MDP reward
    (termination reward substituted on early exit), `outputs["early"]` the
    budget-exceeded flag, `outputs["done"]` early-or-horizon.
    """
    with jax.named_scope("agent"):
        key, k_act = jax.random.split(carry["key"])
        action, h_a2 = ddpg.act(params, carry["obs"], carry["h_a"], k_act,
                                net_cfg, noise_scale=noise_scale,
                                deterministic=deterministic)
        # critic hidden advances on (obs, action) for stored-state replay
        _, h_q2 = nets.critic_apply(params["critic0"], carry["obs"],
                                    action, carry["h_q"], net_cfg)
    env2, next_obs, r, done, info = E.step_core(env_cfg, carry["env"], action)
    cost = info["cost"]
    b_t = carry["b_t"] + cost
    if et_cfg.enabled:
        early = b_t > et_cfg.cost_budget
    else:
        early = jnp.zeros_like(done)
    r_val = jnp.where(early, jnp.float32(et_cfg.termination_reward), r)
    next_obs_eff = jnp.where(early, jnp.zeros_like(next_obs), next_obs)
    done_flag = done | early
    new_carry = {"key": key, "env": env2, "obs": next_obs_eff,
                 "h_a": h_a2, "h_q": h_q2, "b_t": b_t}
    outputs = {"action": action, "reward": r_val, "raw_reward": r,
               "runtime_ns": info["runtime_ns"], "cost": cost,
               "early": early, "done": done_flag,
               "memory_bytes": info["memory_bytes"],
               # the transition view, for replay ingestion off the batched
               # paths: pre-step obs/hiddens + the post-step observation
               # (zeroed on early exit, the absorbing state s_e) — exactly
               # what the serial `rollout_episode` pushes into replay
               "obs": carry["obs"], "next_obs": next_obs_eff,
               "h_a": carry["h_a"], "h_q": carry["h_q"]}
    return new_carry, outputs


@partial(jax.jit, static_argnames=("net_cfg", "env_cfg", "et_cfg",
                                   "deterministic"))
def episode_step(params, carry, noise_scale, net_cfg, env_cfg: E.EnvConfig,
                 et_cfg: ETMDPConfig, deterministic: bool = False):
    """Jitted single-episode step (the serial tuning path)."""
    return _episode_step_core(params, carry, noise_scale, net_cfg, env_cfg,
                              et_cfg, deterministic)


def batched_episode_core(params, carry, noise_scale, net_cfg,
                         env_cfg: E.EnvConfig, et_cfg: ETMDPConfig,
                         deterministic: bool = False):
    """One step for B concurrent episodes (un-jitted core): `carry` has a
    leading slot axis on every leaf, `noise_scale` is [B] (per-request
    exploration).  The policy parameters are shared across slots.

    `lax.map` rather than `vmap` on purpose: the map body is the *same
    unbatched program* as the serial `episode_step`, so per-slot results
    are bitwise identical to the serial path at any slot count — a vmapped
    GEMM changes its reduction lowering with batch width and drifts by an
    ulp, which the carmi runtime model (continuous in the action) amplifies
    into observable divergence.  The batching win on the serving path is
    dispatch amortization plus slot-sharding over host devices
    (launch/tune_serve.py), both of which the map keeps.

    Note the program is independent of `env_cfg.episode_len` except for the
    env-internal horizon flag — the serving loop enforces per-request
    budgets host-side, so heterogeneous budgets share one executable.
    """
    return jax.lax.map(
        lambda cn: _episode_step_core(params, cn[0], cn[1], net_cfg,
                                      env_cfg, et_cfg, deterministic),
        (carry, noise_scale))


batched_episode_step = partial(jax.jit, static_argnames=(
    "net_cfg", "env_cfg", "et_cfg", "deterministic"))(batched_episode_core)


def batched_episode_scan(params, carry, noise_scale, n_steps: int, net_cfg,
                         env_cfg: E.EnvConfig, et_cfg: ETMDPConfig,
                         deterministic: bool = False):
    """`n_steps` ticks of `batched_episode_core` under one `lax.scan`
    (un-jitted; the tuning service wraps it in shard_map+jit).  Outputs
    are stacked [n_steps, B, ...].

    The scan body is the *whole* one-tick map program, so each step's
    per-slot math is the proven-bitwise body — scanning the unbatched core
    per slot instead (map-of-scan) refuses XLA the same lowering and
    drifts by an ulp.
    """
    def body(c, _):
        return batched_episode_core(params, c, noise_scale, net_cfg,
                                    env_cfg, et_cfg, deterministic)
    return jax.lax.scan(body, carry, None, length=n_steps)


def batched_episode_core_lanes(params_lanes, carry, noise_scale, net_cfg,
                               env_cfg: E.EnvConfig, et_cfg: ETMDPConfig,
                               deterministic: bool = False):
    """One step for B concurrent episodes with **per-lane** policy params:
    every leaf of `params_lanes` carries a leading slot axis, so each lane
    may serve a different parameter version — the mixed pool a canary swap
    creates (`launch/serving`).  The map body is the *same* unbatched
    program as `batched_episode_core`'s (params ride the mapped operand
    tuple instead of the closure), so a lane whose params equal the shared
    tree produces bitwise-identical outputs to the shared-params program —
    control lanes are untouched by the canary next door.
    """
    return jax.lax.map(
        lambda pcn: _episode_step_core(pcn[0], pcn[1], pcn[2], net_cfg,
                                       env_cfg, et_cfg, deterministic),
        (params_lanes, carry, noise_scale))


def batched_episode_scan_lanes(params_lanes, carry, noise_scale,
                               n_steps: int, net_cfg,
                               env_cfg: E.EnvConfig, et_cfg: ETMDPConfig,
                               deterministic: bool = False):
    """`n_steps` ticks of `batched_episode_core_lanes` under one
    `lax.scan` — the per-lane-params twin of `batched_episode_scan`, with
    the same whole-tick-map scan body (see that docstring for why the
    lowering order matters for bitwise parity)."""
    def body(c, _):
        return batched_episode_core_lanes(params_lanes, c, noise_scale,
                                          net_cfg, env_cfg, et_cfg,
                                          deterministic)
    return jax.lax.scan(body, carry, None, length=n_steps)


def transition_view(outputs: dict) -> dict:
    """The replay-facing slice of a step's outputs, keyed like the
    sequence-replay ring's wide fields (`core.replay.WIDE_FIELDS`):
    pre-step observation and LSTM hiddens plus the post-step observation
    (zeroed on early exit — the absorbing state s_e).  Works on single
    steps, `[K, B, ...]` tick stacks, anything the step core emitted —
    it's a pure re-keying, so the serving path's device-resident capture
    ingests exactly what the serial `rollout_episode` pushes into replay.
    """
    return {"obs": outputs["obs"], "next_obs": outputs["next_obs"],
            "h_a": outputs["h_a"][0], "c_a": outputs["h_a"][1],
            "h_q": outputs["h_q"][0], "c_q": outputs["h_q"][1]}


# jitted reset shared by the serial and batched paths (slot admission
# resets exactly one episode, so the unbatched program is reused there)
reset_episode = jax.jit(E.reset, static_argnames=("cfg",))


def rollout_episode(key, agent_state, net_cfg, env_cfg: E.EnvConfig,
                    et_cfg: ETMDPConfig, data_keys, workload, wr_ratio,
                    noise_scale: float = 0.1, replay=None,
                    deterministic: bool = False):
    """Run one tuning episode under the ET-MDP.

    Returns a summary dict (episode return, best runtime, violations,
    terminated-early flag, params history).  Transitions are pushed into
    `replay` when provided.
    """
    env_state, obs = reset_episode(env_cfg, data_keys, workload, wr_ratio)
    carry = init_episode_carry(key, env_state, obs, net_cfg)
    params = agent_state["params"]

    total_r, best_rt, violations = 0.0, float(env_state["r_best"]), 0.0
    terminated = False
    runtimes, actions = [], []
    for t in range(env_cfg.episode_len):
        carry, out = episode_step(params, carry, noise_scale, net_cfg,
                                  env_cfg, et_cfg,
                                  deterministic=deterministic)
        cost = float(out["cost"])
        violations += cost
        r_val = float(out["reward"])
        early = bool(out["early"])
        done_flag = bool(out["done"])

        if replay is not None:
            # the step emits its own transition view (pre-step obs/hiddens,
            # post-step next_obs) — the same fields the batched serving
            # path captures per slot
            replay.add(np.asarray(out["obs"]), np.asarray(out["action"]),
                       r_val, np.asarray(out["next_obs"]), float(done_flag),
                       cost,
                       (np.asarray(out["h_a"][0]), np.asarray(out["h_a"][1])),
                       (np.asarray(out["h_q"][0]), np.asarray(out["h_q"][1])))
        total_r += r_val
        best_rt = min(best_rt, float(out["runtime_ns"]))
        runtimes.append(float(out["runtime_ns"]))
        actions.append(np.asarray(out["action"]))
        if early:
            terminated = True
            break
        if done_flag:
            break
    return {
        "episode_return": total_r,
        "best_runtime_ns": best_rt,
        "r0_ns": float(carry["env"]["r0"]),
        "violations": violations,
        "terminated_early": terminated,
        "runtimes": runtimes,
        "actions": actions,
        "steps": len(runtimes),
    }
