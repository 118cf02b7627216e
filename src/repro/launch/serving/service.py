"""`TuningService`: the thin composition root of the serving stack.

Layering (each layer a sibling module with an explicit seam):

    topology.py    device placement: pool slices, annex slice, ring home
        |  which devices serve, learn, and assess
    scheduler.py   admission queue, deadlines, slot-scheduling policy
        |  which requests enter which pool, at what pool width
    pools.py       slot-batched episode execution (device carries)
        |  episodes advance K steps/tick, retire into summaries
    o2_runtime.py  continuous tuning: capture -> learner -> assessments
        |  hot-swaps pool params when the offline model wins
    slo.py         per-request latency tracking + breach accounting
    programs.py    process-wide compiled-program cache under everything

The service itself only orchestrates: one `step()` drains ready O2
verdicts, applies queued-deadline drops, resizes pools per the policy,
admits a wave, advances every active pool by one K-step program, retires
finished episodes, enforces running deadlines, and hands the retired set
to the O2 runtime.  All PR 2/3 parity guarantees (strict-order decisions,
bitwise replay/params, zero program-cache re-traces — now also across
pool resizes) are carried by the layers, not re-implemented here.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import warnings
from collections import deque

import jax
import numpy as np

from repro.launch.serving import programs
from repro.launch.serving.config import (LEGACY_KWARGS, ServeConfig,
                                         config_from_legacy)
from repro.launch.serving.o2_runtime import O2Runtime, O2ServiceConfig
from repro.launch.serving.pools import _SlotPool
from repro.launch.serving.programs import (_mixed_params_program,
                                           _pow2_ladder, _reset_program,
                                           _step_program)
from repro.launch.serving.scheduler import (Scheduler, SlotPolicy,
                                            StaticSlotPolicy, TuneRequest)
from repro.launch.serving.slo import SLOConfig, SLOTracker
from repro.launch.serving.stats import (PoolStats, SchedulerStats,
                                        ServiceStats)
from repro.launch.serving.topology import ServingTopology
from repro.launch.serving.tracing import span
from repro.runtime import compile_cache


class TuningService:
    """Multi-tenant tuning engine over pretrained LITune agents.

    `agents` maps index_type -> a `core.litune.LITune` (or anything with
    `.cfg` and `.state`); a single LITune is accepted and keyed by its own
    `cfg.index_type`.  Submit requests, then `run()` — per-request
    summaries come back keyed by request id.

    The serving posture — slot counts, O2, scheduling policy, SLOs,
    topology, and the hot-swap trust policy — is one frozen
    `ServeConfig` passed as `config=` (`serving/config.py`).  The
    pre-consolidation per-knob kwargs (`slots`, `horizon_cap`, `seed`,
    `o2`, `policy`, `slo`, `clock`, `topology`) still work through a
    thin adapter that builds the equivalent `ServeConfig` and emits a
    `DeprecationWarning`; mixing `config=` with legacy kwargs raises.
    `policy`/`clock`/`topology` keep None-means-default semantics
    (static policy, `time.perf_counter`, flat host layout over
    `jax.devices()`); pass
    `topology=ServingTopology.from_mesh(make_production_mesh(), slots)`
    and one service instance spans a pod — placement is a config field,
    not a rewrite.
    """

    def __init__(self, agents, slots: int | None = None,
                 horizon_cap: int | None = None, seed: int | None = None,
                 o2: O2ServiceConfig | None = None,
                 policy: SlotPolicy | None = None,
                 slo: SLOConfig | None = None, clock=None,
                 topology: ServingTopology | None = None, swap=None, *,
                 config: ServeConfig | None = None):
        legacy = {"slots": slots, "horizon_cap": horizon_cap,
                  "seed": seed, "o2": o2, "policy": policy, "slo": slo,
                  "clock": clock, "topology": topology, "swap": swap}
        passed = {k: v for k, v in legacy.items() if v is not None}
        if config is not None:
            if passed:
                raise TypeError(
                    f"pass the serving posture either as "
                    f"config=ServeConfig(...) or through the legacy "
                    f"kwargs, not both (got config= plus "
                    f"{sorted(passed)})")
        else:
            if passed:
                warnings.warn(
                    f"TuningService's per-knob kwargs "
                    f"({', '.join(LEGACY_KWARGS)}) are deprecated; "
                    f"pass config=ServeConfig(...) instead",
                    DeprecationWarning, stacklevel=2)
            config = config_from_legacy(**passed)
        if not isinstance(agents, dict):
            agents = {agents.cfg.index_type: agents}
        self.agents = agents
        self.config = config
        self.slots = config.slots
        self.horizon_cap = config.horizon_cap
        self.o2 = config.o2
        self.policy = (config.policy if config.policy is not None
                       else StaticSlotPolicy())
        self.slo_cfg = config.slo
        self.swap_cfg = config.swap
        self.clock = (config.clock if config.clock is not None
                      else time.perf_counter)
        self.key = jax.random.PRNGKey(config.seed)
        # every placement decision — serving slices, annex slice, ring
        # home — is the topology layer's (topology.py); the service only
        # consumes slices
        self.topology = (config.topology if config.topology is not None
                         else ServingTopology.host(config.slots))
        self.topology.validate_slots(config.slots)
        self.pools: dict[tuple, _SlotPool] = {}
        self.o2rt: O2Runtime | None = None
        if self.o2.enabled:
            if self.topology.annex_shared:
                # single-device hosts (and annex_rows=0 carvings) run the
                # learner and assessments on a serving device: correct,
                # but the O2 work stops overlapping serving — say so once
                # instead of silently co-locating
                warnings.warn(
                    f"O2 annex shares device(s) "
                    f"{self.topology.annex.device_ids} with the serving "
                    f"slice: learner and assessment work will queue "
                    f"behind serving fetches (stats()['o2'] reports "
                    f"annex_shared)", RuntimeWarning, stacklevel=2)
            self.o2rt = O2Runtime(
                agents, self.o2, self.pools, self.topology,
                horizon_cap=self.horizon_cap,
                max_assess_width=2 * self.slots,
                swap_cfg=self.swap_cfg, clock=self.clock,
                health_cfg=config.health, kernel=config.kernel)
        self.scheduler = Scheduler(self.policy,
                                   strict_order=(self.o2.enabled
                                                 and self.o2.strict_order))
        self.slo = SLOTracker(self.clock)
        self.results: dict[int, dict] = {}
        self._programs: dict[tuple, object] = {}   # compiled-program cache
        self.program_misses = 0
        self.program_hits = 0
        self.service_steps = 0
        self.episode_steps = 0
        self._next_rid = 0

    # ------------------------------------------------- layer delegation
    @property
    def queue(self) -> deque:
        return self.scheduler.queue

    @queue.setter
    def queue(self, value):
        self.scheduler.queue = deque(value)

    @property
    def tenants(self):
        return self.o2rt.tenants if self.o2rt is not None else {}

    @property
    def _o2_pending(self):
        return self.o2rt.pending if self.o2rt is not None else {}

    @property
    def o2_pending_missing(self) -> int:
        return self.o2rt.pending_missing if self.o2rt is not None else 0

    @property
    def assessments(self) -> int:
        return self.o2rt.assessments if self.o2rt is not None else 0

    def _in_trial(self, index_type: str) -> bool:
        """Whether the tenant has a live swap trial (canary stage or
        post-promotion watch window)."""
        return self.o2rt is not None and index_type in self.o2rt.trials

    def _hot_swap(self, index_type: str, req: TuneRequest,
                  window: int | None = None, params=None):
        self.o2rt.hot_swap(index_type, req, window=window, params=params)

    def flush_o2(self, deadline_s: float | None = None) -> dict | None:
        """Settle all in-flight O2 work (see `O2Runtime.flush`); callers
        that only need serving results never have to.  Returns the flush
        report ({deadline_hit, abandoned_backlog, abandoned_inflight,
        elapsed_s}; None with O2 off).  `deadline_s` defaults to
        `HealthConfig.flush_deadline_s` (None -> settle fully — but a
        demoted annex or hung dispatch is abandoned rather than hung
        on, so the call is bounded either way)."""
        if self.o2rt is None:
            return None
        if deadline_s is None:
            deadline_s = self.config.health.flush_deadline_s
        return self.o2rt.flush(deadline_s=deadline_s)

    # ------------------------------------------------------------ intake
    def submit(self, data_keys, workload, wr_ratio: float,
               budget_steps: int | None = None, index_type: str | None = None,
               noise_scale: float | None = None,
               deterministic: bool = False, key=None,
               deadline_s: float | None = None,
               on_breach: str | None = None) -> int:
        """Enqueue one tuning request; returns its request id.

        `deadline_s` (service-clock seconds from now; default the
        service's `SLOConfig.default_deadline_s`) bounds the request's
        total latency; `on_breach` ("truncate" | "drop") picks what a
        mid-flight breach returns."""
        if index_type is None:
            index_type = next(iter(self.agents))
        if index_type not in self.agents:
            raise KeyError(f"no agent for index_type={index_type!r} "
                           f"(have {sorted(self.agents)})")
        tuner = self.agents[index_type]
        if budget_steps is None:
            budget_steps = tuner.cfg.episode_len
        if budget_steps > self.horizon_cap:
            raise ValueError(f"budget_steps={budget_steps} exceeds "
                             f"horizon_cap={self.horizon_cap}")
        if budget_steps < 1:
            raise ValueError(f"budget_steps={budget_steps} must be >= 1")
        # `deterministic` is served as noise_scale=0.0 through the shared
        # stochastic program (a per-request static branch would split the
        # pool's executable): for the tanh-bounded actor, a + 0*noise
        # clipped to [-1,1] equals the deterministic branch's raw output,
        # so recommendations match LITune.tune(deterministic=True)
        if noise_scale is None:
            noise_scale = 0.0 if deterministic else 0.05
        if deadline_s is None:
            deadline_s = self.slo_cfg.default_deadline_s
        if on_breach is None:
            on_breach = self.slo_cfg.on_breach
        if on_breach not in ("truncate", "drop"):
            raise ValueError(f"on_breach={on_breach!r} must be "
                             f"'truncate' or 'drop'")
        # the PRNG split comes after every validation path: a rejected
        # submission must not perturb later requests' auto-drawn keys
        if key is None:
            self.key, key = jax.random.split(self.key)
        # under O2 the submitted key is the *window* key: admission
        # batch-splits it into the episode key (k_on) and the assessment
        # remainder, mirroring O2System.tune_window's PRNG discipline so
        # decisions line up with the serial O2 loop
        rid = self._next_rid
        self._next_rid += 1
        # numpy (uncommitted) on purpose: admission programs place these
        # per the pool's mesh; committed jax arrays would pin device 0
        self.scheduler.submit(TuneRequest(
            rid=rid, data_keys=np.asarray(data_keys),
            workload={"reads": np.asarray(workload["reads"]),
                      "inserts": np.asarray(workload["inserts"])},
            wr_ratio=float(wr_ratio), budget_steps=int(budget_steps),
            index_type=index_type, key=key,
            noise_scale=float(noise_scale), deadline_s=deadline_s,
            on_breach=on_breach, submitted_at=self.clock()))
        return rid

    # ------------------------------------------------------------ pools
    def _pool_key(self, req: TuneRequest) -> tuple:
        return (req.index_type, int(req.data_keys.shape[0]),
                int(req.workload["reads"].shape[0]),
                int(req.workload["inserts"].shape[0]))

    def _pool_for(self, req: TuneRequest) -> _SlotPool:
        pk = self._pool_key(req)
        if pk not in self.pools:
            tuner = self.agents[req.index_type]
            env_cfg = tuner.cfg.env_cfg().with_episode_len(self.horizon_cap)
            # the service's kernel posture rides the pool's env config:
            # frozen dataclasses hash by value, so the default posture
            # keys the same resident programs as the serial path
            env_cfg = dataclasses.replace(env_cfg, kernel=self.config.kernel)
            # under O2, pools serve the tenant's (possibly already swapped)
            # online model rather than the agent's frozen pretrained state
            # (`online_params` — a cold fleet tenant serves its seed tree
            # without materializing a per-tenant copy)
            params = (self.tenants[req.index_type].online_params()
                      if self.o2.enabled else tuner.state["params"])
            # pools pin to the topology's carved slices round-robin by
            # creation order (one flat slice on hosts; one row per pool
            # on a carved production mesh)
            slice_ = self.topology.pool_slice(len(self.pools))
            self.pools[pk] = _SlotPool(env_cfg, tuner.cfg.net_cfg(),
                                       tuner.cfg.et_cfg(), params,
                                       self.slots, slice_,
                                       capture=self.o2.enabled)
            if self.o2.enabled and self.swap_cfg.canary:
                # pre-bind the canary-side programs with the pool: the
                # per-lane K ladder (same lru cache as the shared-params
                # ladder, so `programs_resident` is flat across a whole
                # canary->promote/rollback cycle) and the params mix.
                # Binding is an lru insert; XLA still traces lazily
                pool = self.pools[pk]
                for k in _pow2_ladder(self.horizon_cap):
                    self._pool_step_program(pk, pool, k, per_lane=True)
                _mixed_params_program(slice_, self.slots)
        return self.pools[pk]

    def _size_ladder(self, pool: _SlotPool) -> list[int]:
        """Pool widths the policy may choose from: the initial width plus
        slice-width multiples doubling up to the policy cap — every entry
        shards over the pool's topology slice, and the doubling keeps the
        set of traced carry shapes (and therefore resident executables)
        small."""
        nd = pool.slice.width
        cap = max(getattr(self.policy, "max_slots", self.slots),
                  self.slots)
        sizes = {self.slots}
        s = nd
        while s <= cap:
            sizes.add(s)
            s *= 2
        return sorted(s for s in sizes if s % nd == 0)

    # --------------------------------------------------------- programs
    def _fused(self, pool: _SlotPool) -> bool:
        """Whether this pool's serving tick runs the fused-tick step
        variant (scan + capture append in one program)."""
        return pool.capture and self.config.kernel.fused_tick

    @staticmethod
    def _step_key(pk: tuple, pool: _SlotPool, k: int,
                  per_lane: bool, capture: bool) -> tuple:
        return ("step-lanes" if per_lane else "step", pk, pool.slots, k,
                capture)

    def _pool_step_program(self, pk: tuple, pool: _SlotPool, k: int,
                           per_lane: bool = False):
        """K-step slot program, cached process-wide on
        (slice, frozen configs, width, K) so mixed alex/carmi request
        streams — and successive service instances, and pools returning
        to a previously-served width — alternate between resident
        executables, never re-tracing.  `per_lane` selects the canary
        variant (params carry a leading slot axis); the fused-capture
        variant is derived here from the pool + kernel posture so every
        caller (pre-binds and ticks) agrees on the key.  All variants
        share `_step_program`'s lru cache."""
        capture = self._fused(pool)
        prog_key = self._step_key(pk, pool, k, per_lane, capture)
        if prog_key not in self._programs:
            self.program_misses += 1
            self._programs[prog_key] = _step_program(
                pool.slice, pool.net_cfg, pool.env_cfg, pool.et_cfg, k,
                per_lane=per_lane, capture=capture)
        else:
            self.program_hits += 1
        return self._programs[prog_key]

    def _pool_reset_program(self, pool: _SlotPool, width: int):
        # a wave that does not divide the pool's slice lowers onto the
        # widest sub-slice it does divide (1-device at worst)
        return _reset_program(pool.slice.narrow(width), pool.env_cfg)

    # ------------------------------------------------------------ serving
    def _admit(self, pk: tuple, pool: _SlotPool, admits: list[TuneRequest]):
        """Admit up to `len(free slots)` requests into `pool` with one
        batched reset (padded to a power-of-two width)."""
        free = pool.free_slots()
        assert len(admits) <= len(free)
        m = len(admits)
        widths = sorted(set(_pow2_ladder(pool.slots) + [pool.slots]))
        width = next(w for w in widths if w >= m)
        pad = width - m
        with span("litune.admit"):
            with span("litune.admit.stage"):
                reqs = admits + [admits[0]] * pad
                data = np.stack([r.data_keys for r in reqs])
                reads = np.stack([r.workload["reads"] for r in reqs])
                ins = np.stack([r.workload["inserts"] for r in reqs])
                wr = np.asarray([r.wr_ratio for r in reqs], np.float32)
                keys = np.stack([np.asarray(r.key) for r in reqs])
                assess_keys = None
                if self.o2rt is not None:
                    keys, assess_keys = self.o2rt.admit_keys(keys)
            with span("litune.admit.reset"):
                env_states, obs = self._pool_reset_program(pool, width)(
                    data, reads, ins, wr)
                if width % pool.slice.width != 0:
                    # narrow reset ran on a sub-slice mesh; rehome to host
                    # so the scatter (placed on the pool's slice) accepts it
                    env_states, obs = jax.device_get((env_states, obs))
                if m == pool.slots and pool.carry is None:
                    pool.carry = programs._build_carry_program(
                        pool.slice, pool.net_cfg, pool.slots)(
                        keys, env_states, obs)
                    slots_used = list(range(pool.slots))
                else:
                    if pool.carry is None:
                        # first admission with a partial wave: seed every
                        # slot with episode 0 so idle slots hold valid
                        # (ignored) state
                        es0, obs0 = jax.device_get(
                            (jax.tree.map(lambda x: x[:1], env_states),
                             obs[:1]))
                        full = jax.tree.map(
                            lambda x: np.broadcast_to(
                                x, (pool.slots,) + x.shape[1:]),
                            (es0, obs0))
                        pool.carry = programs._build_carry_program(
                            pool.slice, pool.net_cfg, pool.slots)(
                            np.broadcast_to(keys[:1], (pool.slots,)
                                            + keys.shape[1:]),
                            full[0], full[1])
                    slots_used = free[:m]
                    idx = np.asarray(slots_used + [pool.slots] * pad,
                                     np.int32)
                    pool.carry = programs._admit_scatter_program(
                        pool.slice, pool.net_cfg, pool.slots)(
                        pool.carry, idx, keys, env_states, obs)
            with span("litune.admit.sync"):
                r0s = np.asarray(jax.device_get(env_states["r_best"]))
            now = self.clock()
            for j, (slot, req) in enumerate(zip(slots_used, admits)):
                pool.mark_admitted(slot, req, float(r0s[j]))
                self.slo.on_admit(req, now)
                if self.o2rt is not None:
                    self.o2rt.observe_admission(req, assess_keys[j])

    def _admit_from_queue(self):
        """Fill free slots with queued requests (FIFO per pool group),
        one batched reset per pool per tick; the scheduler picks the
        admissions (and, in strict-order O2 mode, admits one window at a
        time in submission order)."""
        per_pool = self.scheduler.select(
            self.pools, self._pool_for, self._pool_key,
            any_active=any(p.n_active for p in self.pools.values()),
            now=self.clock())
        for pk, admits in per_pool.items():
            self._admit(pk, self.pools[pk], admits)

    def _drop_breached_queued(self):
        """Queued requests past their deadline never occupy a slot: they
        retire straight into a dropped result; under an `EDFSlotPolicy`,
        requests whose budget provably cannot fit their deadline at the
        measured tick rate are pre-dropped the same way (flagged
        `pre_dropped`), freeing their queue time for feasible work."""
        now = self.clock()
        for req in self.scheduler.drop_breached(now):
            self.results[req.rid] = {
                "dropped": True, "slo_breached": True, "steps": 0,
                "terminated_early": False}
            self.slo.on_drop_queued(req, now)
            if self._in_trial(req.index_type):
                self.slo.note_trial_breach()
        for req in self.scheduler.pre_drop_hopeless(now):
            self.results[req.rid] = {
                "dropped": True, "slo_breached": True, "pre_dropped": True,
                "steps": 0, "terminated_early": False}
            self.slo.on_drop_queued(req, now, pre=True)
            if self._in_trial(req.index_type):
                self.slo.note_trial_breach()

    def _apply_slot_policy(self):
        """Consult the slot policy for every pool (pools for queued
        requests are created first so a burst can grow its pool before
        the first admission) and apply planned resizes."""
        if isinstance(self.policy, StaticSlotPolicy) or \
                self.scheduler.strict_order:
            return
        for req in self.scheduler.queue:
            self._pool_for(req)
        queued = self.scheduler.queued_by_pool(self._pool_key)
        for pk, pool in self.pools.items():
            if pool.canary_lanes is not None:
                # a resize would re-map lanes mid-trial and shuffle the
                # canary/control arms; the pool resumes policy sizing
                # the tick after the trial promotes or rolls back
                continue
            new = self.scheduler.plan_resize(pk, pool, queued.get(pk, 0),
                                             self._size_ladder(pool))
            if new is not None:
                pool.resize(new)

    def _enforce_running_deadlines(self, retired: list):
        """Running requests past their deadline retire before the next
        tick advances them further: truncated (best-so-far summary,
        flagged) or dropped, per request — either way the slot frees for
        this tick's admissions.  Slots are independent lanes of the same
        mapped program, so the early retirement never perturbs the
        surviving slots' decisions."""
        now = self.clock()
        for pk, pool in self.pools.items():
            for slot, req in enumerate(pool.requests):
                if req is None or req.deadline_s is None:
                    continue
                if now - req.submitted_at <= req.deadline_s:
                    continue
                if pool.steps_taken[slot] == 0:
                    continue        # admitted this tick; gets one tick
                rreq, summary, narrow = pool.retire(slot, False)
                if self._in_trial(rreq.index_type):
                    # attribution for the swaps block: this breach landed
                    # while the tenant's canary/watch trial was live
                    self.slo.note_trial_breach()
                if rreq.on_breach == "drop":
                    self.results[rreq.rid] = {
                        "dropped": True, "slo_breached": True,
                        "steps": summary["steps"],
                        "terminated_early": False}
                    self.slo.on_breach_running(rreq, now, dropped=True)
                    if self.o2rt is not None:
                        # the window never produced a servable result:
                        # discard its admission verdict silently
                        self.o2rt.pending.pop(rreq.rid, None)
                else:
                    summary["slo_breached"] = True
                    summary["truncated"] = True
                    self.results[rreq.rid] = summary
                    self.slo.on_breach_running(rreq, now, dropped=False)
                    if self.o2rt is not None and narrow is not None:
                        self.o2rt.ingest_retired(pool, slot, rreq, narrow)
                        retired.append((rreq, summary))

    def step(self) -> int:
        """One service tick: drain any ready assessment verdicts, enforce
        deadlines (queued breaches drop, running breaches truncate or
        drop — freeing their slots for this tick), apply the slot policy,
        admit queued requests, advance every active pool by a K-step
        jitted program, retire finished episodes (streaming their
        transitions into the tenant's device replay ring), then — under
        O2 — dispatch the offline learners and the retired windows'
        assessments.  Returns the number of episode-steps of useful
        work."""
        with span("litune.step"):
            return self._step()

    def _step(self) -> int:
        if self.o2rt is not None:
            self.o2rt.drain()
        work = 0
        retired: list[tuple[TuneRequest, dict]] = []
        self._drop_breached_queued()
        self._enforce_running_deadlines(retired)
        self._apply_slot_policy()
        self._admit_from_queue()
        for pk, pool in self.pools.items():
            if pool.n_active == 0 or pool.carry is None:
                continue
            min_rem = min(pool.remaining())
            k = max(w for w in _pow2_ladder(self.horizon_cap)
                    if w <= max(min_rem, 1))
            with span("litune.tick", k=k, slots=pool.slots,
                      active=pool.n_active):
                work += self._tick(pk, pool, k, retired)
        if self.o2rt is not None:
            self.o2rt.tick(retired, self._pool_key)
        self.service_steps += 1
        self.episode_steps += work
        return work

    def _tick(self, pk: tuple, pool: _SlotPool, k: int,
              retired: list) -> int:
        """Advance `pool` by one K-step program, fetch its narrow fields
        and retire the episodes that finished; returns the episode-steps
        of useful work."""
        t_tick = self.clock()
        with span("litune.tick.dispatch"):
            # a live canary routes the tick through the per-lane program
            # variant with the pool's mixed params tree — same resident
            # program cache, zero re-traces (pre-bound at pool creation)
            canary = pool.lane_params is not None
            fused = self._fused(pool)
            # a first-use bind traces/compiles inside the timed window;
            # that sample would poison the EDF feasibility estimate, so
            # only warm ticks feed it
            warm = self._step_key(pk, pool, k, canary,
                                  fused) in self._programs
            program = self._pool_step_program(pk, pool, k,
                                              per_lane=canary)
            if fused:
                # fused tick: the capture append rides the step dispatch
                # (offsets are the pre-tick step counts; collect()
                # advances them after), so no second program runs below
                pool.carry, out, pool.cap = program(
                    pool.lane_params if canary else pool.params,
                    pool.carry, pool.noise_dev(), pool.ensure_cap(),
                    pool.steps_taken.astype(np.int32))
            else:
                pool.carry, out = program(
                    pool.lane_params if canary else pool.params,
                    pool.carry, pool.noise_dev())
        # only the narrow fields the serving loop reads cross to the
        # host — the same five the frozen service transfers
        fields = ["reward", "runtime_ns", "action", "cost", "early"]
        with span("litune.tick.fetch"):
            out_host = jax.device_get({f: out[f] for f in fields})
        # the narrow-field fetch bounds the tick: feed the EDF
        # feasibility estimate (seconds per episode-step)
        if warm:
            self.scheduler.note_tick(
                k, self.clock() - t_tick,
                in_trial=self._in_trial(pk[0]))
        if pool.capture and not fused:
            # unfused fallback (KernelConfig(fused_tick=False)): wide
            # fields stay on device, appended by the standalone
            # capture program before collect() advances offsets
            with span("litune.o2.capture", acc=self.o2rt.phase_ms,
                      key="capture"):
                pool.capture_tick(out)
        work = 0
        with span("litune.tick.collect"):
            for slot, req in enumerate(pool.requests):
                if req is None:
                    continue
                for j in range(k):
                    early = bool(out_host["early"][j, slot])
                    done = pool.collect(slot, out_host, j, early)
                    work += 1
                    if done:
                        rreq, summary, narrow = pool.retire(slot, early)
                        self.results[rreq.rid] = summary
                        self.slo.on_retire(rreq.rid, self.clock())
                        if self.o2rt is not None and narrow is not None:
                            self.o2rt.ingest_retired(pool, slot, rreq,
                                                     narrow)
                            retired.append((rreq, summary))
                        break
        return work

    def run(self, max_service_steps: int | None = None) -> dict[int, dict]:
        """Serve until the queue and every slot drain; returns
        {rid: summary} for everything completed so far.  In concurrent O2
        mode, assessment verdicts that are still executing keep trailing:
        their `swapped` annotations land on `flush_o2` (serving
        throughput never waits for the annex).  Strict mode settled every
        verdict inside its window's tick already."""
        n = 0
        while self.queue or any(p.n_active for p in self.pools.values()):
            if max_service_steps is not None and n >= max_service_steps:
                break
            self.step()
            n += 1
        if self.o2rt is not None:
            self.o2rt.drain()
        return self.results

    def stats_block(self) -> ServiceStats:
        """The typed stats document (`serving/stats.py` is the schema);
        `stats()` renders it to the pinned dict shape."""
        swaps = None
        if self.o2rt is not None:
            swaps = self.o2rt.swap_stats()
            swaps.breaches_during_trial = self.slo.trial_breaches
        return ServiceStats(
            service_steps=self.service_steps,
            episode_steps=self.episode_steps,
            completed=len(self.results),
            queued=len(self.queue),
            pools=len(self.pools),
            devices=self.topology.serving.width,
            topology=self.topology.describe(),
            # per-service binds: first/repeat use of a program key here
            program_misses=self.program_misses,
            program_hits=self.program_hits,
            # actual process-wide compiled step programs (shared cache)
            programs_resident=_step_program.cache_info().currsize,
            # per-pool breakdown: the adaptive scheduler's observability
            per_pool={
                "/".join(str(x) for x in pk): PoolStats(
                    slots=pool.slots, active=pool.n_active,
                    peak_slots=pool.peak_slots,
                    resizes=dict(pool.resizes))
                for pk, pool in self.pools.items()},
            scheduler=SchedulerStats(
                policy=self.policy.name,
                resize_events=self.scheduler.resize_events),
            slo=self.slo.stats_block(),
            o2=(self.o2rt.stats_block()
                if self.o2rt is not None else None),
            swaps=swaps,
            health=(self.o2rt.health_stats()
                    if self.o2rt is not None else None))

    def stats(self) -> dict:
        return self.stats_block().as_dict()


# ---------------------------------------------------------------- driver
def main():
    compile_cache.enable()
    from repro.core.litune import LITune, LITuneConfig
    from repro.index.workloads import sample_keys, wr_workload

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--n-keys", type=int, default=2048)
    ap.add_argument("--budget", type=int, default=10)
    ap.add_argument("--index", default="alex", choices=["alex", "carmi"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # the paper agent's widths (LITuneConfig defaults: LSTM 128, MLP 256)
    cfg = LITuneConfig(index_type=args.index, episode_len=args.budget)
    tuner = LITune(cfg, seed=args.seed)
    service = TuningService(tuner, config=ServeConfig(slots=args.slots,
                                                      seed=args.seed))

    key = jax.random.PRNGKey(args.seed + 1)
    for i in range(args.requests):
        k = jax.random.fold_in(key, i)
        wr = [0.33, 1.0, 3.0][i % 3]
        data = sample_keys(k, args.n_keys, "mix")
        wl, _ = wr_workload(jax.random.fold_in(k, 1), data, wr,
                            total=args.n_keys, dist="mix")
        service.submit(data, wl, wr, budget_steps=args.budget)

    t0 = time.time()
    results = service.run()
    dt = time.time() - t0
    for rid in sorted(results):
        r = results[rid]
        print(f"req {rid}: default {r['r0_ns']:9.1f} ns/op  best "
              f"{r['best_runtime_ns']:9.1f}  steps {r['steps']:3d}  "
              f"violations {r['violations']:.0f}")
    st = service.stats()
    slo = st["slo"]
    print(f"\n{len(results)} requests in {dt:.2f}s "
          f"({len(results) / max(dt, 1e-9):.2f} req/s)  "
          f"ticks={st['service_steps']}  devices={st['devices']}  "
          f"step programs bound={st['program_misses']} "
          f"reused={st['program_hits']} "
          f"resident={st['programs_resident']}")
    print(f"SLO: queue-wait p95={slo['queue_wait_ms']['p95']:.1f}ms "
          f"serve p95={slo['serve_ms']['p95']:.1f}ms  "
          f"breaches={slo['breaches']}")


if __name__ == "__main__":
    main()
