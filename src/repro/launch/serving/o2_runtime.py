"""O2 runtime layer of the serving stack: continuous tuning off the
serving critical path — and the trust machinery that decides when its
verdicts are allowed to touch production pools.

Owns everything the frozen serving path does not need: per-tenant
divergence monitors, the device-resident replay rings retired episodes
stream into, the offline DDPG learners (dispatched onto the O2 annex
slice with backpressure), and the pooled divergence-triggered
assessments whose verdicts hot-swap pool params.  Placement comes from
the service's `ServingTopology`: the annex is a multi-device *slice*,
not a single device — pooled assessments `shard_map` across its width
(each pow2-padded wave lowers onto the widest annex sub-slice it
divides) instead of running `lax.map`-serial, bitwise-equal either way
because per-lane math is mapped; the learner state lives on the slice's
lead device, and can scale its round size to the slice width
(`scale_rounds_to_annex`).  The service hands this
layer two things per tick — the episodes that retired, and a chance to
drain finished verdicts — and the layer never blocks the serving loop:
strict-order mode opts back into the serial loop's synchronous
interleaving for parity, everything else trails the server and settles
in `flush()` at the latest.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.o2 import (DivergenceMonitor, O2Config,
                           _fleet_finetune_program, copy_state,
                           make_replay, offline_finetune)
from repro.core.replay import _pow2_pad

from repro.launch.serving.fleet import (FleetConfig, FleetLearner,
                                        embed_window, nearest_tenant)
from repro.launch.serving.health import HealthConfig, HealthGuard
from repro.launch.serving.programs import (_batched_admit_keys,
                                           _build_carry_program,
                                           _extract_episode_program,
                                           _pow2_ladder, _reset_program,
                                           _step_program)
from repro.launch.serving.stats import (HealthStats, O2Stats, SwapStats,
                                        TenantO2Stats, TenantSwapStats)
from repro.launch.serving.topology import ServingTopology
from repro.launch.serving.tracing import span


@dataclasses.dataclass(frozen=True)
class O2ServiceConfig:
    """Continuous tuning inside the service (the O2 loop, per tenant)."""
    enabled: bool = False
    o2: O2Config = O2Config()
    # offline fine-tune steps dispatched after each tick that retires at
    # least one of the tenant's episodes (ticks with no fresh transitions
    # skip the learner: re-sampling an unchanged replay would add latency
    # to every tick of a long episode and desync the per-window update
    # count from the serial O2 loop).  None -> the O2Config's per-window
    # count, which makes a strict-order single-tenant stream
    # decision-identical to `O2System.tune_window` at any budget.  In
    # concurrent (non-strict) mode the count is a per-tick *cap*: a round
    # is skipped — and counted in `stats()["o2"][...]["finetune_skipped"]`
    # — while the previous round is still executing, so the learner
    # trails the server instead of serializing with it
    offline_updates_per_tick: int | None = None
    # one window in flight at a time, in submission order: trades the
    # service's cross-pool concurrency for the serial O2 loop's exact
    # observe->tune->assess interleaving (the parity mode LITune.stream
    # uses when routed through the service).  Strict mode also awaits
    # every assessment verdict inside its window's tick; concurrent mode
    # drains verdicts when their device work completes (at the latest in
    # `flush_o2`), so a hot-swap may land one or more ticks after the
    # window that earned it
    strict_order: bool = False
    replay_seed: int = 0
    # scale each fine-tune round by the annex slice width: a w-wide annex
    # runs w times the configured updates per round (the slice bought the
    # assessment headroom; the learner may spend it too).  Off by default
    # — scaling changes the update count and therefore the offline params,
    # so every serial-parity guarantee keeps its exact round sizes
    scale_rounds_to_annex: bool = False
    # per-tenant replay ring capacity (rows).  The historical 8192
    # default sizes a single-digit tenant count; a thousand-tenant fleet
    # bounds its per-tenant host/device footprint here
    replay_capacity: int = 8192
    # fleet mode: stacked multi-tenant fine-tune rounds + hot/warm/cold
    # tenant tiering (serving/fleet.py).  Default off — the per-tenant
    # eager path, bitwise-unchanged
    fleet: FleetConfig = FleetConfig()


class _TenantO2:
    """Per-tenant continuous-tuning state: the divergence monitor, the
    device-resident replay ring the offline learner samples, and the
    offline DDPG state that hot-swaps into the tenant's pools on
    divergence + win.  The learner state and its update program live on
    the service's O2 annex device when the host provides one, so their
    execution never queues in front of the serving mesh's fetches; the
    ring stays on the serving side (its writers and sampling readers run
    in the post-fetch window when that queue is empty), with sampled
    batches hopped to the annex per round."""

    def __init__(self, tuner, svc_cfg: O2ServiceConfig, annex=None,
                 ring_device=None, baseline_window: int = 32,
                 guard: HealthGuard | None = None,
                 index_type: str | None = None, kernel=None):
        self.cfg = svc_cfg.o2
        self.guard = guard
        self.index_type = (index_type if index_type is not None
                           else tuner.cfg.index_type)
        self.net_cfg = tuner.cfg.net_cfg()
        self.ddpg_cfg = tuner.cfg.ddpg
        self.et_cfg = tuner.cfg.et_cfg()
        self.env_cfg = tuner.cfg.env_cfg()
        if kernel is not None:
            # the service's kernel posture (ServeConfig.kernel): keeps the
            # tenant's env config — and therefore every assessment program
            # key — aligned with the pools it serves
            self.env_cfg = dataclasses.replace(self.env_cfg, kernel=kernel)
        self.annex = annex
        self.monitor = DivergenceMonitor(self.cfg)
        lazy = svc_cfg.fleet.enabled
        # the ring lives on the serving side (its writers and sampling
        # readers run there, right after the tick fetch when the queue is
        # empty); only the learner state and its update program live on
        # the annex, with sampled batches hopped across per round.  Fleet
        # tenants construct with the ring spilled (host pages, zero
        # device bytes) — `promote_hot` re-pages on first activity
        self.replay = make_replay(self.net_cfg, self.ddpg_cfg, self.env_cfg,
                                  capacity=svc_cfg.replay_capacity,
                                  seed=svc_cfg.replay_seed, device=True,
                                  place_on=ring_device, spilled=lazy)
        # the pretrained tree the learner seeds from.  Read-only — every
        # materialization copies; the fleet warm start may rebind it to a
        # donor tenant's tuned copy before the first promotion
        self._seed_state = tuner.state
        # fleet tiering state (inert off-fleet: every tenant stays "hot")
        self.tier = "cold" if lazy else "hot"
        self.idle_ticks = 0
        self.embedding = None       # workload embedding (warm start)
        self.warm_started = False
        self.repages = 0
        self.spills = 0
        self._host_online = None    # cold tier's evicted online tree
        self._host_offline = None   # cold tier's evicted learner tree
        if lazy:
            # zero device memory until the tenant earns it: no learner
            # copies, no ready snapshot — `promote_hot` materializes
            self.online = None
            self.offline = None
            self.ready_params = None
        else:
            # real copies (not aliases): the scanned fine-tune program
            # donates its input state, so the tuner's pretrained tree and
            # the online model must own their buffers
            self.online = copy_state(tuner.state)
            self.offline = self._place(copy_state(tuner.state))
            # the assessment-facing snapshot: params of the latest
            # *completed* fine-tune round (concurrent mode never blocks
            # on a pending one)
            self.ready_params = self._place(
                copy_state(tuner.state["params"]))
        self.offline_updates = 0
        self.finetune_skipped = 0
        self._inflight = None       # marker array of the pending round
        self._round_dirty = False   # a round completed but isn't published
        self.swaps = 0
        self.swap_times_s: list[float] = []
        # the swap pipeline's verdict state machine counters, plus the
        # rolling pre-swap score baseline (the control arm for pools with
        # no spare lane, and the post-promotion regression reference)
        self.swap = TenantSwapStats()
        self.baseline: deque[float] = deque(maxlen=baseline_window)
        # the health layer's last-known-good learner state: every
        # publish/strict round that passes the param gate refreshes it,
        # and a rejected round restores from it — so one NaN gradient
        # never wedges the tenant's learner permanently (None until a
        # lazy fleet tenant materializes)
        self._last_good = (None if lazy
                           else self._place(copy_state(tuner.state)))
        self.rejected_params = 0
        # circuit-breaker state: consecutive bad events (rejected
        # params, rollbacks); at the guard's threshold the tenant's O2
        # loop is quarantined until `quarantined_until` (a window count
        # on this tenant's own monitor — traffic-paced, not wall-paced)
        self.bad_streak = 0
        self.quarantined_until: int | None = None

    @property
    def quarantined(self) -> bool:
        return self.quarantined_until is not None

    # ---------------------------------------------- fleet tier machinery
    def online_state(self):
        """The tenant's online learner tree, materialized on demand: a
        cold fleet tenant holds none until it earns one (from its cold
        eviction if it tuned before, else a copy of the seed tree)."""
        if self.online is None:
            if self._host_online is not None:
                self.online = copy_state(self._host_online)
                self._host_online = None
            else:
                self.online = copy_state(self._seed_state)
        return self.online

    def online_params(self):
        """Params a new pool of this tenant binds.  A cold never-tuned
        tenant serves the seed tree directly — the pool makes its own
        device copy, so no per-tenant online tree is materialized for a
        tenant that may never diverge."""
        if self.online is not None:
            return self.online["params"]
        if self._host_online is not None:
            return self.online_state()["params"]
        return self._seed_state["params"]

    def promote_hot(self):
        """Cold/warm -> hot: re-page the replay ring onto its device and
        materialize the learner trees (from the cold-evicted host copy
        when the tenant tuned before, else the seed).  Bitwise: the ring
        round-trips float32 exactly, and a never-tuned tenant's learner
        starts from the same seed copy the eager path made."""
        if self.tier == "hot":
            self.idle_ticks = 0
            return
        if self.replay.spilled:
            self.replay.repage()
            self.repages += 1
        if self.offline is None:
            src = (self._host_offline if self._host_offline is not None
                   else self._seed_state)
            self.offline = self._place(copy_state(src))
            self._host_offline = None
            self.ready_params = copy_state(self.offline["params"])
            self._last_good = self._place(copy_state(self.offline))
        if self.online is None:
            self.online_state()
        self.tier = "hot"
        self.idle_ticks = 0

    def demote_warm(self) -> bool:
        """Hot -> warm: the replay pages spill to host; the learner trees
        stay resident (the tenant re-enters the stacked round without a
        re-page the moment traffic returns)."""
        if self.tier != "hot":
            return False
        if not self.replay.spilled:
            self.replay.spill()
            self.spills += 1
        self.tier = "warm"
        return True

    def demote_cold(self, keep_history: int):
        """Warm -> cold: zero device bytes.  The (possibly tuned) learner
        trees evict to host copies, the ready/last-good snapshots drop
        (re-derived at the next promotion), and the divergence monitor's
        unbounded history trims to its last `keep_history` entries — the
        fix for per-tenant state growing forever once a tenant is seen."""
        if self.tier == "cold":
            return
        if not self.replay.spilled:
            self.replay.spill()
            self.spills += 1

        def to_host(tree):
            return jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), tree)

        if self.offline is not None:
            self._host_offline = to_host(self.offline)
            self.offline = None
        if self.online is not None:
            self._host_online = to_host(self.online)
            self.online = None
        self.ready_params = None
        self._last_good = None
        self._inflight = None
        self._round_dirty = False
        self.monitor.trim_history(keep_history)
        self.tier = "cold"

    @staticmethod
    def _tree_bytes(tree) -> int:
        if tree is None:
            return 0
        return sum(int(np.prod(np.shape(x)))
                   * np.dtype(getattr(x, "dtype", np.float32)).itemsize
                   for x in jax.tree.leaves(tree))

    def device_bytes(self) -> int:
        """Approximate device residency of this tenant's O2 state (ring
        pages + learner trees).  Zero for a cold tenant — pinned in
        tests/test_fleet.py and gated in the fleet bench."""
        return (self.replay.device_bytes
                + self._tree_bytes(self.online)
                + self._tree_bytes(self.offline)
                + self._tree_bytes(self.ready_params)
                + self._tree_bytes(self._last_good))

    def host_bytes(self) -> int:
        """Approximate host residency (spilled pages, narrow ring
        fields, cold-evicted learner trees)."""
        return (self.replay.host_bytes
                + self._tree_bytes(self._host_online)
                + self._tree_bytes(self._host_offline))

    def reject_round(self):
        """Drop an unhealthy fine-tune result: count it, restore the
        learner from the last-good snapshot (a real copy — the next
        round donates its input), and clear the round-pending state."""
        self.rejected_params += 1
        if self.guard is not None:
            self.guard.rejected_params += 1
        self.offline = self._place(copy_state(self._last_good))
        self._inflight = None
        self._round_dirty = False

    def gate_round(self) -> bool:
        """Health-gate the latest completed fine-tune round.  Healthy
        rounds refresh the last-good snapshot; unhealthy ones are
        rejected.  The breaker streak deliberately does NOT reset here —
        only a swap that survives its watch window or a quarantine
        release clears it, so repeated canary rollbacks trip the
        breaker even when every fine-tune round between them is
        healthy.  Read-only on the healthy path beyond the snapshot
        copy."""
        if self.guard is None or not self.guard.enabled:
            return True
        if self.guard.params_healthy(self.offline["params"]):
            self._last_good = self._place(copy_state(self.offline))
            return True
        self.reject_round()
        return False

    def _place(self, tree):
        return tree if self.annex is None else jax.device_put(tree,
                                                              self.annex)

    def learner_free(self) -> bool:
        return self._inflight is None or bool(self._inflight.is_ready())

    def publish_ready(self):
        """Expose the latest completed round's params to assessments —
        bounded staleness, never a block on a pending round (the copy
        also shields them from the next round's donation off-CPU).
        The completed round passes the health gate first: a rejected
        round never publishes, and `ready_params` keeps the last-good
        version (callers watch `rejected_params` for breaker strikes)."""
        if self._round_dirty and self.learner_free():
            if not self.gate_round():
                return
            self.ready_params = copy_state(self.offline["params"])
            self._round_dirty = False

    def finetune(self, n_updates: int, strict: bool):
        """Dispatch one offline fine-tune round.  Strict mode always runs
        it (serial-equivalent update counts); concurrent mode applies
        backpressure — if the previous round hasn't finished executing,
        the round is skipped and counted rather than queued behind."""
        if n_updates <= 0:
            return
        if not strict and not self.learner_free():
            self.finetune_skipped += n_updates
            return
        self.offline, done = offline_finetune(
            self.offline, self.replay, self.net_cfg, self.ddpg_cfg,
            n_updates, place_on=self.annex)
        self.offline_updates += done
        if done:
            self._inflight = self.offline["updates"]
            self._round_dirty = True
            if self.guard is not None and self.guard.fire("nan_round"):
                # injected learner divergence: poison the round's params
                # before any gate sees them (the chaos drill's NaN site)
                self.offline["params"] = jax.tree.map(
                    lambda x: jnp.full_like(x, jnp.nan),
                    self.offline["params"])


def _pooled_best(r0: float, runtimes: np.ndarray) -> float:
    """Best runtime of one pooled assessment episode — min over the
    request's step prefix and the default-config runtime, exactly the
    ``best_runtime_ns`` `core.o2.assess_offline` reports for the same key
    (the hot-swap comparison's left-hand side, and the seam tests patch
    to force a verdict)."""
    return min(r0, float(np.min(runtimes)))


def _bootstrap_ci(deltas, level: float, resamples: int,
                  rng: np.random.Generator) -> tuple[float, float]:
    """Percentile-bootstrap confidence interval on the mean of `deltas`
    (the per-window offline-vs-online runtime improvements a pooled
    assessment produced).  Deterministic given the generator state — the
    runtime seeds it from `SwapConfig.ci_seed`, so a replayed request
    stream reproduces every gate decision."""
    deltas = np.asarray(deltas, np.float64)
    if deltas.size == 1:
        return float(deltas[0]), float(deltas[0])
    idx = rng.integers(0, deltas.size, size=(resamples, deltas.size))
    means = deltas[idx].mean(axis=1)
    alpha = (1.0 - level) / 2.0
    return (float(np.quantile(means, alpha)),
            float(np.quantile(means, 1.0 - alpha)))


def _lane_score(summary: dict) -> float:
    """One retired episode's score for canary-arm comparison: tuned best
    runtime normalized by the default-config runtime (lower is better).
    The normalization makes lanes serving different windows comparable —
    raw runtimes mix the workload's difficulty into the arm means.
    Module-level on purpose: the seam tests patch to force a verdict."""
    return summary["best_runtime_ns"] / max(float(summary["r0_ns"]), 1e-9)


@dataclasses.dataclass
class _SwapTrial:
    """One tenant's in-flight swap trial: the canary stage (candidate
    params live on a lane fraction of every pool) and, after promotion,
    the post-swap watch window.  Holds everything a bitwise rollback
    needs: the judged candidate tree, the pre-swap online state, and the
    divergence monitor's pre-promotion reference snapshot."""
    index_type: str
    req: object                  # the request whose verdict started it
    window: int                  # 0-based window index for re-anchoring
    summary: dict                # its summary (swap flags land here)
    candidate: object            # the judged param tree (owned copy)
    prev_online: object          # pre-swap online state (owned copy)
    baseline_mean: float | None  # tenant rolling baseline at trial start
    state: str = "canary"        # "canary" -> "promoted"
    canary_scores: list = dataclasses.field(default_factory=list)
    control_scores: list = dataclasses.field(default_factory=list)
    post_scores: list = dataclasses.field(default_factory=list)
    ticks: int = 0               # service ticks spent in the canary stage
    watch_windows: int = 0       # windows observed since promotion
    monitor_ref: tuple | None = None  # (ref_quantiles, ref_wr) pre-swap
    prev_anchor: int | None = None    # anchor window index pre-swap
    forced_loss: bool = False    # fault injection: lose this canary


@dataclasses.dataclass
class _PendingAssess:
    """One dispatched pooled assessment awaiting its verdict: up to
    2*slots diverged windows of a single tenant, rolled out as one batch
    through the resident step programs.  Holds only device references —
    nothing crosses to the host until `ready()` (or a blocking drain).
    `params` is the exact tree the episodes ran under: a winning verdict
    promotes *those* params, not whatever the learner has advanced to by
    drain time."""
    index_type: str
    items: list          # [(req, summary, pend)] per occupied slot column
    r0: object           # [B] device: r_best at reset
    outs: list           # [(k, runtime_ns [k, B], early [k, B]) ...]
    params: object       # the judged param tree
    dispatched_at: float | None = None  # wall time of dispatch (watchdog)
    forced_hang: bool = False  # fault injection: never report ready

    def ready(self) -> bool:
        if self.forced_hang:
            return False
        return bool(self.outs[-1][1].is_ready())


class O2Runtime:
    """The between-ticks half of the O2 loop, composed into the service.

    Shares the service's pools dict (hot-swaps update every pool of a
    tenant in place) and its device/annex ids; owns the tenants, the
    admission-verdict map, the assessment backlog/in-flight queues, and
    the per-phase host-time accounting.
    """

    def __init__(self, agents: dict, svc_cfg: O2ServiceConfig, pools: dict,
                 topology: ServingTopology, horizon_cap: int,
                 max_assess_width: int, swap_cfg=None, clock=None,
                 health_cfg: HealthConfig | None = None, kernel=None):
        self.cfg = svc_cfg
        if swap_cfg is None:
            # lazy: config.py imports O2ServiceConfig from this module
            from repro.launch.serving.config import SwapConfig
            swap_cfg = SwapConfig()
        self.swap_cfg = swap_cfg
        self.health = HealthGuard(health_cfg if health_cfg is not None
                                  else HealthConfig())
        # the service's injectable clock (swap timing rides it, so tests
        # and benchmarks measure swaps on the same timebase as SLOs)
        self.clock = clock if clock is not None else time.perf_counter
        self.pools = pools              # shared with the service
        self.topology = topology
        # the learner state and its scanned update program live on the
        # annex slice's lead device; assessments spread over the slice
        self.annex = topology.annex.device(0)
        self.horizon_cap = horizon_cap
        self.max_assess_width = max_assess_width
        self.fleet_cfg = svc_cfg.fleet
        self.fleet = (FleetLearner(svc_cfg.fleet, annex=self.annex)
                      if svc_cfg.fleet.enabled else None)
        self.warm_starts = 0
        self.tenants: dict[str, _TenantO2] = {
            it: _TenantO2(tuner, svc_cfg, annex=self.annex,
                          ring_device=topology.ring.device(),
                          baseline_window=swap_cfg.baseline_window,
                          guard=self.health, index_type=it, kernel=kernel)
            for it, tuner in agents.items()}
        # tier bookkeeping: tenants holding any device memory (hot/warm)
        # — the only ones the per-tick aging walk visits, so a mostly-
        # cold thousand-tenant fleet pays O(active), not O(tenants)
        self._noncold: set[str] = {it for it, t in self.tenants.items()
                                   if t.tier != "cold"}
        self._touched: set[str] = set()     # tenants active this tick
        # at most one swap trial per tenant (verdict wins landing while
        # one is live are deferred, not queued): index_type -> _SwapTrial
        self.trials: dict[str, _SwapTrial] = {}
        self._ci_rng = np.random.default_rng(swap_cfg.ci_seed)
        self.pending: dict[int, dict] = {}      # rid -> admission verdict
        self.backlog: list[tuple] = []          # (pk, req, summary, pend)
        self.inflight: deque[_PendingAssess] = deque()
        # bind the assessment-side program wrappers for every annex
        # sub-slice x K up front: which (wave width, K) pairs actually
        # occur is drain-timing-dependent in concurrent mode, and the
        # process-wide program accounting must not move after warmup
        # (tests assert zero new binds across waves).  Binding is a
        # cheap lru insert — XLA still traces lazily per shape, exactly
        # as the single-device annex behaved.  Deduped by config: a
        # thousand-tenant fleet sharing one agent config walks the
        # ladder once, not once per tenant
        _seen_cfgs: set = set()
        for tenant in self.tenants.values():
            if (tenant.net_cfg, tenant.env_cfg, tenant.et_cfg) \
                    in _seen_cfgs:
                continue
            _seen_cfgs.add((tenant.net_cfg, tenant.env_cfg,
                            tenant.et_cfg))
            env_cfg = tenant.env_cfg.with_episode_len(horizon_cap)
            # pad the top: a chunk of max_assess_width windows pads to
            # the next power of two, and that width must be warm too
            widths = _pow2_ladder(_pow2_pad(max_assess_width))
            for sl in {topology.assess_slice(w) for w in widths}:
                _reset_program(sl, env_cfg)
                for w in widths:
                    if w % sl.width == 0 and topology.assess_slice(w) == sl:
                        _build_carry_program(sl, tenant.net_cfg, w)
                for k in _pow2_ladder(horizon_cap):
                    _step_program(sl, tenant.net_cfg, env_cfg,
                                  tenant.et_cfg, k)
        if self.fleet is not None:
            # pre-bind the stacked fine-tune ladder (pow2 stack widths up
            # to the hot-tier cap), deduped by (configs, round size): the
            # hot-set size sweeping 1..max_hot after warmup binds zero
            # new programs — the fleet bench's hard invariant
            _seen_fleet: set = set()
            for tenant in self.tenants.values():
                n = self._round_updates(tenant)
                ck = (tenant.net_cfg, tenant.ddpg_cfg, n)
                if n <= 0 or ck in _seen_fleet:
                    continue
                _seen_fleet.add(ck)
                for k_pad in _pow2_ladder(_pow2_pad(svc_cfg.fleet.max_hot)):
                    _fleet_finetune_program(tenant.net_cfg,
                                            tenant.ddpg_cfg, n, k_pad,
                                            self.fleet.impl)
        self._assess_noise: dict[tuple, jax.Array] = {}  # (slice,w) -> 0s
        # (index_type, slice) -> (source tree, replicated copy): the
        # broadcast onto the assess slice is paid once per params
        # version, not once per wave (identity-compared — publish_ready
        # and each fine-tune round rebind the source tree)
        self._assess_params: dict[tuple, tuple] = {}
        self.pending_missing = 0        # retired without admission verdict
        self.assessments = 0            # pooled assessment episodes judged
        self.phase_ms = {"capture": 0.0, "finetune": 0.0, "assess": 0.0}

    # --------------------------------------------------------- admission
    def admit_keys(self, keys: np.ndarray):
        """One batched split per admission wave: window key -> (episode
        key, assessment key), the same bits as the serial loop's
        per-window jax.random.split chain."""
        k_on, k_off = _batched_admit_keys(keys)
        return np.asarray(k_on), np.asarray(k_off)

    def observe_admission(self, req, assess_key):
        """Each admitted request is one window of the tenant's stream:
        observe divergence now (against the reference distribution),
        assess after the episode retires."""
        tenant = self.tenants[req.index_type]
        if self.fleet is not None and tenant.embedding is None:
            self._admit_fleet(tenant, req)
        div = tenant.monitor.observe(req.data_keys, req.wr_ratio)
        if self.fleet is not None:
            self._touched.add(req.index_type)
            if div["diverged"] and tenant.tier != "hot":
                # first divergence observation re-pages a cold tenant:
                # the O2 loop is about to need its ring and learner
                self._promote_hot(tenant)
        if tenant.quarantined and \
                tenant.monitor.windows_seen >= tenant.quarantined_until:
            # cooloff elapsed (measured in this tenant's own observed
            # windows): release the breaker with a clean streak
            tenant.quarantined_until = None
            tenant.bad_streak = 0
            self.health.quarantine_releases += 1
        self.pending[req.rid] = {
            "div": div, "window": tenant.monitor.windows_seen,
            "assess_key": assess_key}
        trial = self.trials.get(req.index_type)
        if trial is not None and trial.state == "promoted":
            # the post-promotion watch: the monitor was re-anchored on
            # the promoted window's data, so a re-fire this soon means
            # the swap anchored on an unrepresentative window — revert
            trial.watch_windows += 1
            if div["diverged"]:
                self._rollback_promoted(req.index_type, trial, "monitor")
            elif trial.watch_windows >= self.swap_cfg.rollback_windows:
                self._close_trial(req.index_type)

    # ------------------------------------------------------------- fleet
    def _admit_fleet(self, tenant: _TenantO2, req):
        """First observed window of a fleet tenant: embed the workload
        (key-quantile profile + write mix) and, when enabled, seed the
        learner from the L2-nearest existing tenant's tuned params
        instead of the pretrained default (BALANCE-style transfer —
        cold-start tuning becomes transfer from the fleet's accumulated
        knowledge).  Falls back to the default when no other tenant has
        been observed yet.  Counted in `stats()["o2"]["warm_starts"]`."""
        tenant.embedding = embed_window(req.data_keys, req.wr_ratio)
        if not self.fleet_cfg.warm_start or tenant.warm_started:
            return
        if tenant.monitor.windows_seen > 0 or tenant.online is not None \
                or tenant._host_online is not None:
            return      # an already-tuned tenant keeps its own learner
        donors = {it: t for it, t in self.tenants.items()
                  if t is not tenant and t.embedding is not None}
        # prefer donors whose learner is resident (hot/warm) — a cold
        # donor's tree works too, just from its host copy or seed
        warm = {it: t for it, t in donors.items() if t.tier != "cold"}
        pool_ = warm if warm else donors
        pick = nearest_tenant(tenant.embedding,
                              {it: t.embedding for it, t in pool_.items()})
        if pick is None:
            return
        donor = self.tenants[pick]
        src = (donor.online if donor.online is not None else
               donor._host_online if donor._host_online is not None else
               donor._seed_state)
        tenant._seed_state = copy_state(src)
        tenant.warm_started = True
        self.warm_starts += 1
        # admission resolves the pool before this observation lands, so
        # any existing pool of the tenant rebinds to the donor-seeded
        # params — a pure buffer update, zero re-traces
        for pk, pool in self.pools.items():
            if pk[0] == tenant.index_type:
                pool.params = jax.device_put(
                    tenant._seed_state["params"], pool.replicated)

    def _promote_hot(self, tenant: _TenantO2):
        """Promote a tenant into the hot tier, spilling the idlest hot
        tenant to warm when the tier is at `max_hot` capacity."""
        was_cold_or_warm = tenant.tier != "hot"
        tenant.promote_hot()
        self._noncold.add(tenant.index_type)
        if self.fleet is None:
            return
        if was_cold_or_warm:
            self.fleet.promotions += 1
        hot = [self.tenants[it] for it in self._noncold
               if self.tenants[it].tier == "hot"]
        if len(hot) > self.fleet_cfg.max_hot:
            idlest = max((t for t in hot if t is not tenant),
                         key=lambda t: t.idle_ticks)
            if idlest.demote_warm():
                self.fleet.demotions += 1

    def _age_tiers(self):
        """One O2 tick of tier aging, run at the end of `tick`: every
        hot/warm tenant that saw no activity (admission or retirement)
        this tick ages toward warm (`warm_after_ticks`: replay pages
        spill to host) and then cold (`cold_after_ticks`: learner trees
        evict, idle pools drop).  Cold tenants are not walked at all."""
        fc = self.fleet_cfg
        for it in list(self._noncold):
            tenant = self.tenants[it]
            if it in self._touched:
                tenant.idle_ticks = 0
                continue
            tenant.idle_ticks += 1
            if tenant.tier == "hot" \
                    and tenant.idle_ticks >= fc.warm_after_ticks:
                if tenant.demote_warm():
                    self.fleet.demotions += 1
            if tenant.tier == "warm" \
                    and tenant.idle_ticks >= fc.cold_after_ticks:
                self._evict_cold(it, tenant)
        self._touched.clear()

    def _evict_cold(self, it: str, tenant: _TenantO2):
        """Cold eviction: zero device bytes for the tenant, and its idle
        pools (no active episodes) are torn down — `_pool_for` re-creates
        them on demand, re-entering the same resident programs."""
        tenant.demote_cold(self.fleet_cfg.monitor_history)
        self.fleet.evictions += 1
        self._noncold.discard(it)
        for pk in [pk for pk, p in self.pools.items()
                   if pk[0] == it and p.n_active == 0]:
            del self.pools[pk]

    def _round_updates(self, tenant: _TenantO2) -> int:
        """One fine-tune round's update count for a tenant (the serial
        path's exact resolution order)."""
        n = (self.cfg.offline_updates_per_tick
             if self.cfg.offline_updates_per_tick is not None
             else tenant.cfg.offline_updates_per_window)
        if self.cfg.scale_rounds_to_annex:
            n *= self.topology.annex.width
        return n

    # ----------------------------------------------------------- capture
    def ingest_retired(self, pool, slot: int, req, narrow: dict):
        """Extract the retired episode's capture rows (small gather on
        the serving mesh) into the tenant's ring — the wide fields never
        visit the host."""
        with span("litune.o2.capture", acc=self.phase_ms, key="capture"):
            tenant = self.tenants[req.index_type]
            if self.fleet is not None:
                self._touched.add(req.index_type)
                if tenant.tier != "hot":
                    # a retiring episode is ring traffic: re-page before
                    # the write so the capture rows land on device pages
                    self._promote_hot(tenant)
            T = len(narrow["reward"])
            src = np.minimum(np.arange(_pow2_pad(T)),
                             T - 1).astype(np.int32)
            values = _extract_episode_program(pool.slice)(
                pool.cap, np.int32(slot), src)
            tenant.replay.add_episode_values(values, T, **narrow)

    # -------------------------------------------------------------- tick
    def tick(self, retired: list, pool_key):
        """The between-ticks half of the O2 loop.  Strict mode keeps the
        serial interleaving: fine-tune, assess against the fresh offline
        tail, await the verdict.  Concurrent mode inverts it for the
        annex's FIFO: assessments dispatch first (against the last
        *completed* round's published params, so they never chain behind
        a pending one), the fine-tune round queues after them, and
        verdicts land on a later tick's drain."""
        strict = self.cfg.strict_order
        # a demoted annex inside its cooloff pauses all O2 work for the
        # tick — the serving path keeps running frozen on current params
        paused = self.health.o2_paused()
        if paused:
            self.health.degraded_ticks += 1
        if strict and not paused:
            with span("litune.o2.finetune", acc=self.phase_ms,
                      key="finetune"):
                self._finetune_retired(retired, strict)
        with span("litune.o2.assess", acc=self.phase_ms, key="assess"):
            for req, summary in retired:
                tenant = self.tenants[req.index_type]
                pend = self.pending.pop(req.rid, None)
                if pend is None:
                    # admitted before O2 tracked this tenant (or replayed
                    # after a config swap): skip the window verdict
                    # instead of raising mid-tick, and count it
                    self.pending_missing += 1
                    continue
                # annotate the request's result with its window verdict,
                # in the exact shape O2System.tune_window returns;
                # `swapped` flips in the drain if the assessment wins
                summary["divergence"] = pend["div"]
                summary["swapped"] = False
                if pend["div"]["diverged"] and \
                        pend["window"] % tenant.cfg.assess_every == 0 \
                        and not paused and not tenant.quarantined:
                    self.backlog.append((pool_key(req), req, summary,
                                         pend))
            if self.swap_cfg.staged:
                self._observe_retired(retired)
                self._advance_trials()
            if not paused:
                self._pump_assessments()
        if strict:
            # serial-equivalent interleaving: the verdict (and any swap)
            # lands before the next window is admitted
            self.drain(block=True)
        elif not paused:
            with span("litune.o2.finetune", acc=self.phase_ms,
                      key="finetune"):
                self._finetune_retired(retired, strict)
        if self.fleet is not None:
            self._age_tiers()

    def _pump_assessments(self):
        """Move backlog windows into pooled assessment dispatches, widest
        chunks first, with at most two chunks in flight — the annex's
        admission control.  A saturated annex (many diverged windows,
        long budgets) grows the backlog instead of the device queue, and
        `flush` settles whatever is left."""
        while self.backlog and len(self.inflight) < 2:
            if self.health.o2_paused():
                # demoted annex mid-pump: keep the rest of the backlog
                # for after recovery instead of burning it on dispatches
                # that cannot succeed
                break
            pk = self.backlog[0][0]
            chunk = [item for item in self.backlog
                     if item[0] == pk][:self.max_assess_width]
            for item in chunk:
                self.backlog.remove(item)
            pool, tenant = self.pools[pk], self.tenants[pk[0]]
            if tenant.quarantined:
                # stale backlog of a breaker-open tenant: drop it
                continue
            if not self.cfg.strict_order:
                before = tenant.rejected_params
                tenant.publish_ready()
                if tenant.rejected_params != before:
                    self._note_bad(tenant)
                    if tenant.quarantined:
                        continue
            entry = self._guarded_dispatch(
                pk, pool, tenant, [item[1:] for item in chunk])
            if entry is not None:
                self.inflight.append(entry)

    def _guarded_dispatch(self, pk: tuple, pool, tenant: _TenantO2,
                          chunk: list):
        """One pooled-assessment dispatch under the annex watchdog:
        bounded retries with seeded backoff; exhaustion drops the chunk
        and strikes the annex breaker.  Returns None when dropped."""
        g = self.health
        if not g.enabled:
            entry = self._dispatch_assess(pk, pool, tenant, chunk)
            entry.dispatched_at = time.monotonic()
            return entry
        if g.o2_paused():
            # demoted mid-tick: the cooloff applies to the rest of the
            # tick's dispatches too, not just the next tick's
            g.dropped_dispatches += 1
            return None
        for attempt in range(g.cfg.dispatch_retries + 1):
            try:
                g.raise_if_planned("assess_fail")
                entry = self._dispatch_assess(pk, pool, tenant, chunk)
            except RuntimeError:
                # InjectedFailure and device/runtime faults alike
                if attempt < g.cfg.dispatch_retries:
                    g.note_retry()
                    g.sleep_backoff(attempt)
                    continue
                g.note_annex_failure()
                g.dropped_dispatches += 1
                return None
            g.note_annex_ok()
            entry.dispatched_at = time.monotonic()
            if g.fire("assess_hang"):
                entry.forced_hang = True
            return entry

    def _finetune_retired(self, retired: list, strict: bool):
        # deterministic first-retirement tenant order (a set of strings
        # iterates in hash order, which varies with PYTHONHASHSEED): the
        # fleet stack's lane order — and therefore which replay RNG draws
        # pair with which lane — must be reproducible run to run
        order = list(dict.fromkeys(req.index_type for req, _ in retired))
        tenants = [self.tenants[it] for it in order
                   if not self.tenants[it].quarantined]
        if not tenants:
            return
        # getattr: tests drive this method on lightweight runtime
        # stand-ins that don't construct the fleet learner
        if getattr(self, "fleet", None) is not None:
            self._fleet_finetune(tenants, strict)
            return
        for tenant in tenants:
            self._guarded_finetune(tenant, self._round_updates(tenant),
                                   strict)

    def _fleet_finetune(self, tenants: list, strict: bool):
        """One stacked fine-tune round over every tenant that retired
        episodes this tick.  Quarantined tenants were already filtered
        out — the stack is re-formed from scratch each round, so a
        mid-round eviction cannot perturb the surviving lanes' bits
        (each lane's state and batches are its own; parity pinned in
        tests/test_fleet.py).  Per-tenant semantics match the serial
        path lane by lane: backpressure skips, update counters, the
        NaN-round fault site, and strict-mode gating."""
        g = self.health
        ready = []
        for tenant in tenants:
            n = self._round_updates(tenant)
            if n <= 0:
                continue
            if tenant.tier != "hot":
                # retirement promoted it in ingest; belt and braces for
                # direct callers
                self._promote_hot(tenant)
            if not strict and not tenant.learner_free():
                tenant.finetune_skipped += n
                continue
            ready.append((tenant, n))
        if not ready:
            return
        # one watchdog-guarded dispatch for the whole stack (the same
        # retry/backoff contract as the serial per-tenant rounds)
        if not g.enabled:
            ran = self.fleet.round(ready)
        else:
            ran = None
            for attempt in range(g.cfg.dispatch_retries + 1):
                try:
                    g.raise_if_planned("finetune_fail")
                    ran = self.fleet.round(ready)
                except RuntimeError:
                    if attempt < g.cfg.dispatch_retries:
                        g.note_retry()
                        g.sleep_backoff(attempt)
                        continue
                    g.note_annex_failure()
                    return
                g.note_annex_ok()
                break
        for tenant, n in ran:
            tenant.offline_updates += n
            tenant._inflight = tenant.offline["updates"]
            tenant._round_dirty = True
            if g.fire("nan_round"):
                tenant.offline["params"] = jax.tree.map(
                    lambda x: jnp.full_like(x, jnp.nan),
                    tenant.offline["params"])
            if strict and tenant._round_dirty:
                if tenant.gate_round():
                    tenant._round_dirty = False   # strict never publishes
                else:
                    self._note_bad(tenant)

    def _guarded_finetune(self, tenant: _TenantO2, n: int, strict: bool):
        """One learner round under the watchdog (same retry/backoff
        contract as assessments).  Strict mode additionally gates the
        completed round's params right here, preserving the serial
        interleaving — concurrent mode gates at publish time instead, so
        a pending round is never synced on."""
        g = self.health
        if not g.enabled:
            tenant.finetune(n, strict)
            return
        if g.o2_paused():        # demoted mid-tick: no learner work either
            return
        for attempt in range(g.cfg.dispatch_retries + 1):
            try:
                g.raise_if_planned("finetune_fail")
                tenant.finetune(n, strict)
            except RuntimeError:
                if attempt < g.cfg.dispatch_retries:
                    g.note_retry()
                    g.sleep_backoff(attempt)
                    continue
                g.note_annex_failure()
                return
            g.note_annex_ok()
            if strict and tenant._round_dirty:
                if tenant.gate_round():
                    tenant._round_dirty = False   # strict never publishes
                else:
                    self._note_bad(tenant)
            return

    def _note_bad(self, tenant: _TenantO2):
        """One tenant-level health strike (rejected params, rollback).
        At the configured threshold the tenant's breaker opens: its O2
        loop quarantines until `quarantine_windows` more of its windows
        are observed, and any live canary is rolled back (the incumbent
        params were never touched, so serving stays frozen-good)."""
        tenant.bad_streak += 1
        g = self.health
        if not g.enabled or tenant.quarantined:
            return
        if tenant.bad_streak >= g.cfg.quarantine_threshold:
            tenant.quarantined_until = (tenant.monitor.windows_seen
                                        + g.cfg.quarantine_windows)
            g.quarantines += 1
            if self.fleet is not None and g.cfg.quarantine_spills:
                # a quarantined tenant leaves the stacked round (the
                # stack is re-formed each round, so the others' bits are
                # untouched) and cannot fine-tune during the cooloff —
                # spill its ring pages rather than hold device memory
                if tenant.demote_warm():
                    self.fleet.demotions += 1
            trial = self.trials.get(tenant.index_type)
            if trial is not None and trial.state == "canary":
                self._rollback_canary(tenant.index_type, trial,
                                      note=False)

    def _assess_noise_dev(self, slice_, width: int):
        key = (slice_, width)
        if key not in self._assess_noise:
            self._assess_noise[key] = jax.device_put(
                jnp.zeros((width,), jnp.float32), slice_.sharded())
        return self._assess_noise[key]

    def _dispatch_assess(self, pk: tuple, pool,
                         tenant: _TenantO2, chunk: list) -> _PendingAssess:
        """Launch one pooled assessment on the O2 annex slice: up to B
        diverged windows of one tenant reset and roll out as a single
        batch through the K-ladder step-program cache (zero-noise inputs
        — the deterministic branch for the tanh-bounded actor), in place
        of len(chunk) serial `rollout_episode` calls.  The pow2-padded
        wave shards over the widest annex sub-slice it divides — lanes
        split across annex devices instead of `lax.map`-serial on one,
        bitwise-equal because the per-lane program is identical (the
        1-device slice *is* the serial path).  Strict mode assesses the
        offline tail (serial semantics); concurrent mode the published
        ready params.  Nothing is fetched here; the verdict scalars cross
        to the host in `drain` once the device work completes."""
        m = len(chunk)
        width = _pow2_pad(m)
        sl = self.topology.assess_slice(width)
        reqs = [item[0] for item in chunk]
        rpad = reqs + [reqs[0]] * (width - m)
        data = np.stack([r.data_keys for r in rpad])
        reads = np.stack([r.workload["reads"] for r in rpad])
        ins = np.stack([r.workload["inserts"] for r in rpad])
        wr = np.asarray([r.wr_ratio for r in rpad], np.float32)
        # the assessment keys were derived in the admission wave's
        # batched split (same bits as the serial loop's chain)
        k_offs = np.stack([item[2]["assess_key"] for item in chunk])
        keys = np.concatenate(
            [k_offs, np.broadcast_to(k_offs[:1], (width - m, 2))])
        env_states, obs = _reset_program(sl, pool.env_cfg)(
            data, reads, ins, wr)
        carry = _build_carry_program(sl, pool.net_cfg, width)(
            keys, env_states, obs)
        # replicate the judged params over the assess slice (a local view
        # on a 1-wide slice; a broadcast onto a wider one) so the sharded
        # step program never mixes committed device sets; cached until
        # the source tree is rebound (a completed round / publish)
        src = (tenant.offline["params"] if self.cfg.strict_order
               else tenant.ready_params)
        ck = (pk[0], sl)
        if ck not in self._assess_params or \
                self._assess_params[ck][0] is not src:
            self._assess_params[ck] = (src, jax.device_put(
                src, sl.replicated()))
        params = self._assess_params[ck][1]
        outs = []
        remaining = max(r.budget_steps for r in reqs)
        while remaining > 0:
            k = max(w for w in _pow2_ladder(self.horizon_cap)
                    if w <= remaining)
            program = _step_program(sl, pool.net_cfg, pool.env_cfg,
                                    pool.et_cfg, k)
            carry, out = program(params, carry,
                                 self._assess_noise_dev(sl, width))
            outs.append((k, out["runtime_ns"], out["early"]))
            remaining -= k
        return _PendingAssess(pk[0], list(chunk), env_states["r_best"],
                              outs, params)

    def drain(self, block: bool = False, deadline_s: float | None = None):
        """Judge every in-flight pooled assessment whose device work has
        completed (all of them when `block`), in dispatch order: fetch
        the per-slot runtime scalars, compare each window's offline best
        against its online summary, and hot-swap winners.

        The annex watchdog rides along: a dispatched entry not ready
        after `HealthConfig.dispatch_timeout_s` of wall time is
        abandoned (counted, annex breaker struck) instead of blocking
        forever, and a blocking drain stops at `deadline_s` (from
        `flush`'s partial-flush budget) with the remainder in flight."""
        t_start = time.monotonic()
        while self.inflight:
            entry = self.inflight[0]
            if not entry.ready():
                if self.health.watchdog_expired(entry.dispatched_at):
                    # hung dispatch: abandon the verdict (its windows
                    # simply keep their online summaries) and strike
                    self.inflight.popleft()
                    self.health.dropped_dispatches += 1
                    self.health.note_annex_failure()
                    continue
                if not block:
                    break
                if deadline_s is not None and \
                        (time.monotonic() - t_start) >= deadline_s:
                    break
                time.sleep(5e-4)
                continue
            self.inflight.popleft()
            with span("litune.o2.drain", acc=self.phase_ms, key="assess"):
                self._judge(entry)

    def _judge(self, entry: _PendingAssess):
        """Fetch one completed assessment's runtimes and settle each of
        its windows: a win hot-swaps (or, staged, enters the trial)."""
        r0s = np.asarray(jax.device_get(entry.r0))
        rts = np.concatenate(
            [np.asarray(jax.device_get(r)) for _, r, _ in entry.outs])
        earls = np.concatenate(
            [np.asarray(jax.device_get(e)) for _, _, e in entry.outs])
        deltas: dict[int, float] = {}   # slot column -> delta (ns)
        wins: dict[int, float] = {}     # winning columns only
        stops: dict[int, int] = {}
        tenant = self.tenants[entry.index_type]
        candidate_ok = None   # lazy health verdict on entry.params
        for j, (req, summary, pend) in enumerate(entry.items):
            T = req.budget_steps
            hit = np.flatnonzero(earls[:T, j])
            stop = int(hit[0]) + 1 if hit.size else T
            stops[j] = stop
            best = _pooled_best(float(r0s[j]), rts[:stop, j])
            self.assessments += 1
            delta = summary["best_runtime_ns"] - best
            deltas[j] = delta
            if best < summary["best_runtime_ns"]:
                wins[j] = delta
                if not self.swap_cfg.staged:
                    # the immediate path — bitwise the pre-pipeline
                    # behavior: every per-window win swaps, in order
                    if tenant.quarantined:
                        continue    # breaker open: serve frozen
                    if candidate_ok is None:
                        # swap candidacy gate, once per entry: a
                        # non-finite/exploded tree never reaches a
                        # pool, win or not
                        candidate_ok = self.health.params_healthy(
                            entry.params)
                        if not candidate_ok:
                            self.health.rejected_params += 1
                            tenant.rejected_params += 1
                            self._note_bad(tenant)
                    if not candidate_ok:
                        continue
                    tenant.swap.candidates += 1
                    tenant.swap.immediate += 1
                    tenant.swap.promoted += 1
                    self.hot_swap(entry.index_type, req,
                                  window=pend["window"] - 1,
                                  params=entry.params)
                    summary["swapped"] = True
        if wins and self.swap_cfg.staged and not tenant.quarantined:
            self._judge_staged(entry, deltas, wins, stops, rts)

    # ------------------------------------------- the swap state machine
    # verdict win -> [CI gate] -> candidate -> [canary trial] -> promoted
    # -> [watch window], with auto-rollback out of both bracketed stages.
    # All host-side bookkeeping: the only device work is the same pure
    # buffer updates the immediate path already performed.

    def _judge_staged(self, entry: _PendingAssess, deltas: dict,
                      wins: dict, stops: dict, rts: np.ndarray):
        """Entry-level verdict for the staged pipeline: one pooled
        assessment produces one candidate at most (the window with the
        largest improvement), gated on the bootstrap CI when armed."""
        tenant = self.tenants[entry.index_type]
        if not self.health.params_healthy(entry.params):
            # swap candidacy gate: an unhealthy tree is rejected before
            # the CI gate can even look at it — it never becomes a
            # candidate, never touches a canary lane
            self.health.rejected_params += 1
            tenant.rejected_params += 1
            self._note_bad(tenant)
            return
        if self.swap_cfg.ci_gate:
            if len(entry.items) > 1:
                samples = list(deltas.values())
            else:
                # a single-window dispatch has one per-window delta — fall
                # back to per-step deltas (online best vs each offline
                # assessment step) so the bootstrap still sees spread
                j = next(iter(deltas))
                summary = entry.items[j][1]
                samples = (summary["best_runtime_ns"]
                           - rts[:stops[j], j]).tolist()
            lo, _ = _bootstrap_ci(samples, self.swap_cfg.ci_level,
                                  self.swap_cfg.ci_resamples, self._ci_rng)
            if lo <= 0.0:
                # the interval does not exclude zero: a win this noisy is
                # not evidence the offline model is better
                tenant.swap.ci_rejected += 1
                return
        self._on_win(entry, max(wins, key=wins.get))

    def _on_win(self, entry: _PendingAssess, j: int):
        """One gated candidate: promote immediately (canary stage off),
        defer (a trial is already live), or start the canary trial."""
        req, summary, pend = entry.items[j]
        tenant = self.tenants[entry.index_type]
        tenant.swap.candidates += 1
        window = pend["window"] - 1
        if not self.swap_cfg.canary:
            # CI-gate-only posture: promote pool-wide now, but still arm
            # the post-promotion watch so the monitor can revert it
            trial = _SwapTrial(entry.index_type, req, window, summary,
                               copy_state(entry.params),
                               copy_state(tenant.online),
                               self._baseline_mean(tenant))
            tenant.swap.immediate += 1
            self.trials[entry.index_type] = trial
            self._promote_trial(entry.index_type, trial)
            return
        if entry.index_type in self.trials:
            tenant.swap.deferred += 1
            summary["swap_deferred"] = True
            return
        self._start_trial(entry, j)

    @staticmethod
    def _baseline_mean(tenant: _TenantO2) -> float | None:
        return (float(np.mean(tenant.baseline))
                if tenant.baseline else None)

    def _canary_lanes(self, slots: int) -> list[int]:
        """The trailing `canary_fraction` of a pool's lanes (at least
        one; at most slots-1 so a multi-lane pool keeps a control arm)."""
        n = max(1, int(round(self.swap_cfg.canary_fraction * slots)))
        if slots > 1:
            n = min(n, slots - 1)
        return list(range(slots - n, slots))

    def _start_trial(self, entry: _PendingAssess, j: int):
        """Land the candidate on a lane fraction of every pool of the
        tenant — a pure buffer update per pool (`set_canary` builds the
        mixed per-lane tree for the resident `per_lane` step program)."""
        req, summary, pend = entry.items[j]
        tenant = self.tenants[entry.index_type]
        pools = [p for pk, p in self.pools.items()
                 if pk[0] == entry.index_type]
        if not pools:
            # nothing to canary on (the tenant's pools were torn down
            # between dispatch and drain); treat as deferred
            tenant.swap.deferred += 1
            return
        candidate = copy_state(entry.params)
        trial = _SwapTrial(entry.index_type, req, pend["window"] - 1,
                           summary, candidate, copy_state(tenant.online),
                           self._baseline_mean(tenant))
        for pool in pools:
            pool.set_canary(self._canary_lanes(pool.slots), candidate)
        if self.health.fire("canary_loss"):
            trial.forced_loss = True
        self.trials[entry.index_type] = trial
        tenant.swap.canaried += 1
        tenant.swap.active_state = "canary"
        summary["canaried"] = True

    def _observe_retired(self, retired: list):
        """Feed retired-episode scores into the tenant baselines and any
        live trial's arms (the pool lane-tagged each summary at retire
        while its canary was live)."""
        for req, summary in retired:
            tenant = self.tenants[req.index_type]
            trial = self.trials.get(req.index_type)
            score = _lane_score(summary)
            if trial is None:
                tenant.baseline.append(score)
            elif trial.state == "canary":
                if "canary" in summary:
                    (trial.canary_scores if summary["canary"]
                     else trial.control_scores).append(score)
            else:
                trial.post_scores.append(score)

    def _advance_trials(self):
        """Decide every live trial that has enough evidence: canary arms
        compare once the canary side has `canary_min_episodes` retired
        summaries (against concurrent control lanes, falling back to the
        tenant's pre-swap baseline); promoted trials regression-check
        against that baseline.  Idle canaries time out into rollback."""
        cfg = self.swap_cfg
        for it, trial in list(self.trials.items()):
            if trial.state == "canary":
                trial.ticks += 1
                if trial.forced_loss:
                    # injected canary loss (the chaos drill's repeated-
                    # rollback site): decide against it immediately
                    self._rollback_canary(it, trial)
                    continue
                if len(trial.canary_scores) >= cfg.canary_min_episodes:
                    control = (float(np.mean(trial.control_scores))
                               if len(trial.control_scores)
                               >= cfg.canary_min_episodes
                               else trial.baseline_mean)
                    if control is not None:
                        canary = float(np.mean(trial.canary_scores))
                        if canary <= control * (1.0 + cfg.canary_tolerance):
                            self._promote_trial(it, trial)
                        else:
                            self._rollback_canary(it, trial)
                        continue
                if trial.ticks > cfg.canary_timeout_ticks:
                    self._rollback_canary(it, trial)
            elif trial.state == "promoted":
                if trial.baseline_mean is not None and \
                        len(trial.post_scores) >= cfg.canary_min_episodes:
                    post = float(np.mean(trial.post_scores))
                    if post > trial.baseline_mean * \
                            (1.0 + cfg.rollback_tolerance):
                        self._rollback_promoted(it, trial, "regression")

    def _promote_trial(self, index_type: str, trial: _SwapTrial):
        """Pool-wide promotion of a trial's candidate: clear the canary
        mix, snapshot the rollback state (pre-swap online tree + monitor
        reference), then run the standard hot swap.  The cleared pools
        re-enter the shared-params step program — still resident, still
        zero re-traces."""
        tenant = self.tenants[index_type]
        for pk, pool in self.pools.items():
            if pk[0] == index_type and pool.canary_lanes is not None:
                pool.clear_canary()
        # refresh the rollback snapshot at the promotion boundary (the
        # online tree cannot have moved during the trial — wins defer —
        # but the monitor reference may have: windows kept arriving)
        trial.prev_online = copy_state(tenant.online)
        mon = tenant.monitor
        trial.monitor_ref = (None if mon.ref_quantiles is None
                             else mon.ref_quantiles.copy(), mon.ref_wr)
        trial.prev_anchor = mon.anchors[-1] if mon.anchors else None
        self.hot_swap(index_type, trial.req, window=trial.window,
                      params=trial.candidate)
        trial.summary["swapped"] = True
        trial.state = "promoted"
        trial.ticks = 0
        trial.watch_windows = 0
        trial.post_scores = []
        tenant.swap.promoted += 1
        tenant.swap.active_state = "promoted"

    def _rollback_canary(self, index_type: str, trial: _SwapTrial,
                         note: bool = True):
        """Abort a canary: drop the per-lane mix on every pool — the
        incumbent `pool.params` was never touched, so this *is* the
        bitwise revert — and retire the trial.  A rollback is a breaker
        strike (`note`), except when the breaker itself triggered it."""
        for pk, pool in self.pools.items():
            if pk[0] == index_type and pool.canary_lanes is not None:
                pool.clear_canary()
        tenant = self.tenants[index_type]
        tenant.swap.rolled_back_canary += 1
        tenant.swap.active_state = None
        trial.summary["swap_rolled_back"] = "canary"
        del self.trials[index_type]
        if note:
            self._note_bad(tenant)

    def _rollback_promoted(self, index_type: str, trial: _SwapTrial,
                           reason: str, note: bool = True):
        """Revert a promoted swap bitwise: restore the pre-swap online
        tree on every pool and the divergence monitor's pre-promotion
        reference distribution (re-appending the pre-swap anchor keeps
        the monitor's anchors-history invariant — the revert stays
        visible)."""
        tenant = self.tenants[index_type]
        tenant.online = trial.prev_online
        for pk, pool in self.pools.items():
            if pk[0] == index_type:
                pool.params = jax.device_put(tenant.online["params"],
                                             pool.replicated)
        if trial.monitor_ref is not None:
            mon = tenant.monitor
            mon.ref_quantiles, mon.ref_wr = trial.monitor_ref
            if trial.prev_anchor is not None:
                mon.anchors.append(trial.prev_anchor)
        tenant.swap.rolled_back_promoted += 1
        tenant.swap.active_state = None
        trial.summary["swap_rolled_back"] = reason
        del self.trials[index_type]
        if note:
            self._note_bad(tenant)

    def _close_trial(self, index_type: str):
        """A promoted trial survived its watch window: drop the rollback
        snapshots and free the tenant for the next candidate (a
        surviving swap also clears the tenant's breaker streak)."""
        self.trials.pop(index_type, None)
        tenant = self.tenants[index_type]
        tenant.swap.active_state = None
        tenant.bad_streak = 0

    def swap_stats(self) -> SwapStats:
        """The `stats()["swaps"]` block's data (the service adds SLO
        breach attribution before rendering)."""
        return SwapStats(per_tenant={it: t.swap
                                     for it, t in self.tenants.items()})

    def health_stats(self) -> HealthStats:
        """The `stats()["health"]` block's data: the guard's aggregate
        counters plus the currently quarantined tenant list."""
        g = self.health
        return HealthStats(
            state="degraded" if g.degraded else "healthy",
            rejected_params=g.rejected_params,
            retries=g.retries,
            annex_demotions=g.annex_demotions,
            annex_recoveries=g.annex_recoveries,
            dropped_dispatches=g.dropped_dispatches,
            quarantines=g.quarantines,
            quarantine_releases=g.quarantine_releases,
            degraded_ticks=g.degraded_ticks,
            quarantined=sorted(it for it, t in self.tenants.items()
                               if t.quarantined))

    def hot_swap(self, index_type: str, req,
                 window: int | None = None, params=None):
        """Promote the offline model to online: a pure buffer update on
        every pool of the tenant.  Params are program *inputs*, not traced
        constants, so the K-ladder compiled-program cache is untouched —
        no re-trace, no re-compile (asserted in tests/test_o2_service.py).
        `params` is the judged tree an assessment verdict promotes (the
        concurrent learner may have advanced past it by drain time);
        None — the strict/serial case and direct callers — promotes the
        offline tail.  `window` is the retired window whose data
        re-anchors the monitor (under concurrent serving it may not be
        the latest one observed).

        Swap timing rides the service's injectable clock (not a bare
        `time.perf_counter`), so `mean_swap_ms` shares the timebase of
        every other latency the service reports — and tests can pin it
        with a fake clock."""
        t0 = self.clock()
        tenant = self.tenants[index_type]
        # real copies: the next fine-tune round donates the offline
        # tree's buffers, and the promoted online model must outlive that
        tenant.online = copy_state(tenant.offline)
        if params is not None:
            tenant.online["params"] = copy_state(params)
        for pk, pool in self.pools.items():
            if pk[0] == index_type:
                pool.params = jax.device_put(tenant.online["params"],
                                             pool.replicated)
        tenant.monitor.re_anchor(req.data_keys, req.wr_ratio,
                                 window=window)
        tenant.swaps += 1
        tenant.swap_times_s.append(self.clock() - t0)

    def flush(self, deadline_s: float | None = None) -> dict:
        """Settle all in-flight O2 work: the assessment backlog drains
        through the annex, every verdict lands (hot-swaps applied), and
        the trailing offline learner catches up.  Blocks; callers that
        only need serving results never have to.

        Returns a flush report instead of hanging: with `deadline_s`
        set, whatever has not settled by then is abandoned and counted;
        without one, a demoted annex (which can never settle its
        backlog) still abandons rather than spinning forever, and hung
        dispatches are abandoned by the drain watchdog — the historical
        block-until-settled contract only ever applies to work that can
        actually finish."""
        t0 = time.monotonic()
        report = {"deadline_hit": False, "abandoned_backlog": 0,
                  "abandoned_inflight": 0, "elapsed_s": 0.0}
        while self.backlog or self.inflight:
            out_of_time = deadline_s is not None and \
                (time.monotonic() - t0) >= deadline_s
            # a paused annex with nothing in flight cannot make progress
            # until its cooloff elapses — and a failed half-open probe
            # restarts that clock, so waiting it out is unbounded
            stalled = self.health.o2_paused() and not self.inflight
            if out_of_time or stalled:
                report["deadline_hit"] = out_of_time
                report["abandoned_backlog"] = len(self.backlog)
                report["abandoned_inflight"] = len(self.inflight)
                self.health.dropped_dispatches += len(self.inflight)
                self.backlog.clear()
                self.inflight.clear()
                break
            self._pump_assessments()
            remaining = (None if deadline_s is None
                         else max(deadline_s - (time.monotonic() - t0),
                                  0.0))
            self.drain(block=True, deadline_s=remaining)
        if not report["deadline_hit"]:
            for tenant in self.tenants.values():
                if tenant.offline is not None:
                    jax.block_until_ready(tenant.offline["params"])
        report["elapsed_s"] = time.monotonic() - t0
        return report

    # ------------------------------------------------------------- stats
    def stats_block(self) -> O2Stats:
        tenants = {
            it: TenantO2Stats(
                windows=t.monitor.windows_seen,
                diverged=t.monitor.diverged_count,
                swaps=t.swaps,
                offline_updates=t.offline_updates,
                finetune_skipped=t.finetune_skipped,
                replay_size=t.replay.size,
                mean_swap_ms=(1e3 * float(np.mean(t.swap_times_s))
                              if t.swap_times_s else 0.0),
                tier=t.tier)
            for it, t in self.tenants.items()}
        tiers = {"hot": 0, "warm": 0, "cold": 0}
        for t in self.tenants.values():
            tiers[t.tier] += 1
        return O2Stats(
            tenants=tenants,
            # host-side time spent driving each O2 phase (dispatch +
            # verdict fetches — device execution overlaps serving)
            phase_ms={k: round(v, 3) for k, v in self.phase_ms.items()},
            assessments=self.assessments,
            inflight_assessments=len(self.inflight),
            pending_missing=self.pending_missing,
            # annex placement (the topology layer's verdict): a shared
            # annex queues learner/assessment work behind serving fetches
            annex_width=self.topology.annex.width,
            annex_shared=self.topology.annex_shared,
            warm_starts=self.warm_starts,
            tenants_hot=tiers["hot"],
            tenants_warm=tiers["warm"],
            tenants_cold=tiers["cold"],
            device_bytes=sum(t.device_bytes()
                             for t in self.tenants.values()),
            host_bytes=sum(t.host_bytes()
                           for t in self.tenants.values()),
            fleet=(self.fleet.stats() if self.fleet is not None
                   else FleetLearner.empty_stats()))

    def stats(self) -> dict:
        return self.stats_block().as_dict()
