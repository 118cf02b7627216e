"""Drive one cell through the tuning service's public API.

The system under test is `repro.launch.serving.TuningService`: requests
go in through `submit`, the benchmark's own loop calls `step` (one tick:
admission, one K-step program per active pool, retirement, and under O2
the annex's fine-tune rounds and assessments), and results come back in
`results`.  The loop is closed: `outstanding` requests are in the
service at any time, and each completion is replaced at once by the
next request of a ring built from the seed.  Every host-side phase sits
in a `TraceAnnotation` of the benchmark's own, so a traced run can say
what the host was doing in each idle gap of the device.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

import traffic as T

SPAN_SUBMIT = "bench.submit"
SPAN_STEP = "bench.step"
SPAN_HANDOUT = "bench.handout"
SPAN_WINDOW = "bench.window"
SPAN_CHECK = "bench.check"


def program_seed(seed: int) -> int:
    """The program's agent and service take 31-bit seeds: fold a larger
    one into that range."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


# The agent's weights are drawn from this seed in every run: one agent
# serves every run, as a deployed agent does, so a run's seed varies the
# requests and the exploration noise and never the agent.
AGENT_SEED = 0


def make_agent(config: dict):
    """The cell's agent at the configuration's widths, with the ET-MDP
    runtime budget held at its per-operation value
    (`runtime_budget_ns_per_op` times the request's operations), its
    weights drawn from `AGENT_SEED`."""
    from repro.core.litune import LITune, LITuneConfig

    ops = config["ops_per_request"]
    budget = config["runtime_budget_ns_per_op"] * ops

    @dataclasses.dataclass(frozen=True)
    class CellConfig(LITuneConfig):
        def env_cfg(self):
            return dataclasses.replace(super().env_cfg(),
                                       runtime_budget=budget)

    cfg = CellConfig(index_type=config["index_type"],
                     episode_len=config["budget_steps"],
                     lstm_hidden=config["agent"]["lstm_hidden"],
                     mlp_hidden=config["agent"]["mlp_hidden"])
    return LITune(cfg, seed=AGENT_SEED)


def make_service(agent, config: dict, traffic: dict, seed: int,
                 devices):
    from repro.launch.serving import (O2ServiceConfig, ServeConfig,
                                      ServingTopology, TuningService)
    o2 = O2ServiceConfig(enabled=bool(traffic["o2"]))
    return TuningService(agent, config=ServeConfig(
        slots=config["slots"], horizon_cap=config["horizon_cap"],
        seed=program_seed(seed), o2=o2,
        topology=ServingTopology.host(config["slots"], devices=devices)))


def make_requests(config: dict, traffic: dict, seed: int):
    ops = config["ops_per_request"]
    if traffic["reads"] + traffic["inserts"] != ops:
        raise ValueError("traffic's reads + inserts must equal the "
                         "configuration's ops_per_request")
    return T.make_ring(seed, traffic["ring"], n_keys=config["n_keys"],
                       n_reads=traffic["reads"],
                       n_inserts=traffic["inserts"],
                       dist=config["key_distribution"],
                       drift=traffic["drift"], offset=traffic["offset"])


class ClosedLoop:
    """Client side of the closed loop: submits ring requests in turn and
    records which request each id carries."""

    def __init__(self, svc, ring, budget: int):
        self.svc = svc
        self.ring = ring
        self.budget = budget
        self.next = 0
        self.outstanding: dict[int, int] = {}     # rid -> ring index
        self.done: dict[int, int] = {}            # rid -> ring index

    def submit(self, budget: int | None = None):
        with jax.profiler.TraceAnnotation(SPAN_HANDOUT):
            req = self.ring[self.next % len(self.ring)]
            self.next += 1
            wl = {"reads": req.reads, "inserts": req.inserts}
        with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
            rid = self.svc.submit(req.keys, wl, req.wr_ratio,
                                  budget_steps=budget or self.budget)
        self.outstanding[rid] = req.index
        return rid

    def step(self, refill: bool) -> tuple[int, list[int]]:
        """One service tick; returns (useful steps, ids completed).
        With `refill`, each completion is replaced by a new request."""
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            work = self.svc.step()
        finished = [r for r in self.outstanding if r in self.svc.results]
        for rid in finished:
            self.done[rid] = self.outstanding.pop(rid)
            if refill:
                self.submit()
        return work, finished

    def drain(self):
        while self.outstanding:
            self.step(refill=False)


def warm_up(loop: ClosedLoop, waves):
    """Run each warm-up wave (`[requests, budget]`) to completion: the
    waves are chosen so that every admission width and every K of the
    ladder that the window can use has run once."""
    for n, budget in waves:
        for _ in range(n):
            loop.submit(budget)
        loop.drain()
    if loop.svc.o2rt is not None:
        loop.svc.flush_o2()


@dataclasses.dataclass
class Window:
    seconds: float
    steps: int
    ticks: int
    completed: list            # rids completed inside the window


def run_window(loop: ClosedLoop, outstanding: int, seconds: float,
               on_start=None) -> Window:
    """Fill the loop to `outstanding`, then tick until `seconds` have
    passed; the window closes at the end of the tick that crosses it."""
    while len(loop.outstanding) < outstanding:
        loop.submit()
    if on_start is not None:
        on_start()
    steps = ticks = 0
    completed: list[int] = []
    with jax.profiler.TraceAnnotation(SPAN_WINDOW):
        t0 = time.perf_counter()
        while True:
            work, finished = loop.step(refill=True)
            steps += work
            ticks += 1
            completed.extend(finished)
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
    return Window(t - t0, steps, ticks, completed)


def tree_finite(tree) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree.leaves(tree))
