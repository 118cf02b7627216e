"""The serving stack's marks in a profiler trace:

* every serving program carries its name as its outermost scope
  (`pool_tick`, `env_reset`) and the environment's phase scopes in its
  ops' `op_name` metadata, at 4,096 keys;
* the scopes change nothing else: the compiled tick and reset programs,
  stripped of metadata and of the source-location tables, are the same
  with the scopes turned into null contexts;
* a small O2 service run under `jax.profiler` writes every `litune.*`
  host span, nested as `launch/serving/tracing.py` lists them, with
  their ids, and `stats()["o2"]["phase_ms"]` still grows.
"""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.serving.programs as programs
from repro.core.ddpg import DDPGConfig
from repro.core.litune import LITune, LITuneConfig
from repro.core.o2 import O2Config
from repro.index.workloads import sample_keys, wr_workload
from repro.kernels.dispatch import KernelConfig
from repro.launch.serving import O2ServiceConfig, TuningService
from repro.launch.serving.config import ServeConfig

N_KEYS = 4096
PHASES = ("agent", "env_build", "env_reads", "probe", "env_inserts",
          "env_observe")


def _config() -> LITuneConfig:
    # seq_len=3 < the 4-step windows so the offline learner fine-tunes
    return LITuneConfig(index_type="alex", episode_len=4, lstm_hidden=16,
                        mlp_hidden=32,
                        ddpg=DDPGConfig(seq_len=3, burn_in=1, batch_size=8),
                        o2=O2Config(divergence_threshold=0.05,
                                    offline_updates_per_window=2))


def _service(n_keys: int, n_req: int) -> TuningService:
    """An O2 service with `n_req` drifting windows queued (the key
    distribution changes every window, so assessments run)."""
    svc = TuningService(LITune(_config(), seed=0), config=ServeConfig(
        slots=2, horizon_cap=4, seed=0, o2=O2ServiceConfig(enabled=True)))
    dists, wrs = ["uniform", "books", "osm", "fb"], [1.0, 1.0, 3.0, 0.33]
    key = jax.random.PRNGKey(7)
    for i in range(n_req):
        k = jax.random.fold_in(key, i)
        data = sample_keys(k, n_keys, dists[i % 4])
        wl, _ = wr_workload(jax.random.fold_in(k, 1), data, wrs[i % 4],
                            total=n_keys, dist="mix")
        svc.submit(data, wl, wrs[i % 4], budget_steps=4)
    return svc


@pytest.fixture(scope="module")
def pool():
    """A pool at 4,096 keys after one admission wave: its carry, params
    and capture buffer are the operands of every program variant."""
    svc = _service(N_KEYS, 2)
    svc._admit_from_queue()
    (pk, pool), = svc.pools.items()
    return pool


def _tick_args(pool, per_lane: bool, capture: bool):
    params = pool.params
    if per_lane:
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (pool.slots,) + x.shape), params)
    args = [params, pool.carry, pool.noise_dev()]
    if capture:
        args += [pool.ensure_cap(), np.zeros(pool.slots, np.int32)]
    return args


def _reset_args(pool):
    w = pool.slots
    n = pool.carry["env"]["data_keys"].shape[1]
    r = pool.carry["env"]["reads"].shape[1]
    i = pool.carry["env"]["inserts"].shape[1]
    return (np.zeros((w, n), np.float32), np.zeros((w, r), np.float32),
            np.zeros((w, i), np.float32), np.ones((w,), np.float32))


def _compiled(program, args) -> str:
    return program.lower(*args).compile().as_text()


def _scopes(hlo: str) -> set:
    return {seg for name in re.findall(r'op_name="([^"]*)"', hlo)
            for seg in name.split("/")}


@pytest.mark.parametrize("per_lane", [False, True])
@pytest.mark.parametrize("capture", [False, True])
def test_step_program_scopes(pool, per_lane, capture):
    prog = programs._step_program(pool.slice, pool.net_cfg, pool.env_cfg,
                                  pool.et_cfg, 2, per_lane=per_lane,
                                  capture=capture)
    hlo = _compiled(prog, _tick_args(pool, per_lane, capture))
    assert {"pool_tick", *PHASES} <= _scopes(hlo)
    assert "env_reset" not in _scopes(hlo)
    # the jitted function keeps its name: module names are read by the
    # benchmark's trace readers
    assert hlo.startswith("HloModule jit_core" if capture
                          else "HloModule jit_scan_core")


def test_reset_program_scopes(pool):
    prog = programs._reset_program(pool.slice, pool.env_cfg)
    scopes = _scopes(_compiled(prog, _reset_args(pool)))
    assert {"env_reset", *PHASES[1:]} <= scopes
    assert "pool_tick" not in scopes and "agent" not in scopes


def test_step_program_scopes_the_probes_routing(pool):
    """With the probe kernel on (interpret mode here, compiled on the
    chip) the tick's routing of reads to tiles carries `probe_route`
    inside `probe`, which `probe_route_device_ms_per_step` reads."""
    env_cfg = dataclasses.replace(pool.env_cfg,
                                  kernel=KernelConfig(mode="interpret"))
    prog = programs._step_program(pool.slice, pool.net_cfg, env_cfg,
                                  pool.et_cfg, 2)
    hlo = _compiled(prog, _tick_args(pool, False, False))
    # the program's ops (a reducer's body names only its own scope)
    routed = [name.split("/") for name in
              re.findall(r'op_name="(jit\([^"]*)"', hlo)
              if "probe_route" in name.split("/")]
    assert routed
    for segs in routed:
        assert segs.index("pool_tick") < segs.index("env_reads") \
            < segs.index("probe") < segs.index("probe_route"), segs


def test_step_program_scopes_the_leaf_fit_and_the_overflow(pool):
    """The exact leaf and root fits carry `leaf_fit` inside `env_build`,
    and the probe's overflow chunks `probe_overflow` inside `probe`
    (kernel on, as above), which `leaf_fit_device_ms_per_step` and
    `probe_overflow_device_ms_per_step` read."""
    env_cfg = dataclasses.replace(pool.env_cfg,
                                  kernel=KernelConfig(mode="interpret"))
    prog = programs._step_program(pool.slice, pool.net_cfg, env_cfg,
                                  pool.et_cfg, 2)
    hlo = _compiled(prog, _tick_args(pool, False, False))
    paths = [name.split("/") for name in
             re.findall(r'op_name="(jit\([^"]*)"', hlo)]
    for scopes in (("pool_tick", "env_build", "leaf_fit"),
                   ("pool_tick", "env_reads", "probe", "probe_overflow")):
        found = [segs for segs in paths if scopes[-1] in segs]
        assert found, scopes
        for segs in found:
            at = [segs.index(s) for s in scopes]
            assert at == sorted(at), segs


_LOCATION_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames")


def _strip(hlo: str) -> str:
    """The HLO without op metadata and the source-location tables."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    out, table = [], False
    for line in hlo.splitlines():
        if line in _LOCATION_TABLES:
            table = True
        elif table and re.match(r"^\d+ ", line):
            continue
        else:
            table = False
            out.append(line)
    return "\n".join(out)


def test_scopes_leave_compiled_programs_unchanged(pool, monkeypatch):
    """Fresh (uncached) tick and reset programs, compiled with the
    scopes and with `jax.named_scope` a null context, are the same
    program."""
    def build():
        tick = programs._step_program.__wrapped__(
            pool.slice, pool.net_cfg, pool.env_cfg, pool.et_cfg, 2)
        reset = programs._reset_program.__wrapped__(pool.slice,
                                                    pool.env_cfg)
        return (_compiled(tick, _tick_args(pool, False, False)),
                _compiled(reset, _reset_args(pool)))

    scoped = build()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = build()
    assert "pool_tick" in _scopes(scoped[0])
    assert "pool_tick" not in _scopes(plain[0])
    for a, b in zip(scoped, plain):
        assert a != b
        assert _strip(a) == _strip(b)


# ------------------------------------------------------------ host spans
# child -> the span it nests in, on the thread that ran it
NESTING = {
    "litune.admit": "litune.step",
    "litune.admit.stage": "litune.admit",
    "litune.admit.reset": "litune.admit",
    "litune.admit.sync": "litune.admit",
    "litune.tick": "litune.step",
    "litune.tick.dispatch": "litune.tick",
    "litune.tick.fetch": "litune.tick",
    "litune.tick.collect": "litune.tick",
    "litune.o2.capture": "litune.tick.collect",
    "litune.o2.assess": "litune.step",
    "litune.o2.finetune": "litune.step",
    "litune.o2.drain": "litune.step",
}
IDS = {"litune.tick": {"k", "slots", "active"}}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("litune."):
                    out.append((plane.name, line.name, ev.name,
                                ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def test_service_spans_under_the_profiler(tmp_path):
    svc = _service(512, 6)
    svc.step()                      # compiles outside the trace
    before = dict(svc.stats()["o2"]["phase_ms"])
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(8):
            svc.step()
    after = svc.stats()["o2"]["phase_ms"]
    assert set(after) == set(before)
    assert sum(after.values()) > sum(before.values())
    assert after["capture"] > before["capture"]
    assert after["assess"] > before["assess"]
    svc.flush_o2()

    spans = _host_spans(tmp_path)
    names = {s[2] for s in spans}
    assert {"litune.step", *NESTING} <= names
    for child, parent in NESTING.items():
        for plane, line, _, s, e, _ in (x for x in spans
                                        if x[2] == child):
            assert any(p[:2] == (plane, line) and p[3] <= s and e <= p[4]
                       for p in spans if p[2] == parent), (child, parent)
    for name, ids in IDS.items():
        for s in spans:
            if s[2] == name:
                assert ids <= set(s[5]), (name, s[5])
    ticks = [s[5] for s in spans if s[2] == "litune.tick"]
    assert all(t["slots"] == 2 and 1 <= t["active"] <= 2 for t in ticks)
    assert all(t["k"] in (1, 2, 4) for t in ticks)
