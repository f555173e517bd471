"""Trajectory dispatcher behind the unified `RunSpec` API.

`RunSpec` is THE run configuration: problem, hyper, engine selection,
arrival schedule, data source, worker mesh and chunking in one frozen,
typed object.  `run(spec)` is the canonical entry; every engine hangs
off `spec.engine`:

  "scan"   (default) materialize the straggler schedule up front and
           execute the whole trajectory inside one compiled `lax.scan`
           (`repro.core.engine.run_scanned`); with `chunk_size` set the
           trajectory splits into state-continued dispatches with
           `chunk_hook` called on the live carry at chunk boundaries
           (`repro.core.engine.run_chunked`).  `metrics_fn` must be
           JAX-traceable.
  "sweep"  R whole trajectories (per-seed schedules, per-run
           data/hypers) in one vmapped dispatch
           (`repro.core.engine.run_swept`).
  "eager"  the per-iteration host loop: arbitrary host-side
           `metrics_fn` callbacks and per-iteration host timestamps.
  "async"  the REAL asynchronous federation runtime
           (`repro.fed.runtime`): a master plus `hyper.n_workers`
           worker endpoints exchanging serialized messages over a
           pluggable transport — workers compute Eq. 16 gradients at
           their own pace, the master applies them stale under the
           S-of-N / tau arrival rule and records the LIVE arrival
           process (returned as `RunResult.arrivals`).  Passing
           `schedule` replays that arrival order deterministically —
           the conformance mode that reproduces `run_scanned`.

The historical kwargs form ``run(problem, hyper, mode=..., ...)`` still
works as a thin shim (it builds a `RunSpec` and emits a
`DeprecationWarning`); new call sites should construct the spec.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import afto as afto_lib
from repro.core import engine as engine_lib
from repro.core import stationarity as stat_lib
from repro.core.engine import RunResult, SweepResult
from repro.core.scheduler import (Schedule, StragglerConfig,
                                  StragglerScheduler)
from repro.core.types import AFTOState, Hyper, TrilevelProblem
from repro.data import stream as stream_lib
from repro.data.stream import Stream

ENGINES = ("scan", "sweep", "eager", "async")


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """One run, fully specified.

    Engine-shape fields (what used to be the `run(...)` kwarg sprawl):

      problem / hyper   the trilevel problem and algorithm hypers.
      engine            "scan" | "sweep" | "eager" | "async".
      n_iterations      master iterations T (ignored when `schedule`
                        fixes the length).
      scheduler         `StragglerConfig` for the simulated arrival
                        process (defaults to hyper's N/S/tau); unused
                        by engine="async", whose arrivals are real.
      schedule          a materialized `Schedule`: the arrival order to
                        run ("scan") or to replay deterministically
                        ("async" conformance mode).
      schedules/seeds   per-run arrival processes for engine="sweep"
                        (one of them; `seeds` re-seeds `scheduler`).
      metrics_fn        extra per-record metrics; JAX-traceable except
                        on the eager loop.
      metrics_every     record stride.
      state             initial `AFTOState` (continuation runs).
      sweep_states/sweep_hypers  per-run initial states / swept hyper
                        scalars for engine="sweep".
      data              replacement `problem.data` arrays or a
                        `repro.data.stream.Stream` (in-scan synthesis);
                        for sweeps, leaves carry a leading (R,) axis.
      mesh              `jax.sharding.Mesh` with a "worker" axis: the
                        shard_map-distributed engines ("scan"/"sweep").
      jit               False drops to the un-jitted eager loop
                        (debugging).
      chunk_size        engine="scan": split the trajectory into
                        state-continued dispatches of this many
                        iterations.
      chunk_hook        `(state, t_abs) -> state | None`, called on the
                        live carry at every chunk boundary (checkpoint
                        / push-pull seam; requires `chunk_size`).
      transport         engine="async": a `repro.fed.runtime.transport`
                        hub (defaults to an in-process queue transport
                        with one thread per worker).

    Frozen: derive variants with `dataclasses.replace(spec, ...)`.
    """
    problem: TrilevelProblem
    hyper: Hyper
    engine: str = "scan"
    n_iterations: int = 200
    scheduler: Optional[StragglerConfig] = None
    schedule: Optional[Schedule] = None
    schedules: Optional[Sequence[Schedule]] = None
    seeds: Optional[Sequence[int]] = None
    metrics_fn: Optional[Callable] = None
    metrics_every: int = 10
    state: Optional[AFTOState] = None
    sweep_states: Optional[AFTOState] = None
    sweep_hypers: Optional[Mapping] = None
    data: Any = None
    mesh: Any = None
    jit: bool = True
    chunk_size: Optional[int] = None
    chunk_hook: Optional[Callable] = None
    transport: Any = None

    def resolved_scheduler(self) -> StragglerConfig:
        if self.scheduler is not None:
            return self.scheduler
        return StragglerConfig(n_workers=self.hyper.n_workers,
                               s_active=self.hyper.s_active,
                               tau=self.hyper.tau)

    def resolved_iterations(self) -> int:
        if self.schedule is not None:
            return self.schedule.n_iterations
        return self.n_iterations


_LEGACY_KWARGS = {
    "scheduler_cfg": "scheduler", "mode": "engine",
    "n_iterations": "n_iterations", "metrics_fn": "metrics_fn",
    "metrics_every": "metrics_every", "state": "state", "jit": "jit",
    "schedule": "schedule", "schedules": "schedules", "seeds": "seeds",
    "sweep_states": "sweep_states", "sweep_data": "data",
    "sweep_hypers": "sweep_hypers", "mesh": "mesh", "data": "data",
}


def spec_from_kwargs(problem: TrilevelProblem, hyper: Hyper,
                     **kwargs) -> RunSpec:
    """A `RunSpec` from the historical `run(problem, hyper, ...)` kwarg
    surface (`mode`->`engine`, `scheduler_cfg`->`scheduler`,
    `sweep_data`->`data`).  Raises on unknown kwargs and on passing both
    `data` and `sweep_data` (they were one parameter in disguise)."""
    if "data" in kwargs and kwargs.get("sweep_data") is not None \
            and kwargs["data"] is not None:
        raise ValueError(
            "pass per-run data via either `data` or `sweep_data`, "
            "not both")
    fields: Dict[str, Any] = {}
    for name, value in kwargs.items():
        new = _LEGACY_KWARGS.get(name)
        if new is None:
            raise TypeError(f"run() got an unexpected keyword argument "
                            f"{name!r}")
        if value is None and new in fields:
            continue
        if new in fields and fields[new] is not None and value is not None:
            raise ValueError(
                "pass per-run data via either `data` or `sweep_data`, "
                "not both")
        if value is not None or new not in fields:
            fields[new] = value
    return RunSpec(problem=problem, hyper=hyper, **fields)


def run(spec, hyper: Optional[Hyper] = None, **kwargs):
    """Run AFTO.  Canonical form: ``run(RunSpec(...))``.

    The legacy kwargs form ``run(problem, hyper, mode="scan", ...)``
    still works (a shim builds the spec) but is deprecated — see the
    README's kwargs->RunSpec migration table.
    """
    if isinstance(spec, RunSpec):
        if hyper is not None or kwargs:
            raise TypeError(
                "run(spec) takes no extra arguments; derive a new spec "
                "with dataclasses.replace(spec, ...)")
        return run_spec(spec)
    if hyper is None:
        raise TypeError("run(problem, hyper, ...) needs a Hyper (or pass "
                        "a RunSpec)")
    warnings.warn(
        "run(problem, hyper, mode=..., ...) kwargs are deprecated; build "
        "a repro.core.RunSpec and call run(spec) (see the README "
        "migration table)", DeprecationWarning, stacklevel=2)
    return run_spec(spec_from_kwargs(spec, hyper, **kwargs))


def run_spec(spec: RunSpec):
    """Dispatch a `RunSpec` to its engine (the canonical entry's body).

    The whole call is the profiler span `afto.run`; the compiled engines
    nest their phases inside it (README, "Observability")."""
    with TraceAnnotation("afto.run"):
        return _dispatch(spec)


def _precompute(cfg: StragglerConfig, n_iterations: int) -> Schedule:
    with TraceAnnotation("afto.schedule"):
        return StragglerScheduler(cfg).precompute(n_iterations)


def _dispatch(spec: RunSpec):
    problem, hyper = spec.problem, spec.hyper
    engine = spec.engine
    scheduler_cfg = spec.resolved_scheduler()
    n_iterations = spec.resolved_iterations()
    if engine not in ENGINES:
        raise ValueError(
            f"unknown mode {engine!r}; expected 'scan'|'sweep'|'eager'"
            "|'async'")
    if not spec.jit:
        if engine == "sweep":
            raise ValueError("mode='sweep' requires jit")
        if engine == "async":
            raise ValueError("mode='async' requires jit")
        engine = "eager"   # un-jitted debugging only exists on the host loop
    if spec.chunk_hook is not None and spec.chunk_size is None:
        raise ValueError("chunk_hook requires chunk_size")
    if spec.chunk_size is not None and engine != "scan":
        raise ValueError("chunk_size/chunk_hook require engine='scan'")

    if engine == "async":
        from repro.fed import runtime as runtime_lib
        if spec.mesh is not None:
            raise ValueError("mesh= requires mode='scan' or 'sweep'")
        return runtime_lib.run_async(
            problem, hyper, n_iterations=n_iterations,
            metrics_fn=spec.metrics_fn, metrics_every=spec.metrics_every,
            state=spec.state, replay=spec.schedule,
            transport=spec.transport, data=spec.data)

    if engine == "sweep":
        if spec.state is not None or spec.schedule is not None:
            raise ValueError(
                "mode='sweep' takes per-run sweep_states/schedules; the "
                "single-run state/schedule parameters would be silently "
                "ignored")
        if spec.schedules is not None and spec.seeds is not None:
            raise ValueError(
                "pass either explicit `schedules` or `seeds` (which "
                "materialize one schedule per seed), not both")
        schedules = spec.schedules
        if schedules is None:
            seed_list = list(spec.seeds) if spec.seeds is not None \
                else [scheduler_cfg.seed]
            schedules = [
                _precompute(dataclasses.replace(scheduler_cfg, seed=s),
                            n_iterations)
                for s in seed_list]
        return engine_lib.run_swept(
            problem, hyper, schedules, metrics_fn=spec.metrics_fn,
            metrics_every=spec.metrics_every, states=spec.sweep_states,
            data=spec.data, sweep_hypers=spec.sweep_hypers, mesh=spec.mesh)

    if engine == "scan":
        schedule = spec.schedule
        if schedule is None:
            schedule = _precompute(scheduler_cfg, n_iterations)
        if spec.chunk_size is not None:
            return engine_lib.run_chunked(
                problem, hyper, schedule, spec.chunk_size,
                chunk_hook=spec.chunk_hook, metrics_fn=spec.metrics_fn,
                metrics_every=spec.metrics_every, state=spec.state,
                mesh=spec.mesh, data=spec.data)
        return engine_lib.run_scanned(
            problem, hyper, schedule, metrics_fn=spec.metrics_fn,
            metrics_every=spec.metrics_every, state=spec.state,
            mesh=spec.mesh, data=spec.data)
    if spec.mesh is not None:
        raise ValueError("mesh= requires mode='scan' or 'sweep'")
    return _run_eager(spec, scheduler_cfg, n_iterations)


def _run_eager(spec: RunSpec, scheduler_cfg: StragglerConfig,
               n_iterations: int) -> RunResult:
    """The per-iteration host loop (engine="eager"): host `metrics_fn`
    callbacks, per-iteration host timestamps, and the host-fed reference
    the streamed engines are parity-tested against."""
    problem, hyper = spec.problem, spec.hyper
    schedule, state, data = spec.schedule, spec.state, spec.data
    metrics_every, metrics_fn = spec.metrics_every, spec.metrics_fn
    use_jit = spec.jit

    sched = StragglerScheduler(scheduler_cfg)

    stream = data if isinstance(data, Stream) else None
    if data is not None and stream is None:
        problem = dataclasses.replace(
            problem, data=jax.tree.map(jnp.asarray, data))

    def _with(d):
        return problem if d is None else dataclasses.replace(
            problem, data=d)

    step = lambda s, m, d=None: afto_lib.afto_step(_with(d), hyper, s, m)
    refresh = lambda s, d=None: afto_lib.cut_refresh(_with(d), hyper, s)
    gap = lambda s, d=None: stat_lib.stationarity_gap_sq(
        _with(d), hyper, s)
    if use_jit:
        step, refresh, gap = jax.jit(step), jax.jit(refresh), jax.jit(gap)

    if state is None:
        state = afto_lib.init_state(problem, hyper)

    hist: Dict[str, List[float]] = {
        "t": [], "sim_time": [], "host_time": [], "gap_sq": [],
        "n_cuts_i": [], "n_cuts_ii": [], "max_staleness": []}
    # afto_step increments t by exactly 1, so the absolute count is host
    # arithmetic — no per-iteration device sync for the refresh predicate
    t0_abs = int(state.t)
    t_start = time.perf_counter()

    for it in range(n_iterations):
        if schedule is not None:
            mask, sim_t = schedule.active[it], float(schedule.sim_time[it])
        else:
            mask, sim_t = sched.next_active()
        # same iteration's batch for step / refresh / gap, each worker
        # row keyed on its pre-step consumption time state.stale.t_hat —
        # exactly what the streamed scan body does
        batch = None if stream is None else \
            stream_lib.next_batch(stream, state.stale.t_hat)
        state = step(state, jnp.asarray(mask), batch)
        # refresh on the absolute post-step count (== it + 1 for fresh
        # runs), matching the engine — continued states refresh where
        # the unchunked trajectory would
        t_post = t0_abs + it + 1
        if t_post % hyper.t_pre == 0 and t_post - 1 < hyper.t1:
            state = refresh(state, batch)

        if (it + 1) % metrics_every == 0 or it == n_iterations - 1:
            hist["t"].append(it + 1)
            hist["sim_time"].append(float(sim_t))
            hist["host_time"].append(time.perf_counter() - t_start)
            hist["gap_sq"].append(float(gap(state, batch)))
            hist["n_cuts_i"].append(float(jnp.sum(state.cuts_i.active)))
            hist["n_cuts_ii"].append(float(jnp.sum(state.cuts_ii.active)))
            hist["max_staleness"].append(float(
                schedule.max_staleness[it] if schedule is not None
                else sched.max_staleness()))
            if metrics_fn is not None:
                for k, v in metrics_fn(state).items():
                    hist.setdefault(k, []).append(float(v))

    return RunResult(state=state, history=hist)
