"""Compiled trajectory engine: whole AFTO runs (and sweeps) in one
`lax.scan` dispatch.

The straggler scheduler is a seeded host-side simulation with no feedback
from the optimization state, so its entire arrival process can be
materialized up front (`StragglerScheduler.precompute`) and the
T-iteration trajectory of Alg. 1 driven inside a single donated-buffer
`jax.lax.scan`:

  * `afto_step` every master iteration (Eqs. 16-21),
  * `cut_refresh` via `lax.cond` on every t_pre-th iteration with
    t < t1 (Eqs. 23-25),
  * gap / cut-count / user metrics accumulated into preallocated
    history arrays at `metrics_every` strides (under `lax.cond`, and the
    stationarity gap is *fused* with the step: it reuses the step's
    canonical cut operator and cut values instead of recomputing them —
    see `afto_step_aux` / `stationarity_gap_sq(aux=...)`).

The scan carry holds each polytope as canonical `FlatCuts` — two dense
(P, D)/(P,) array groups instead of ~10 stacked block trees — so the
carry is small, `cut_refresh` writes rows in place, and the dense
matrix shards by worker columns (a tree of stacked blocks does not).

`run_scanned` drives one trajectory; `run_swept` vmaps the same scan
body over a leading run axis R (stacked initial states, stacked schedule
masks, per-run data and sweepable hyper scalars) so a whole benchmark
sweep — every (seed, method) cell — is ONE donated XLA dispatch
returning (R,)-leading states and histories.  A vmapped `lax.cond` on a
per-run predicate would run both branches every iteration, so the sweep
gates the refresh by a cond over the run axis instead: it runs at the
iterations where any run refreshes, and each run keeps its own result.

Both accept `mesh=` (a `jax.sharding.Mesh` with a "worker" axis) and
then run shard_map-distributed: worker-stacked state, per-worker data,
schedule-mask columns and the polytope b-columns partition over the
axis while master state replicates, and the only cross-shard traffic is
the cut-scalar / z-sized psums of the paper's cut exchange (the refresh
math lives in `repro.core.sharded`; partitioning rules in
`repro.fed.sharding.afto_state_specs`).  Sharded trajectories match the
replicated engines to f32 tolerance (`tests/test_sharded_engine.py`).

Both engines also accept `data=`: replacement `problem.data` arrays
(traced, not closed over — the compiled trajectory is reused across
datasets of one layout), or a `repro.data.stream.Stream`, in which case
every iteration's worker batches are SYNTHESIZED INSIDE the scan body
from fold-in PRNG keys (`stream.batch_at(spec, key, state.stale.t_hat,
...)`).  The stream's base key rides the donated carry untouched and
each worker's row folds on its absolute consumption time (the carried
pre-step `state.stale.t_hat`), so any chunk partition of a trajectory
(state-continued `run_scanned` calls) sees the bit-identical batch
sequence, and the worker-mesh engines draw each shard's own global
worker rows locally — streaming adds NO data collectives
(`tests/test_stream.py` is the conformance harness).

`metrics_fn` must be JAX-traceable here (it is traced into the scan
body); host-callback metrics still work through the eager path of
`repro.core.runner.run(mode="eager")`.

Compiled trajectories are cached per (problem, hyper, metrics_fn,
schedule length, record layout), so repeated runs — e.g. the AFTO/SFTO
sweeps in the benchmarks — pay tracing + compilation once.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import afto as afto_lib
from repro.core import cuts as cuts_lib
from repro.core import sharded as sharded_lib
from repro.core import stationarity as stat_lib
from repro.core.scheduler import Schedule
from repro.core.types import AFTOState, Hyper, TrilevelProblem
from repro.data import stream as stream_lib
from repro.data.stream import Stream


@dataclasses.dataclass
class RunResult:
    state: AFTOState
    history: Dict
    # the LIVE arrival process recorded by the async runtime
    # (`repro.fed.runtime`), as a replayable `Schedule`; None for the
    # scheduled engines, whose arrival order was an input
    arrivals: Any = None


@dataclasses.dataclass
class SweepResult:
    """R trajectories from one dispatch: every state leaf and per-run
    history array carries a leading (R,) axis ("t" is shared)."""
    state: AFTOState
    history: Dict

    @property
    def n_runs(self) -> int:
        return int(jax.tree.leaves(self.state)[0].shape[0])

    def run(self, r: int) -> RunResult:
        """Row r as a RunResult with the single-run history layout."""
        state_r = jax.tree.map(lambda x: x[r], self.state)
        hist_r = {k: (v[r] if getattr(v, "ndim", 1) == 2 else v)
                  for k, v in self.history.items()}
        return RunResult(state=state_r, history=hist_r)


def record_slots(n_iterations: int,
                 metrics_every: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side record layout matching the eager runner.

    Returns (record_its, slots): `record_its` are the iterations whose
    metrics are recorded — every `metrics_every`-th plus the final one —
    and `slots[it]` is the history-array row for iteration `it` (-1 when
    iteration `it` records nothing).
    """
    record_its = np.array(
        [it for it in range(n_iterations)
         if (it + 1) % metrics_every == 0 or it == n_iterations - 1],
        dtype=np.int64)
    slots = np.full((n_iterations,), -1, np.int32)
    slots[record_its] = np.arange(len(record_its), dtype=np.int32)
    return record_its, slots


def _hyper_key(hyper: Hyper) -> tuple:
    return tuple(sorted(
        (f.name, getattr(hyper, f.name))
        for f in dataclasses.fields(hyper)))


# Compiled-trajectory caches.  Keyed on object identity for problem /
# metrics_fn (both are kept alive by the cache entry itself, so ids
# cannot be recycled while a key references them) and structurally on
# the hyper scalars and record layout.
_CACHE: Dict[tuple, tuple] = {}
_SWEEP_CACHE: Dict[tuple, tuple] = {}
_CACHE_MAX = 16


def _cached_build(cache: Dict[tuple, tuple], key: tuple, build,
                  keep_alive: tuple):
    """Fetch the compiled trajectory for `key`, building on miss; the
    `keep_alive` refs ride in the entry so the ids in `key` cannot be
    recycled while the entry lives.  Re-inserting on hit keeps the dict
    in LRU order for the size-capped eviction."""
    hit = cache.pop(key, None)
    if hit is None:
        hit = (build(),) + keep_alive
        while len(cache) >= _CACHE_MAX:
            cache.pop(next(iter(cache)))
    cache[key] = hit
    return hit[0]

# How many times each build function traced a new scan/sweep (a cache
# miss; in a profiler trace, the long `afto.build` span) — the
# retrace regression tests assert this stays flat across warm calls
# (the *_sharded counters cover the worker-mesh shard_map paths, the
# *_streamed ones the in-scan data-stream paths: a stream's key is
# traced, so re-seeding must never rebuild).
BUILD_COUNTS = {"scan": 0, "sweep": 0, "scan_sharded": 0,
                "sweep_sharded": 0, "scan_streamed": 0,
                "sweep_streamed": 0, "scan_sharded_streamed": 0,
                "sweep_sharded_streamed": 0}


def _data_key(data):
    """Structural cache-key component for the `data=` argument: streams
    key on their static spec (the traced key never retraces), host
    arrays on their layout."""
    if data is None:
        return None
    if isinstance(data, Stream):
        return ("stream", data.spec)
    leaves, tdef = jax.tree_util.tree_flatten(data)
    return ("host", tdef,
            tuple((tuple(map(int, l.shape)), str(l.dtype))
                  for l in leaves))


def _check_stream(stream: Stream, hyper: Hyper) -> None:
    if stream.spec is None:
        raise ValueError("Stream has no spec; build with "
                         "repro.data.stream.make_stream")
    if stream.spec.n_workers != hyper.n_workers:
        raise ValueError(
            f"stream spans {stream.spec.n_workers} workers but "
            f"hyper.n_workers={hyper.n_workers}")

# Hyper fields that determine array shapes or unrolled loop lengths;
# they must be Python constants at trace time and cannot be swept.
_STATIC_HYPER_FIELDS = frozenset({"n_workers", "p_max", "k_inner", "d1"})


# vmap axis name of the sweeps' run axis
_RUN_AXIS = "run"


def _run_gated_cond(pred, run_axis: Optional[str], true_fn, false_fn,
                    *operands):
    """`lax.cond(pred, true_fn, false_fn, *operands)` for a per-run `pred`.

    Under `jax.vmap` a batched predicate turns `lax.cond` into a select
    that executes both branches for every run.  With `run_axis` (the
    vmap axis name) the branch is instead gated on whether ANY run takes
    it — a pmax over the run axis, unbatched, so a real cond — and
    inside, each run keeps its own branch's result by a `where`.  Runs
    out of phase then pay the branch at the union of their iterations.
    """
    if run_axis is None:
        return jax.lax.cond(pred, true_fn, false_fn, *operands)
    any_pred = jax.lax.pmax(pred.astype(jnp.int32), run_axis) > 0

    def gated(*ops):
        return jax.tree.map(lambda a, b: jnp.where(pred, a, b),
                            true_fn(*ops), false_fn(*ops))

    return jax.lax.cond(any_pred, gated, false_fn, *operands)


def _make_step_body(problem: TrilevelProblem, hyper: Hyper,
                    metrics_fn: Optional[Callable], keys,
                    axis: Optional[str] = None,
                    stream_spec=None, n_shards: Optional[int] = None,
                    run_axis: Optional[str] = None):
    """The per-iteration scan body shared by run_scanned and run_swept.

    axis: worker mesh axis when tracing inside the shard_map'd engines —
    `problem`/state/mask then carry this shard's workers only and the
    refresh dispatches to the sharded cut generation.

    run_axis: the sweeps' vmap axis over runs; the refresh and the gap
    record's refreshed cut operator are then gated by `_run_gated_cond`.
    None (the scan engines) traces plain `lax.cond`s.

    stream_spec: when set, the carry grows a (constant) stream key and
    each iteration's `problem.data` is synthesized in-scan from fold-in
    keys on each worker's absolute consumption time (the pre-step
    `state.stale.t_hat` — worker j's row is folded at the iteration its
    current local point was handed out, which is what a self-paced
    async worker can reproduce from its REFRESH frame alone).  Still
    chunk-partition invariant (t_hat rides the carry), and on a mesh
    each shard draws only its own global worker rows (t_hat is
    worker-stacked, so the shard's slice arrives with the state;
    `axis_index * n_local` offset), so streaming adds no collectives.

    The refresh predicate also runs on `state.t` (identical to the old
    xs-iteration form for fresh starts), so state-continued chunked
    dispatches refresh exactly where the unchunked trajectory does."""
    if stream_spec is not None:
        n_local = (stream_spec.n_workers if axis is None
                   else stream_spec.n_workers // n_shards)

    def step_body(carry, xs):
        mask, slot = xs
        if stream_spec is None:
            st, hist = carry
            prob = problem
        else:
            st, hist, key = carry
            off = 0 if axis is None else jax.lax.axis_index(axis) * n_local
            prob = dataclasses.replace(
                problem,
                data=stream_lib.batch_at(stream_spec, key,
                                         st.stale.t_hat, off, n_local))
        st, step_aux = afto_lib.afto_step_aux(prob, hyper, st, mask,
                                              axis=axis)
        # post-step st.t is the 1-based master iteration count
        do_refresh = (st.t % hyper.t_pre == 0) & (st.t - 1 < hyper.t1)
        refresh = (
            (lambda s: afto_lib.cut_refresh(prob, hyper, s))
            if axis is None else
            (lambda s: sharded_lib.cut_refresh_sharded(prob, hyper, s,
                                                       axis)))
        st = _run_gated_cond(do_refresh, run_axis, refresh, lambda s: s,
                             st)

        @jax.named_scope("gap_record")
        def write(h):
            # the gap reuses the step's flat cut operator + cut values;
            # a refresh rewrote the polytope, so recompute them there.
            aux = _run_gated_cond(
                do_refresh, run_axis,
                lambda s, _a: stat_lib.make_gap_aux(prob, hyper, s,
                                                    axis=axis),
                lambda _s, a: a, st, step_aux)
            vals = {
                "gap_sq": stat_lib.stationarity_gap_sq(
                    prob, hyper, st, aux=aux, axis=axis),
                "n_cuts_i": jnp.sum(st.cuts_i.active),
                "n_cuts_ii": jnp.sum(st.cuts_ii.active),
            }
            if metrics_fn is not None:
                vals.update(metrics_fn(st))
            return {k: h[k].at[slot].set(
                jnp.asarray(vals[k], jnp.float32)) for k in keys}

        hist = jax.lax.cond(slot >= 0, write, lambda h: h, hist)
        return ((st, hist) if stream_spec is None
                else (st, hist, key)), None

    return step_body


def _build_scan(problem: TrilevelProblem, hyper: Hyper,
                metrics_fn: Optional[Callable], keys, donate: bool,
                stream_spec=None):
    BUILD_COUNTS["scan_streamed" if stream_spec else "scan"] += 1

    def scan_all(st, hist, data, key, masks, slots):
        prob = problem if data is None else \
            dataclasses.replace(problem, data=data)
        step_body = _make_step_body(prob, hyper, metrics_fn, keys,
                                    stream_spec=stream_spec)
        carry = (st, hist) if stream_spec is None else (st, hist, key)
        carry, _ = jax.lax.scan(step_body, carry, (masks, slots))
        return carry[0], carry[1]

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(scan_all, donate_argnums=donate_argnums)


def _metric_keys(problem, hyper, metrics_fn, state):
    keys = ["gap_sq", "n_cuts_i", "n_cuts_ii"]
    if metrics_fn is not None:
        extra = jax.eval_shape(metrics_fn, state)
        keys += [k for k in extra if k not in keys]
    return tuple(keys)


# ---------------------------------------------------------------------------
# worker-mesh sharded dispatch (shard_map over the cut-exchange axis)
# ---------------------------------------------------------------------------

def _worker_axis_size(mesh) -> int:
    shape = dict(mesh.shape)
    if sharded_lib.WORKER_AXIS not in shape:
        raise ValueError(
            f"mesh must carry a {sharded_lib.WORKER_AXIS!r} axis; got "
            f"axes {tuple(shape)} (see repro.launch.mesh.make_worker_mesh)")
    return shape[sharded_lib.WORKER_AXIS]


def _check_mesh(mesh, hyper: Hyper) -> int:
    w = _worker_axis_size(mesh)
    if hyper.n_workers % w != 0:
        raise ValueError(
            f"n_workers={hyper.n_workers} must divide over the "
            f"{w}-shard worker mesh")
    return w


def _shard_state(state: AFTOState, n_shards: int) -> AFTOState:
    """Host-side sharded view: polytopes become the stacked-local column
    groups of `cuts.shard_cuts`; every other leaf keeps its global shape
    (the shard_map in_specs split the worker-stacked axes)."""
    return dataclasses.replace(
        state,
        cuts_i=cuts_lib.shard_cuts(state.cuts_i, n_shards),
        cuts_ii=cuts_lib.shard_cuts(state.cuts_ii, n_shards))


def _unshard_state(state: AFTOState, spec_i, spec_ii) -> AFTOState:
    return dataclasses.replace(
        state,
        cuts_i=cuts_lib.unshard_cuts(state.cuts_i, spec_i),
        cuts_ii=cuts_lib.unshard_cuts(state.cuts_ii, spec_ii))


def _map_cuts(state: AFTOState, fn) -> AFTOState:
    return dataclasses.replace(
        state,
        cuts_i=dataclasses.replace(state.cuts_i, a=fn(state.cuts_i.a)),
        cuts_ii=dataclasses.replace(state.cuts_ii, a=fn(state.cuts_ii.a)))


def _state_specs(state_sharded, lead=()):
    from repro.fed import sharding as shd
    return shd.afto_state_specs(state_sharded,
                                axis=sharded_lib.WORKER_AXIS, lead=lead)


def _build_scan_sharded(problem: TrilevelProblem, hyper: Hyper,
                        metrics_fn: Optional[Callable], keys,
                        donate: bool, mesh, state_specs,
                        stream_spec=None, n_shards: Optional[int] = None):
    from jax.sharding import PartitionSpec as P

    BUILD_COUNTS["scan_sharded_streamed" if stream_spec
                 else "scan_sharded"] += 1
    axis = sharded_lib.WORKER_AXIS

    def scan_all(st, hist, data, key, masks, slots):
        # drop the shard_map-local leading worker axis of the cut blocks
        st = _map_cuts(st, lambda a: a[0])
        prob = problem if data is None else \
            dataclasses.replace(problem, data=data)
        step_body = _make_step_body(prob, hyper, metrics_fn, keys,
                                    axis=axis, stream_spec=stream_spec,
                                    n_shards=n_shards)
        carry = (st, hist) if stream_spec is None else (st, hist, key)
        carry, _ = jax.lax.scan(step_body, carry, (masks, slots))
        st, hist = carry[0], carry[1]
        return _map_cuts(st, lambda a: a[None]), hist

    hist_specs = {k: P() for k in keys}
    from repro.fed import sharding as shd
    # streamed shards draw their own rows in-scan: no data input at all,
    # and the (replicated) base key is the only stream state.
    data_specs = None if stream_spec is not None else \
        shd.worker_data_specs(problem.data, axis=axis)
    key_spec = None if stream_spec is None else P()
    fn = jax.shard_map(
        scan_all, mesh=mesh,
        in_specs=(state_specs, hist_specs, data_specs, key_spec,
                  P(None, axis), P()),
        out_specs=(state_specs, hist_specs),
        check_vma=False)
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(fn, donate_argnums=donate_argnums)


def _stitch_histories(parts, offsets, elapsed_offsets) -> Dict:
    """Concatenate per-chunk histories into one absolute-iteration
    record: "t" shifts by each chunk's start, host_time accumulates the
    wall-clock spent before the chunk."""
    out: Dict = {}
    for k in parts[0]:
        segs = []
        for h, off, el in zip(parts, offsets, elapsed_offsets):
            v = np.asarray(h[k])
            if k == "t":
                v = v + off
            elif k == "host_time":
                v = v + el
            segs.append(v)
        out[k] = np.concatenate(segs)
    return out


def run_chunked(problem: TrilevelProblem, hyper: Hyper, schedule: Schedule,
                chunk_size: int,
                chunk_hook: Optional[Callable] = None,
                metrics_fn: Optional[Callable] = None,
                metrics_every: int = 10,
                state: Optional[AFTOState] = None,
                mesh=None, data=None) -> RunResult:
    """`run_scanned` split into state-continued `chunk_size`-iteration
    dispatches, with `chunk_hook(state, t_abs)` called on the LIVE carry
    at every chunk boundary (including the final one).

    The hook sees the post-chunk state and may return a replacement
    state (or None to keep it) — the push/pull seam the async runtime
    and the elastic-checkpoint path hang off: push = read the carry out
    (checkpoint it, ship cut rows to a master), pull = splice refreshed
    master state back in before the next dispatch.  Chunking is exact
    for fresh starts by the continuation contract (the refresh predicate
    and the streamed batches key on carried absolute counters —
    `state.t` and the per-worker `state.stale.t_hat`), so
    a hook that returns None reproduces the unchunked trajectory
    bit-for-bit; warm equal-size chunks reuse one compiled trace.

    History records per chunk (every `metrics_every`-th iteration plus
    each chunk's final one), stitched to absolute iterations.
    """
    n_iterations = schedule.n_iterations
    chunk_size = max(1, int(chunk_size))
    parts, offsets, elapsed = [], [], []
    spent = 0.0
    for a in range(0, n_iterations, chunk_size):
        b = min(a + chunk_size, n_iterations)
        res = run_scanned(problem, hyper, schedule.slice(a, b),
                          metrics_fn=metrics_fn,
                          metrics_every=metrics_every, state=state,
                          mesh=mesh, data=data)
        state = res.state
        parts.append(res.history)
        offsets.append(a)
        elapsed.append(spent)
        spent += float(res.history["host_time"][-1])
        if chunk_hook is not None:
            replacement = chunk_hook(state, b)
            if replacement is not None:
                state = replacement
    return RunResult(state=state,
                     history=_stitch_histories(parts, offsets, elapsed))


def run_scanned(problem: TrilevelProblem, hyper: Hyper, schedule: Schedule,
                metrics_fn: Optional[Callable] = None,
                metrics_every: int = 10,
                state: Optional[AFTOState] = None,
                mesh=None, data=None) -> RunResult:
    """Run the full AFTO trajectory over `schedule` in one compiled scan.

    Produces the same history layout as the eager runner: arrays
    (instead of Python lists) keyed by t / sim_time / host_time /
    gap_sq / n_cuts_i / n_cuts_ii / max_staleness plus any `metrics_fn`
    keys.  `host_time` is prorated from the single dispatch's total —
    per-iteration host timestamps do not exist inside a compiled
    trajectory.

    mesh: a `jax.sharding.Mesh` with a "worker" axis distributes the
    federation via shard_map — worker-stacked state, schedule-mask
    columns, per-worker data and the polytope b-columns partition over
    the axis; only cut scalars / z-sized reductions cross it (see
    `repro.core.sharded`).  `hyper.n_workers` must be divisible by the
    axis size; results match the single-device scan to f32 tolerance
    (the returned state is reassembled to the canonical global layout).
    `metrics_fn` is traced on the shard-local state view — metrics over
    master variables (z's, lam, cut masks) are exact and replicated;
    a metric that reads the worker stacks computes a PER-SHARD partial
    value, and the history records whichever shard's buffer backs the
    replicated-out layout (shard 0 in practice — the engine cannot
    know how to reduce an arbitrary user metric).  psum inside your
    metrics_fn over `repro.core.sharded.WORKER_AXIS` if you need the
    global value.

    data: replacement `problem.data` arrays (traced — the compiled
    trajectory is shared across datasets of one layout), or a
    `repro.data.stream.Stream` whose per-iteration worker batches are
    synthesized INSIDE the scan from fold-in keys on the absolute
    `state.t` (chunk-partition invariant; on a mesh each shard draws
    its own global worker rows with no data collectives).  Re-seeding a
    stream (`dataclasses.replace(stream, key=...)`) never retraces.
    """
    n_iterations = schedule.n_iterations
    n_shards = None if mesh is None else _check_mesh(mesh, hyper)
    stream = data if isinstance(data, Stream) else None
    if stream is not None:
        _check_stream(stream, hyper)
    stream_spec = None if stream is None else stream.spec
    donate = state is None
    with TraceAnnotation("afto.init_state"):
        if state is None:
            # init_state aliases some buffers across fields (e.g. z3 and
            # inner3.z3); donation requires distinct buffers, so copy once.
            state = jax.tree.map(jnp.array,
                                 afto_lib.init_state(problem, hyper))

    with TraceAnnotation("afto.build"):
        keys = _metric_keys(problem, hyper, metrics_fn, state)
        cache_key = (id(problem), id(metrics_fn), _hyper_key(hyper),
                     n_iterations, metrics_every, donate, mesh,
                     _data_key(data))
        if mesh is None:
            fn = _cached_build(
                _CACHE, cache_key,
                lambda: _build_scan(problem, hyper, metrics_fn, keys,
                                    donate, stream_spec=stream_spec),
                (problem, metrics_fn, stream_spec))
        else:
            spec_i, spec_ii = state.cuts_i.spec, state.cuts_ii.spec
            state = _shard_state(state, n_shards)
            fn = _cached_build(
                _CACHE, cache_key,
                lambda: _build_scan_sharded(problem, hyper, metrics_fn,
                                            keys, donate, mesh,
                                            _state_specs(state),
                                            stream_spec=stream_spec,
                                            n_shards=n_shards),
                (problem, metrics_fn, mesh, stream_spec))

    with TraceAnnotation("afto.stage"):
        record_its, slots = record_slots(n_iterations, metrics_every)
        hist0 = {k: jnp.zeros((len(record_its),), jnp.float32)
                 for k in keys}
        masks = jnp.asarray(schedule.active, jnp.float32)
        slots = jnp.asarray(slots)
        key = None if stream is None else jnp.asarray(stream.key)
        if stream is not None:
            data_arg = None
        elif data is not None:
            data_arg = jax.tree.map(jnp.asarray, data)
        else:
            data_arg = None if mesh is None else \
                jax.tree.map(jnp.asarray, problem.data)

    t_start = time.perf_counter()
    with TraceAnnotation("afto.dispatch"):
        state, hist = fn(state, hist0, data_arg, key, masks, slots)
        if mesh is not None:
            state = _unshard_state(state, spec_i, spec_ii)
    with TraceAnnotation("afto.wait"):
        jax.block_until_ready(state)
    elapsed = time.perf_counter() - t_start

    with TraceAnnotation("afto.fetch"):
        history = {k: np.asarray(v) for k, v in hist.items()}
        history["t"] = (record_its + 1).astype(np.float64)
        history["sim_time"] = np.asarray(schedule.sim_time)[record_its]
        history["max_staleness"] = np.asarray(
            schedule.max_staleness)[record_its].astype(np.float64)
        history["host_time"] = elapsed * (record_its + 1) / n_iterations
    return RunResult(state=state, history=history)


# ---------------------------------------------------------------------------
# batched sweeps: R trajectories in one vmapped dispatch
# ---------------------------------------------------------------------------

def _build_sweep(problem: TrilevelProblem, hyper: Hyper,
                 metrics_fn: Optional[Callable], keys,
                 sweep_names: tuple, has_data: bool, init_inside: bool,
                 stream_spec=None):
    BUILD_COUNTS["sweep_streamed" if stream_spec else "sweep"] += 1

    def one_run(st, hist, masks, sweep_vals, data, key, slots):
        prob = problem if data is None else \
            dataclasses.replace(problem, data=data)
        hyp = dataclasses.replace(
            hyper, **dict(zip(sweep_names, sweep_vals))) \
            if sweep_names else hyper
        step_body = _make_step_body(prob, hyp, metrics_fn, keys,
                                    stream_spec=stream_spec,
                                    run_axis=_RUN_AXIS)
        carry = (st, hist) if stream_spec is None else (st, hist, key)
        carry, _ = jax.lax.scan(step_body, carry, (masks, slots))
        return carry[0], carry[1]

    def vmapped(st, hist, masks, sweep_vals, data, key, slots):
        # one stream is SHARED by all runs (same data per row, parity
        # with run_scanned); per-run variation comes from the schedules
        return jax.vmap(
            one_run,
            in_axes=(0, 0, 0, 0, 0 if has_data else None, None, None),
            axis_name=_RUN_AXIS)(
                st, hist, masks, sweep_vals, data, key, slots)

    if not init_inside:
        return jax.jit(vmapped, donate_argnums=(0, 1))

    # default-init sweeps build the stacked initial state inside the
    # compiled dispatch (masks carries R statically) — the ~60 tiny
    # init_state + tile host dispatches otherwise dominate the whole
    # warm sweep at quickstart scale.
    def sweep_all(hist, masks, sweep_vals, data, key, slots):
        st0 = afto_lib.init_state(problem, hyper)
        st = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None], masks.shape[:1] + x.shape).astype(x.dtype), st0)
        return vmapped(st, hist, masks, sweep_vals, data, key, slots)

    return jax.jit(sweep_all, donate_argnums=(0,))


def _build_sweep_sharded(problem: TrilevelProblem, hyper: Hyper,
                         metrics_fn: Optional[Callable], keys,
                         sweep_names: tuple, has_data: bool, mesh,
                         state_specs, stream_spec=None,
                         n_shards: Optional[int] = None):
    from jax.sharding import PartitionSpec as P

    BUILD_COUNTS["sweep_sharded_streamed" if stream_spec
                 else "sweep_sharded"] += 1
    axis = sharded_lib.WORKER_AXIS

    def one_run(st, hist, masks, sweep_vals, data, key, slots):
        prob = problem if data is None else \
            dataclasses.replace(problem, data=data)
        hyp = dataclasses.replace(
            hyper, **dict(zip(sweep_names, sweep_vals))) \
            if sweep_names else hyper
        step_body = _make_step_body(prob, hyp, metrics_fn, keys,
                                    axis=axis, stream_spec=stream_spec,
                                    n_shards=n_shards, run_axis=_RUN_AXIS)
        carry = (st, hist) if stream_spec is None else (st, hist, key)
        carry, _ = jax.lax.scan(step_body, carry, (masks, slots))
        return carry[0], carry[1]

    def sweep_all(st, hist, data, key, masks, sweep_vals, slots):
        # (R, 1, P, D_loc) cut blocks -> (R, P, D_loc) inside the shard
        st = _map_cuts(st, lambda a: a[:, 0])
        # the run axis is local to the shard and st.t is replicated over
        # workers, so every shard opens the refresh gate together
        st, hist = jax.vmap(
            one_run,
            in_axes=(0, 0, 0, 0, 0 if has_data else None, None, None),
            axis_name=_RUN_AXIS)(
                st, hist, masks, sweep_vals, data, key, slots)
        return _map_cuts(st, lambda a: a[:, None]), hist

    hist_specs = {k: P() for k in keys}
    from repro.fed import sharding as shd
    data_lead = (None,) if has_data else ()
    data_specs = None if stream_spec is not None else \
        shd.worker_data_specs(problem.data, axis=axis, lead=data_lead)
    key_spec = None if stream_spec is None else P()
    sweep_specs = tuple(P() for _ in sweep_names)
    fn = jax.shard_map(
        sweep_all, mesh=mesh,
        in_specs=(state_specs, hist_specs, data_specs, key_spec,
                  P(None, None, axis), sweep_specs, P()),
        out_specs=(state_specs, hist_specs),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1))


def run_swept(problem: TrilevelProblem, hyper: Hyper,
              schedules: Sequence[Schedule],
              metrics_fn: Optional[Callable] = None,
              metrics_every: int = 10,
              states: Optional[AFTOState] = None,
              data=None,
              sweep_hypers: Optional[Dict] = None,
              mesh=None) -> SweepResult:
    """Run R = len(schedules) whole trajectories in ONE vmapped dispatch.

    The scan body of `run_scanned` is `jax.vmap`'d over a leading run
    axis: stacked initial states, stacked schedule masks, per-run data
    slices and per-run hyper scalars; the iteration/slot streams are
    shared.  All schedules must have the same length and worker count.

      states       optional stacked AFTOState ((R,)-leading leaves, e.g.
                   per-seed inits via utils.tree.tree_stack); defaults to
                   R copies of `init_state`.  Copied internally — the
                   dispatch donates its own buffers, never the caller's.
      data         optional replacement for `problem.data` with a
                   leading (R,) axis per leaf (per-seed datasets), OR a
                   `repro.data.stream.Stream` — then every run's batches
                   are synthesized in-scan from the SHARED stream (each
                   row sees the data a `run_scanned(data=stream)` of its
                   schedule would; per-run variation comes from the
                   schedules/hypers, and re-seeding the stream never
                   retraces).
      sweep_hypers dict of Hyper field name -> (R,) values, threaded
                   into the traced step per run.  Shape-determining
                   fields (n_workers/p_max/k_inner/d1) stay static and
                   cannot be swept.  t_pre/t1 may be swept.

    The refresh runs at the iterations where any run refreshes: its
    predicate is per-run (it reads each run's `state.t`, and swept
    t_pre/t1), so it is gated by a `lax.cond` on a max over the run axis
    and each run keeps its own result by a `where`.  Runs in phase
    refresh every t_pre-th iteration, as one scanned run does; runs out
    of phase (stacked `states` at different `t`, swept t_pre/t1) pay the
    refresh at the union of their refresh iterations.

    History layout: per-run keys (gap_sq, n_cuts_*, sim_time,
    max_staleness, host_time, metrics_fn keys) are (R, n_records)
    arrays; "t" is shared (n_records,).  `host_time` is an
    elapsed/R-proration: the single dispatch interleaves all R
    trajectories, so per-run host seconds do not exist — each run is
    charged an equal 1/R share of the dispatch wall-clock, prorated
    over iterations exactly like the single-run engine.

    mesh: worker mesh as in `run_scanned` — the run axis is vmapped
    INSIDE the shard_map body, so the R trajectories still dispatch
    once while the federation partitions over the "worker" axis.  The
    sharded sweep always materializes the stacked initial states on the
    host (the fused in-dispatch default-init is a replicated-engine
    optimization).
    """
    schedules = list(schedules)
    if not schedules:
        raise ValueError("run_swept needs at least one schedule")
    n_runs = len(schedules)
    n_iterations = schedules[0].n_iterations
    for s in schedules[1:]:
        if (s.n_iterations, s.n_workers) != (n_iterations,
                                             schedules[0].n_workers):
            raise ValueError(
                "all swept schedules must share n_iterations/n_workers")

    sweep_hypers = dict(sweep_hypers or {})
    field_names = {f.name for f in dataclasses.fields(Hyper)}
    for name in sweep_hypers:
        if name not in field_names:
            raise ValueError(f"unknown hyper field {name!r}")
        if name in _STATIC_HYPER_FIELDS:
            raise ValueError(
                f"hyper field {name!r} is shape-determining and cannot "
                "be swept; run separate sweeps instead")
    sweep_names = tuple(sorted(sweep_hypers))
    for name in sweep_names:
        shape = np.shape(sweep_hypers[name])
        if shape != (n_runs,):
            raise ValueError(
                f"sweep_hypers[{name!r}] must have shape ({n_runs},), "
                f"got {shape}")

    n_shards = None if mesh is None else _check_mesh(mesh, hyper)
    dkey = _data_key(data)
    stream = data if isinstance(data, Stream) else None
    stream_spec = None
    if stream is not None:
        _check_stream(stream, hyper)
        stream_spec = stream.spec
        data = None
    if data is not None:
        for leaf in jax.tree.leaves(data):
            if np.shape(leaf)[:1] != (n_runs,):
                raise ValueError(
                    "swept data leaves need a leading (R,) axis")
    with TraceAnnotation("afto.init_state"):
        if mesh is not None and states is None:
            st0 = afto_lib.init_state(problem, hyper)
            states = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (n_runs,) + x.shape).astype(x.dtype), st0)
        init_inside = states is None
        if not init_inside:
            # private copy: the swept dispatch donates its inputs
            states = jax.tree.map(jnp.array, states)

    with TraceAnnotation("afto.build"):
        if metrics_fn is None:
            state_one = None       # _metric_keys won't trace anything
        elif init_inside:
            state_one = jax.eval_shape(
                lambda: afto_lib.init_state(problem, hyper))
        else:
            state_one = jax.tree.map(lambda x: x[0], states)
        keys = _metric_keys(problem, hyper, metrics_fn, state_one)

        cache_key = (id(problem), id(metrics_fn), _hyper_key(hyper),
                     sweep_names, dkey, init_inside, n_runs,
                     n_iterations, metrics_every, mesh)
        if mesh is not None:
            spec_i = states.cuts_i.spec
            spec_ii = states.cuts_ii.spec
            states = dataclasses.replace(
                states,
                cuts_i=jax.vmap(
                    lambda fc: cuts_lib.shard_cuts(fc, n_shards))(
                        states.cuts_i),
                cuts_ii=jax.vmap(
                    lambda fc: cuts_lib.shard_cuts(fc, n_shards))(
                        states.cuts_ii))
            fn = _cached_build(
                _SWEEP_CACHE, cache_key,
                lambda: _build_sweep_sharded(
                    problem, hyper, metrics_fn, keys, sweep_names,
                    data is not None, mesh,
                    _state_specs(states, lead=(None,)),
                    stream_spec=stream_spec, n_shards=n_shards),
                (problem, metrics_fn, mesh, stream_spec))
        else:
            fn = _cached_build(
                _SWEEP_CACHE, cache_key,
                lambda: _build_sweep(problem, hyper, metrics_fn, keys,
                                     sweep_names, data is not None,
                                     init_inside, stream_spec=stream_spec),
                (problem, metrics_fn, stream_spec))

    with TraceAnnotation("afto.stage"):
        sweep_vals = tuple(jnp.asarray(sweep_hypers[k])
                           for k in sweep_names)
        if data is not None:
            data = jax.tree.map(jnp.asarray, data)
        record_its, slots = record_slots(n_iterations, metrics_every)
        hist0 = {k: jnp.zeros((n_runs, len(record_its)), jnp.float32)
                 for k in keys}
        masks = jnp.asarray(
            np.stack([s.active for s in schedules]), jnp.float32)
        slots = jnp.asarray(slots)
        key = None if stream is None else jnp.asarray(stream.key)
        if mesh is not None and stream is None and data is None:
            data = jax.tree.map(jnp.asarray, problem.data)

    t_start = time.perf_counter()
    with TraceAnnotation("afto.dispatch"):
        if mesh is not None:
            state, hist = fn(states, hist0, data, key, masks, sweep_vals,
                             slots)
            state = dataclasses.replace(
                state,
                cuts_i=jax.vmap(
                    lambda fc: cuts_lib.unshard_cuts(fc, spec_i))(
                        state.cuts_i),
                cuts_ii=jax.vmap(
                    lambda fc: cuts_lib.unshard_cuts(fc, spec_ii))(
                        state.cuts_ii))
        elif init_inside:
            state, hist = fn(hist0, masks, sweep_vals, data, key, slots)
        else:
            state, hist = fn(states, hist0, masks, sweep_vals, data, key,
                             slots)
    with TraceAnnotation("afto.wait"):
        jax.block_until_ready(state)
    elapsed = time.perf_counter() - t_start

    with TraceAnnotation("afto.fetch"):
        history = {k: np.asarray(v) for k, v in hist.items()}
        history["t"] = (record_its + 1).astype(np.float64)
        history["sim_time"] = np.stack(
            [np.asarray(s.sim_time)[record_its] for s in schedules])
        history["max_staleness"] = np.stack(
            [np.asarray(s.max_staleness)[record_its].astype(np.float64)
             for s in schedules])
        # one dispatch covers R trajectories: charge each run elapsed/R
        # (an approximation — the runs execute interleaved, not serially).
        history["host_time"] = np.broadcast_to(
            (elapsed / n_runs) * (record_its + 1) / n_iterations,
            (n_runs, len(record_its))).copy()
    return SweepResult(state=state, history=history)
