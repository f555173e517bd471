"""Compiled trajectory engine: schedule precompute, scan-vs-eager, and
the batched sweep (swept-vs-looped equivalence + retrace caching)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (make_hyper, make_quadratic_problem, make_schedules,
                      make_straggler_cfg)
from repro.core import StragglerScheduler, run, run_scanned, run_swept
from repro.core import engine as engine_lib
from repro.core.engine import SweepResult, record_slots

# shared small-problem builders live in conftest (one definition for
# test_engine / test_system / test_sharded_engine)
_hyper = make_hyper
_cfg = make_straggler_cfg


# ---------------------------------------------------------------------------
# schedule precompute (regression: bit-identical to stepping)
# ---------------------------------------------------------------------------

def test_precompute_bit_identical_to_stepping():
    sched = StragglerScheduler(_cfg())
    stepped = StragglerScheduler(_cfg())
    schedule = sched.precompute(64)
    assert schedule.n_iterations == 64
    assert schedule.n_workers == 4
    for i in range(64):
        mask, t_done = stepped.next_active()
        assert np.array_equal(schedule.active[i], mask), i
        assert schedule.sim_time[i] == t_done, i
        assert schedule.max_staleness[i] == stepped.max_staleness(), i


def test_precompute_leaves_scheduler_untouched():
    sched = StragglerScheduler(_cfg(seed=7))
    sched.precompute(32)
    fresh = StragglerScheduler(_cfg(seed=7))
    for _ in range(5):
        m1, t1 = sched.next_active()
        m2, t2 = fresh.next_active()
        assert np.array_equal(m1, m2) and t1 == t2


def test_precompute_mid_stream():
    """Precompute after stepping continues the same process."""
    sched = StragglerScheduler(_cfg(seed=3))
    ref = StragglerScheduler(_cfg(seed=3))
    for _ in range(10):
        sched.next_active()
        ref.next_active()
    schedule = sched.precompute(16)
    for i in range(16):
        mask, t_done = ref.next_active()
        assert np.array_equal(schedule.active[i], mask)
        assert schedule.sim_time[i] == t_done


def test_precompute_respects_tau():
    schedule = StragglerScheduler(
        _cfg(s_active=2, tau=4, n_stragglers=2,
             straggler_slowdown=20.0, seed=3)).precompute(60)
    assert schedule.max_staleness.max() <= 4


# ---------------------------------------------------------------------------
# record layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_iterations,metrics_every", [
    (40, 10), (41, 10), (7, 10), (1, 1), (10, 3)])
def test_record_slots_matches_eager_layout(n_iterations, metrics_every):
    record_its, slots = record_slots(n_iterations, metrics_every)
    expect = [it for it in range(n_iterations)
              if (it + 1) % metrics_every == 0 or it == n_iterations - 1]
    assert record_its.tolist() == expect
    for it in range(n_iterations):
        if it in expect:
            assert slots[it] == expect.index(it)
        else:
            assert slots[it] == -1


# ---------------------------------------------------------------------------
# scan-vs-eager equivalence
# ---------------------------------------------------------------------------

def test_scan_matches_eager_trajectory():
    prob = make_quadratic_problem()
    hyper, cfg = _hyper(), _cfg()
    schedule = StragglerScheduler(cfg).precompute(40)

    res_e = run(prob, hyper, scheduler_cfg=cfg, n_iterations=40,
                metrics_every=10, mode="eager", schedule=schedule)
    res_s = run(prob, hyper, scheduler_cfg=cfg, n_iterations=40,
                metrics_every=10, mode="scan", schedule=schedule)

    for a, b in zip(jax.tree.leaves(res_e.state),
                    jax.tree.leaves(res_s.state)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)
    h_e, h_s = res_e.history, res_s.history
    assert list(h_e["t"]) == list(h_s["t"])
    np.testing.assert_allclose(h_e["sim_time"], h_s["sim_time"])
    np.testing.assert_allclose(h_e["max_staleness"], h_s["max_staleness"])
    np.testing.assert_allclose(h_e["gap_sq"], h_s["gap_sq"],
                               rtol=1e-4, atol=1e-6)
    assert list(h_e["n_cuts_i"]) == list(h_s["n_cuts_i"])
    assert list(h_e["n_cuts_ii"]) == list(h_s["n_cuts_ii"])


def test_scan_matches_eager_with_metrics_fn():
    prob = make_quadratic_problem()
    hyper, cfg = _hyper(), _cfg(seed=1)
    schedule = StragglerScheduler(cfg).precompute(25)

    def metrics(state):
        return {"z1_norm_sq": jnp.sum(state.z1 ** 2)}

    res_e = run(prob, hyper, scheduler_cfg=cfg, n_iterations=25,
                metrics_every=10, metrics_fn=metrics, mode="eager",
                schedule=schedule)
    res_s = run(prob, hyper, scheduler_cfg=cfg, n_iterations=25,
                metrics_every=10, metrics_fn=metrics, mode="scan",
                schedule=schedule)
    # 25 iters at stride 10 -> records at 10, 20, 25 (the final iter)
    assert len(res_s.history["z1_norm_sq"]) == 3
    np.testing.assert_allclose(res_e.history["z1_norm_sq"],
                               res_s.history["z1_norm_sq"],
                               rtol=1e-5, atol=1e-7)


def test_scan_fresh_schedule_matches_eager_fresh_scheduler():
    """No explicit schedule: both modes materialize the same seeded
    process from scheduler_cfg, so trajectories still agree."""
    prob = make_quadratic_problem()
    hyper, cfg = _hyper(), _cfg()
    res_e = run(prob, hyper, scheduler_cfg=cfg, n_iterations=20,
                metrics_every=5, mode="eager")
    res_s = run(prob, hyper, scheduler_cfg=cfg, n_iterations=20,
                metrics_every=5, mode="scan")
    np.testing.assert_allclose(res_e.history["gap_sq"],
                               res_s.history["gap_sq"],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(res_e.history["sim_time"],
                               res_s.history["sim_time"])


def test_run_scanned_caller_state_not_donated():
    from repro.core import afto as afto_lib
    prob = make_quadratic_problem()
    hyper, cfg = _hyper(), _cfg()
    schedule = StragglerScheduler(cfg).precompute(10)
    state = afto_lib.init_state(prob, hyper)
    res = run_scanned(prob, hyper, schedule, metrics_every=5, state=state)
    # the caller's buffers must remain readable after the run
    assert np.all(np.isfinite(np.asarray(state.z1)))
    assert np.all(np.isfinite(res.history["gap_sq"]))


def test_run_rejects_unknown_mode():
    prob = make_quadratic_problem()
    with pytest.raises(ValueError):
        run(prob, _hyper(), n_iterations=2, mode="wat")


def test_no_reflatten_on_scanned_path(monkeypatch):
    """Acceptance guard: `flat_spec`/`flatten_cuts` never execute while
    tracing afto_step_aux / cut_refresh / stationarity_gap_sq — the
    canonical `FlatCuts` matrix is consumed as stored, and flattening
    happens only at cut construction (`flatten_coeffs`) and at the
    `to_tree`/`from_tree` compatibility boundary."""
    from repro.core import afto as afto_lib
    from repro.core import cuts as cuts_lib
    from repro.core import stationarity as stat_lib

    calls = []
    orig_spec, orig_flat = cuts_lib.flat_spec, cuts_lib.flatten_cuts
    monkeypatch.setattr(
        cuts_lib, "flat_spec",
        lambda *a, **k: (calls.append("flat_spec"), orig_spec(*a, **k))[1])
    monkeypatch.setattr(
        cuts_lib, "flatten_cuts",
        lambda *a, **k: (calls.append("flatten_cuts"),
                         orig_flat(*a, **k))[1])

    prob = make_quadratic_problem()
    hyper = _hyper()
    state = afto_lib.init_state(prob, hyper)
    jax.eval_shape(
        lambda s: afto_lib.afto_step_aux(prob, hyper, s, jnp.ones(4)),
        state)
    jax.eval_shape(lambda s: afto_lib.cut_refresh(prob, hyper, s), state)
    jax.eval_shape(
        lambda s: stat_lib.stationarity_gap_sq(prob, hyper, s), state)
    assert calls == []


def test_scan_cache_hit_does_not_retrace():
    prob = make_quadratic_problem()
    hyper, cfg = _hyper(), _cfg()
    schedule = StragglerScheduler(cfg).precompute(12)
    run_scanned(prob, hyper, schedule, metrics_every=6)
    builds = engine_lib.BUILD_COUNTS["scan"]
    run_scanned(prob, hyper, schedule, metrics_every=6)
    assert engine_lib.BUILD_COUNTS["scan"] == builds


def _fresh_data(kind, prob):
    from repro.data import stream as stream_lib

    if kind == "plain":
        return None
    if kind == "host":
        return jax.tree.map(lambda x: x + 0.5, prob.data)

    def sample(key):
        ka, kb = jax.random.split(key)
        return {"A": jax.random.normal(ka, (3, 3)) * 0.3,
                "b": jax.random.normal(kb, (3,))}

    return stream_lib.make_stream(sample, prob.n_workers, 7)


def _assert_runs_equal(a, b, records=None):
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for k in records or a.history:
        np.testing.assert_array_equal(a.history[k], b.history[k])


@pytest.mark.parametrize("kind", ["plain", "host", "stream"])
def test_fresh_scan_equals_scan_from_init_state(kind):
    """A fresh solve builds its initial state and history buffers inside
    its program; it matches a solve handed `init_state` bit for bit."""
    from repro.core import afto as afto_lib

    prob = make_quadratic_problem()
    hyper = _hyper()
    schedule = StragglerScheduler(_cfg()).precompute(30)
    data = _fresh_data(kind, prob)
    fresh = run_scanned(prob, hyper, schedule, metrics_every=4, data=data)
    given = run_scanned(prob, hyper, schedule, metrics_every=4, data=data,
                        state=afto_lib.init_state(prob, hyper))
    _assert_runs_equal(fresh, given,
                       ("t", "gap_sq", "n_cuts_i", "n_cuts_ii", "sim_time",
                        "max_staleness"))
    assert fresh.history["gap_sq"].shape == (8,)


@pytest.mark.parametrize("kind", ["plain", "stream"])
def test_chunked_fresh_first_chunk_equals_unchunked(kind):
    """`run_chunked` starts fresh (the state built in the program) and
    continues from the carried state: its first chunk is the solve of
    that slice from `init_state`, and the whole equals one fresh solve."""
    from repro.core import afto as afto_lib

    prob = make_quadratic_problem()
    hyper = _hyper()
    schedule = StragglerScheduler(_cfg()).precompute(30)
    data = _fresh_data(kind, prob)
    seen = []
    chunked = engine_lib.run_chunked(
        prob, hyper, schedule, 10, metrics_every=5, data=data,
        chunk_hook=lambda st, t: seen.append(st))
    first = run_scanned(prob, hyper, schedule.slice(0, 10), metrics_every=5,
                        data=data, state=afto_lib.init_state(prob, hyper))
    for x, y in zip(jax.tree.leaves(seen[0]), jax.tree.leaves(first.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    whole = run_scanned(prob, hyper, schedule, metrics_every=5, data=data)
    _assert_runs_equal(chunked, whole,
                       ("t", "gap_sq", "n_cuts_i", "n_cuts_ii", "sim_time"))


def test_warm_fresh_scan_is_one_dispatch(monkeypatch):
    """On a warm cache hit a fresh solve calls `init_state` nowhere on
    the host: it ran once, inside the build, and `INIT_COUNTS` says the
    program built the state."""
    from repro.core import afto as afto_lib

    calls = []
    real = afto_lib.init_state
    monkeypatch.setattr(afto_lib, "init_state",
                        lambda *a: (calls.append(1), real(*a))[1])
    prob = make_quadratic_problem()
    hyper = _hyper()
    schedule = StragglerScheduler(_cfg()).precompute(12)
    run_scanned(prob, hyper, schedule, metrics_every=6)
    assert len(calls) == 1
    builds = engine_lib.BUILD_COUNTS["scan"]
    inits = dict(engine_lib.INIT_COUNTS)
    del calls[:]
    for n in (1, 2):
        res = run_scanned(prob, hyper, schedule, metrics_every=6)
        assert calls == []
        assert engine_lib.BUILD_COUNTS["scan"] == builds
        assert engine_lib.INIT_COUNTS == {"program": inits["program"] + n,
                                          "host": inits["host"]}
    assert np.all(np.isfinite(res.history["gap_sq"]))
    # a passed state is used as given: no count, and not traced again
    run_scanned(prob, hyper, schedule, metrics_every=6,
                state=real(prob, hyper))
    assert engine_lib.INIT_COUNTS["program"] == inits["program"] + 2
    assert engine_lib.INIT_COUNTS["host"] == inits["host"]


# ---------------------------------------------------------------------------
# batched sweep: swept rows must reproduce individual scanned runs
# ---------------------------------------------------------------------------

_schedules = make_schedules


def test_swept_matches_looped_scanned():
    """Row r of run_swept reproduces run_scanned on schedule r.

    Tolerance, not bit-equality: the vmapped body batches every
    contraction over the run axis, which reorders f32 accumulations
    relative to the single-run scan (e.g. batched matvec vs matvec);
    observed drift at 40 quickstart-scale iterations is < 1e-6 relative.
    """
    prob = make_quadratic_problem()
    hyper = _hyper()
    scheds = _schedules(40, (0, 1, 2))

    def metrics(state):
        return {"z1_norm_sq": jnp.sum(state.z1 ** 2)}

    swept = run_swept(prob, hyper, scheds, metrics_fn=metrics,
                      metrics_every=10)
    assert swept.n_runs == 3
    for r in range(3):
        single = run_scanned(prob, hyper, scheds[r], metrics_fn=metrics,
                             metrics_every=10)
        row = swept.run(r)
        for a, b in zip(jax.tree.leaves(single.state),
                        jax.tree.leaves(row.state)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(single.history["gap_sq"],
                                   row.history["gap_sq"],
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(single.history["z1_norm_sq"],
                                   row.history["z1_norm_sq"],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(single.history["sim_time"],
                                   row.history["sim_time"])
        np.testing.assert_allclose(single.history["max_staleness"],
                                   row.history["max_staleness"])
        assert list(single.history["t"]) == list(row.history["t"])
        assert list(single.history["n_cuts_ii"]) == \
            list(row.history["n_cuts_ii"])


def test_swept_cache_hit_does_not_retrace():
    prob = make_quadratic_problem()
    hyper = _hyper()
    scheds = _schedules(16, (0, 1))
    run_swept(prob, hyper, scheds, metrics_every=8)
    builds = engine_lib.BUILD_COUNTS["sweep"]
    # identical sweep: cached compiled trajectory, no new trace
    run_swept(prob, hyper, scheds, metrics_every=8)
    assert engine_lib.BUILD_COUNTS["sweep"] == builds
    # fresh schedules with the same shape also reuse the trace
    run_swept(prob, hyper, _schedules(16, (5, 6)), metrics_every=8)
    assert engine_lib.BUILD_COUNTS["sweep"] == builds


def test_swept_hyper_sweep_matches_scanned():
    prob = make_quadratic_problem()
    hyper = _hyper()
    scheds = _schedules(25, (0, 0))       # same arrival process
    swept = run_swept(prob, hyper, scheds, metrics_every=10,
                      sweep_hypers={"eta_z": [0.05, 0.01]})
    for r, eta_z in enumerate((0.05, 0.01)):
        single = run_scanned(prob, dataclasses.replace(hyper, eta_z=eta_z),
                             scheds[r], metrics_every=10)
        np.testing.assert_allclose(single.history["gap_sq"],
                                   swept.run(r).history["gap_sq"],
                                   rtol=2e-4, atol=1e-6)


def test_swept_rejects_bad_inputs():
    prob = make_quadratic_problem()
    hyper = _hyper()
    with pytest.raises(ValueError):
        run_swept(prob, hyper, [])
    scheds = _schedules(10, (0, 1))
    with pytest.raises(ValueError):                 # length mismatch
        run_swept(prob, hyper, [scheds[0], _schedules(12, (1,))[0]])
    with pytest.raises(ValueError):                 # unknown hyper field
        run_swept(prob, hyper, scheds, sweep_hypers={"nope": [1, 2]})
    with pytest.raises(ValueError):                 # shape-determining
        run_swept(prob, hyper, scheds, sweep_hypers={"p_max": [4, 8]})
    with pytest.raises(ValueError):                 # wrong sweep length
        run_swept(prob, hyper, scheds, sweep_hypers={"eta_z": [0.1]})


def test_run_mode_sweep_dispatch_and_host_time():
    """runner.run(mode='sweep') seeds R schedules and the history carries
    per-run rows with the elapsed/R host_time proration."""
    prob = make_quadratic_problem()
    hyper, cfg = _hyper(), _cfg()
    res = run(prob, hyper, scheduler_cfg=cfg, n_iterations=20,
              metrics_every=5, mode="sweep", seeds=(0, 1))
    assert isinstance(res, SweepResult)
    assert res.history["gap_sq"].shape == (2, 4)
    assert res.history["host_time"].shape == (2, 4)
    # equal 1/R share, prorated over iterations: rows identical and
    # increasing, final entry = elapsed / R
    np.testing.assert_allclose(res.history["host_time"][0],
                               res.history["host_time"][1])
    assert np.all(np.diff(res.history["host_time"][0]) > 0)
    # seed 0's row matches a plain scan run over the same process
    single = run(prob, hyper, scheduler_cfg=cfg, n_iterations=20,
                 metrics_every=5, mode="scan")
    np.testing.assert_allclose(single.history["gap_sq"],
                               res.run(0).history["gap_sq"],
                               rtol=2e-4, atol=1e-6)
    with pytest.raises(ValueError):
        run(prob, hyper, n_iterations=4, mode="sweep", jit=False)


def test_swept_respects_caller_states_and_data():
    """Stacked per-run initial states and per-run data: each row must
    match a run_scanned with that run's state/data, and the caller's
    buffers must survive the donated dispatch."""
    from repro.core import afto as afto_lib
    from repro.utils.tree import tree_stack

    hyper = _hyper()
    probs = [make_quadratic_problem(seed=s) for s in (0, 3)]
    scheds = _schedules(15, (0, 1))
    states = tree_stack([afto_lib.init_state(p, hyper) for p in probs])
    data = tree_stack([p.data for p in probs])
    swept = run_swept(probs[0], hyper, scheds, states=states, data=data,
                      metrics_every=5)
    for r in range(2):
        single = run_scanned(probs[r], hyper, scheds[r], metrics_every=5,
                             state=afto_lib.init_state(probs[r], hyper))
        np.testing.assert_allclose(single.history["gap_sq"],
                                   swept.run(r).history["gap_sq"],
                                   rtol=2e-4, atol=1e-6)
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(states))


def _count_conds(jaxpr) -> int:
    """`cond` equations in a jaxpr and every sub-jaxpr it holds."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    n += _count_conds(sub)
    return n


def test_swept_refresh_stays_gated():
    """The sweep keeps the refresh under a real cond: a vmapped cond on
    the per-run predicate would lower to a select of both branches."""
    from repro.core import afto as afto_lib

    prob = make_quadratic_problem()
    hyper = _hyper()
    sched = _schedules(12, (0,))[0]
    keys = engine_lib._metric_keys(prob, hyper, None, None)
    _, slots = record_slots(12, 6)
    slots = jnp.asarray(slots)
    masks = jnp.asarray(sched.active, jnp.float32)
    hist = {k: jnp.zeros((3,), jnp.float32) for k in keys}

    scan = engine_lib._build_scan(prob, hyper, None, keys, donate=False)
    scan_jaxpr = jax.make_jaxpr(scan)(
        afto_lib.init_state(prob, hyper), hist, None, None, masks, slots)
    sweep = engine_lib._build_sweep(prob, hyper, None, keys, (), False,
                                    init_inside=True)
    sweep_jaxpr = jax.make_jaxpr(sweep)(
        {k: v[None] for k, v in hist.items()}, masks[None], (), None,
        None, slots)
    n_scan, n_sweep = _count_conds(scan_jaxpr), _count_conds(sweep_jaxpr)
    assert n_scan >= 3
    assert n_sweep >= n_scan


@pytest.mark.parametrize("case", ["stacked_t", "swept_t_pre"])
def test_swept_out_of_phase_runs_refresh_on_their_own_t(case):
    """Runs whose refresh iterations differ — stacked states at different
    t, or a swept t_pre — each match run_scanned from the same state: the
    gate opens at the union of their refresh iterations and the per-run
    `where` keeps every other run's state as it was."""
    from repro.core import afto as afto_lib
    from repro.utils.tree import tree_stack

    prob = make_quadratic_problem()
    hyper = _hyper()
    if case == "stacked_t":
        n_runs, hypers, sweep = 3, [hyper] * 3, None
        pre = _schedules(2, (7,))[0]
        init = afto_lib.init_state(prob, hyper)
        starts = [init] + [run_scanned(prob, hyper, pre.slice(0, r),
                                       state=init).state
                           for r in (1, 2)]
        assert [int(s.t) for s in starts] == [0, 1, 2]
    else:
        t_pres = [5, 10]
        n_runs = len(t_pres)
        hypers = [dataclasses.replace(hyper, t_pre=t) for t in t_pres]
        sweep = {"t_pre": t_pres}
        starts = [afto_lib.init_state(prob, hyper)] * n_runs
    scheds = _schedules(40, tuple(range(n_runs)))
    swept = run_swept(prob, hyper, scheds, states=tree_stack(starts),
                      sweep_hypers=sweep, metrics_every=10)
    for r in range(n_runs):
        single = run_scanned(prob, hypers[r], scheds[r], state=starts[r],
                             metrics_every=10)
        row = swept.run(r)
        for a, b in zip(jax.tree.leaves(single.state),
                        jax.tree.leaves(row.state)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(single.history["gap_sq"],
                                   row.history["gap_sq"],
                                   rtol=2e-4, atol=1e-6)
        assert list(single.history["n_cuts_ii"]) == \
            list(row.history["n_cuts_ii"])
