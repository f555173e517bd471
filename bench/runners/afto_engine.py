"""Runner of the compiled-engine cells: back-to-back `repro.core.run`
solves of one trilevel problem, each a whole AFTO trajectory (master
iterations, cut refreshes, gap records) in one dispatch, with the
history back on the host.

`traffic["engine"]` is "scan" (one federation a solve) or "sweep" (a
grid of `len(seeds) x len(eta_x)` federations a solve, through
`sweep_hypers`).  The problem's data and initial weights are the
benchmark's, from the seed; solve i's arrival schedules are seeded
`seed + i * runs + r` for run r, and the program draws them from that
seed itself, as a user's call does.

After the window, a sample of the window's runs drawn from the seed is
solved again by the plain reference (`lib/afto_ref.py` with the
configuration's problem file) on the same schedule, and the comparison
decides `correct`.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import statistics
import time

import numpy as np

from lib import afto_ref as ref
from lib import traffic_gen, work
from lib.common import CompileCounter, load_module, log

STATIC = ("n_workers", "s_active", "tau", "k_inner", "p_max", "d1",
          "use_fused_inner")


def grid(traffic, seed: int, i: int):
    """Run r of solve i: (schedule seed, weight seed index, eta_x)."""
    if traffic["engine"] == "scan":
        return [(seed + i, 0, None)]
    ks, etas = traffic["seeds"], traffic["eta_x"]
    base = seed + i * ks * len(etas)
    return [(base + k, k, e) for e in etas for k in range(ks)]


class EngineProgram:
    """The system under test: the app's problem with the benchmark's data
    and weights, its `Hyper`, and one solve through `repro.core.run`."""

    def __init__(self, config, traffic, data, weights):
        import jax.numpy as jnp

        from repro.apps.robust_hpo import make_robust_hpo_problem
        from repro.core import RunSpec, run
        from repro.core.scheduler import StragglerConfig
        from repro.core.types import Hyper

        c, f = config["problem"], config["federation"]
        task = make_robust_hpo_problem(c["dataset"], c["n_workers"],
                                       hidden=c["hidden"],
                                       adv_penalty=c["adv_penalty"])
        self.problem = dataclasses.replace(
            task.problem, data={k: jnp.asarray(v) for k, v in data.items()},
            x2_init=jnp.zeros(data["xtr"].shape, jnp.float32),
            x3_init=weights[0])
        self.hyper = Hyper(**config["hyper"])
        self.traffic = traffic
        self.RunSpec, self.run = RunSpec, run
        self.sched = lambda s: StragglerConfig(
            n_workers=f["n_workers"], s_active=f["s_active"], tau=f["tau"],
            n_stragglers=f["n_stragglers"],
            straggler_slowdown=f["straggler_slowdown"], seed=s)
        self.states = None
        if traffic["engine"] == "sweep":
            self._stack_states(weights)

    def reseed(self, data, weights):
        """The same compiled solves on another seed's data and weights."""
        import jax.numpy as jnp

        self.problem = dataclasses.replace(
            self.problem,
            data={k: jnp.asarray(v) for k, v in data.items()},
            x3_init=weights[0])
        if self.states is not None:
            self._stack_states(weights)

    def _stack_states(self, weights):
        import jax
        import jax.numpy as jnp

        from repro.core.afto import init_state

        one = [init_state(dataclasses.replace(self.problem, x3_init=w),
                          self.hyper) for w in weights]
        n_eta = len(self.traffic["eta_x"])
        self.states = jax.tree.map(
            lambda *xs: jnp.stack(list(xs) * n_eta), *one)

    def solve(self, runs):
        tr = self.traffic
        if tr["engine"] == "scan":
            spec = self.RunSpec(
                problem=self.problem, hyper=self.hyper, engine="scan",
                n_iterations=tr["iterations"],
                scheduler=self.sched(runs[0][0]),
                metrics_every=tr["record_every"])
        else:
            spec = self.RunSpec(
                problem=self.problem, hyper=self.hyper, engine="sweep",
                n_iterations=tr["iterations"],
                scheduler=self.sched(runs[0][0]),
                seeds=[r[0] for r in runs],
                sweep_hypers={"eta_x": [r[2] for r in runs]},
                sweep_states=self.states, metrics_every=tr["record_every"])
        return self.run(spec)


def _run_state(res, engine: str, r: int):
    """Run r's final state as the reference's flat leaves."""
    st = res.state if engine == "scan" else res.run(r).state
    return _leaves({"X1": st.X1, "X2": st.X2, "X3": st.X3, "z1": st.z1,
                    "z2": st.z2, "z3": st.z3, "theta": st.theta,
                    "lam": st.lam})


def _run_gaps(res, engine: str, r: int):
    g = np.asarray(res.history["gap_sq"])
    return g if engine == "scan" else g[r]


def _leaves(d):
    import jax

    out = {}
    for k, v in d.items():
        for path, leaf in jax.tree_util.tree_leaves_with_path(v):
            out[k + jax.tree_util.keystr(path)] = np.asarray(leaf, np.float64)
    return out


def setup_problem(config, seed):
    model = load_module("configs", config["name"] + ".py")
    c = config["problem"]
    data = model.make_data(c, seed)
    key = traffic_gen.seed_key(seed)
    return model, data, key


def weights_for(model, config, traffic, key):
    import jax

    n = 1 if traffic["engine"] == "scan" else traffic["seeds"]
    return [model.init_weights(config["problem"], jax.random.fold_in(key, k))
            for k in range(n)]


def run(ctx) -> dict:
    import jax

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    model, data, key = setup_problem(config, seed)
    weights = weights_for(model, config, traffic, key)
    prog = EngineProgram(config, traffic, data, weights)
    if ctx.get("plant"):
        ctx["plant"](prog)      # a fault planted by the benchmark's tests
    counter = CompileCounter()

    warm = prog.solve(grid(traffic, seed, 0))
    jax.block_until_ready(warm.state)
    del warm
    setup_s = time.perf_counter() - ctx["t_start"]

    seconds = ctx["seconds"]
    annotate = ctx["annotate"]
    rng = np.random.default_rng(seed)
    keep, lat, i = [], [], 0
    counter.armed = True
    with ctx["tracer"]():
        with annotate("window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                i += 1
                runs = grid(traffic, seed, i)
                ts = time.perf_counter()
                with annotate("solve"):
                    res = prog.solve(runs)
                lat.append(time.perf_counter() - ts)
                with annotate("sample"):
                    # a reservoir of the window's solves, drawn from the seed
                    k = traffic["sampled_solves"]
                    if len(keep) < k:
                        keep.append((i, runs, res))
                    elif rng.random() < k / i:
                        keep[int(rng.integers(k))] = (i, runs, res)
                    del res
            window_s = time.perf_counter() - t0
    counter.armed = False
    log(f"window: {i} solves in {window_s} s; compiles in window: "
        f"{counter.count}")
    device = ctx["device_record"]()
    gc.collect()

    numbers = compare(model, config, traffic, data, weights, keep,
                      ctx.get("reference", "f32"), rng)
    n_runs = len(grid(traffic, seed, 0))
    iters = i * n_runs * traffic["iterations"]
    e2e = {"fed_iters_per_s": iters / window_s,
           "solve_s_p95": (statistics.quantiles(lat, n=20)[-1]
                           if len(lat) >= 2 else lat[0])}
    log(f"solve latency: median {statistics.median(lat)} s over {len(lat)}")
    return {"attempted": i, "failed": 0, "device": device,
            "numbers": numbers, "setup_s": setup_s, "window_s": window_s,
            "compiles_in_window": counter.count, "e2e": e2e,
            "work": dict(work.afto_iteration_work(config, traffic),
                         iterations=iters)}


# ---------------------------------------------------------------------------
# the comparison with the plain reference
# ---------------------------------------------------------------------------

_SOLVERS = {}


def reference_solver(model, config, traffic, kind="f32"):
    """A jitted plain solve: (hyper floats, data, initial point, masks) ->
    (final state, gap history); `kind` "bf16" is the control."""
    import jax
    import jax.numpy as jnp

    sig = (config["name"], json.dumps(traffic, sort_keys=True), kind)
    if sig in _SOLVERS:
        return _SOLVERS[sig]
    c, hc = config["problem"], config["hyper"]
    static = {k: hc[k] for k in STATIC}
    mm = MM[kind]
    f1, f2, f3 = model.objectives(c, mm)
    T, every = traffic["iterations"], traffic["record_every"]
    cast = (lambda t: jax.tree.map(
        lambda x: _round_bf16(x)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, t)) \
        if kind == "bf16" else None

    @jax.jit
    def solve(hf, data, x0, masks):
        h = dict(hf, **static)
        prob = {"f1": f1, "f2": f2, "f3": f3, "data": data}
        st = ref.init_state(h, h["n_workers"], *x0)
        st, gaps = ref.solve(prob, h, st, masks, every, cast=cast)
        its = np.arange(T)
        return st, gaps[np.nonzero(((its + 1) % every == 0)
                                   | (its == T - 1))[0]]

    _SOLVERS[sig] = solve
    return solve


def _mm_f32(spec, a, b):
    import jax.numpy as jnp

    return jnp.einsum(spec, a, b)


def _round_bf16(x):
    """x rounded to bfloat16's precision, kept in its own dtype.  A
    float32 -> bfloat16 -> float32 round trip is not enough: XLA on a TPU
    may drop such a pair of converts (excess precision), and the control
    then computes what the program does (on the chip it read no higher
    than the program)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm_bf16(spec, a, b):
    import jax.numpy as jnp

    return _round_bf16(jnp.einsum(spec, _round_bf16(a), _round_bf16(b)))


MM = {"f32": _mm_f32, "bf16": _mm_bf16}


def reference_runs(model, config, traffic, data, weights, runs, kind="f32"):
    """[(final leaves, gap history)] of the reference for each run."""
    import jax
    import jax.numpy as jnp

    hc, f = config["hyper"], config["federation"]
    solve = reference_solver(model, config, traffic, kind)
    out = []
    with jax.default_matmul_precision("highest" if kind == "f32"
                                      else "default"):
        for sched_seed, k, eta in runs:
            masks = traffic_gen.arrival_masks(
                f["n_workers"], f["s_active"], f["tau"], f["n_stragglers"],
                f["straggler_slowdown"], traffic["iterations"], sched_seed)
            hf = {k2: jnp.float32(v) for k2, v in hc.items()
                  if k2 not in STATIC}
            if eta is not None:
                hf["eta_x"] = jnp.float32(eta)
            x1, x2 = model.initial_point(config["problem"], data)
            st, gaps = solve(hf, {k2: jnp.asarray(v) for k2, v in data.items()},
                             (x1, x2, weights[k]), jnp.asarray(masks))
            out.append((_leaves({k2: st[k2] for k2 in (
                "X1", "X2", "X3", "z1", "z2", "z3", "theta", "lam")}),
                np.asarray(gaps, np.float64)))
    return out


def numbers_from(prog_runs, ref_runs):
    """gap_rel_err: the largest relative gap between the program's and the
    reference's stationarity gap at any record of any sampled run;
    state_rel_err: the largest ||x_prog - x_ref|| over the reference's
    norm of that leaf or of the median leaf, whichever is larger, at the
    end of any sampled run."""
    g_err, s_err, where = 0.0, 0.0, None
    for (p_leaves, p_gaps), (r_leaves, r_gaps) in zip(prog_runs, ref_runs):
        g_err = max(g_err, float(np.max(np.abs(p_gaps - r_gaps)
                                        / np.abs(r_gaps))))
        norms = {k: float(np.linalg.norm(v)) for k, v in r_leaves.items()}
        med = statistics.median(norms.values())
        for k, v in r_leaves.items():
            e = float(np.linalg.norm(p_leaves[k] - v)) / max(norms[k], med)
            if e > s_err:
                s_err, where = e, k
    log(f"gap_rel_err {g_err}; state_rel_err {s_err} at {where}")
    return {"gap_rel_err": g_err, "state_rel_err": s_err}


def sample_runs(keep, rng):
    """(solve, run index, run) triples of the sampled solves: every run of
    a single-run solve, one run drawn from the seed of a sweep's."""
    out = []
    for _, runs, res in keep:
        r = 0 if len(runs) == 1 else int(rng.integers(len(runs)))
        out.append((res, r, runs[r]))
    return out


def compare(model, config, traffic, data, weights, keep, kind, rng):
    engine = traffic["engine"]
    picked = sample_runs(keep, rng)
    refs = reference_runs(model, config, traffic, data, weights,
                          [p[2] for p in picked])
    if kind != "f32":
        prog = reference_runs(model, config, traffic, data, weights,
                              [p[2] for p in picked], kind)
    else:
        prog = [(_run_state(res, engine, r), _run_gaps(res, engine, r))
                for res, r, _ in picked]
    return numbers_from(prog, refs)


def calibrate(ctx, seeds, control_seeds, emit):
    """The readings the limits are set from, in one process: on `seeds`
    the program's first solve against the reference; on `control_seeds`
    the control (the reference in bfloat16) in the program's place."""
    config, traffic = ctx["config"], ctx["traffic"]
    prog = None
    for kind, seed_set in (("program", seeds), ("control_bf16",
                                               control_seeds)):
        for seed in seed_set:
            model, data, key = setup_problem(config, seed)
            weights = weights_for(model, config, traffic, key)
            runs = grid(traffic, seed, 1)
            r = int(np.random.default_rng(seed).integers(len(runs)))
            refs = reference_runs(model, config, traffic, data, weights,
                                  [runs[r]])
            if kind == "program":
                if prog is None:
                    prog = EngineProgram(config, traffic, data, weights)
                else:
                    prog.reseed(data, weights)
                res = prog.solve(runs)
                got = [(_run_state(res, traffic["engine"], r),
                        _run_gaps(res, traffic["engine"], r))]
            else:
                got = reference_runs(model, config, traffic, data, weights,
                                     [runs[r]], "bf16")
            emit({"kind": kind, "seed": seed, **numbers_from(got, refs)})
