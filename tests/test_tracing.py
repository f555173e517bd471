"""The program's own spans and name scopes.

A `core.run` call is the host span `afto.run`, with the compiled
engines' phases inside it; the compiled trajectory carries the scopes
`afto_step`, `cut_refresh`, `gap_record` and, around the cut kernels'
calls alone, `cut_kernel` (README, "Observability").  Here a tiny scan
and a tiny sweep run under `jax.profiler.trace` and are read back with
`jax.profiler.ProfileData`, and the scan and sweep programs are
compiled with the Pallas kernels forced on (interpret mode) to read
their `op_name`s.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_hyper, make_quadratic_problem, make_straggler_cfg
from repro.core import RunSpec, run

PHASES = ("afto.schedule", "afto.init_state", "afto.build", "afto.stage",
          "afto.dispatch", "afto.wait", "afto.fetch")
PROGRAMS = ("jit_scan_all", "jit_sweep_all")
T = 12


def _spec(engine):
    prob = make_quadratic_problem()
    hyper = make_hyper()
    extra = {"seeds": [0, 1, 2]} if engine == "sweep" else {}
    return RunSpec(problem=prob, hyper=hyper, engine=engine, n_iterations=T,
                   scheduler=make_straggler_cfg(), metrics_every=4, **extra)


def _events(trace_dir):
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans, ops = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                e = (ev.name, int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns), line.name)
                if ev.name.startswith("afto."):
                    spans.append(e)
                elif dict(ev.stats).get("hlo_module") in PROGRAMS:
                    ops.append(e)
    return spans, ops


@pytest.fixture(scope="module", params=["scan", "sweep"])
def traced(request, tmp_path_factory):
    spec = _spec(request.param)
    run(spec)        # compiled before the trace: a warm call is traced
    d = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    with jax.profiler.trace(d):
        res = run(spec)
    assert np.all(np.isfinite(np.asarray(res.history["gap_sq"])))
    return _events(d)


def test_every_span_nests_in_the_run(traced):
    spans, _ = traced
    runs = [s for s in spans if s[0] == "afto.run"]
    assert len(runs) == 1
    _, a, b, line = runs[0]
    names = {s[0] for s in spans}
    assert set(PHASES) <= names
    for name, x, y, ln in spans:
        assert a <= x <= y <= b and ln == line, name


def test_the_phases_cover_the_run(traced):
    spans, _ = traced
    (_, a, b, _), = [s for s in spans if s[0] == "afto.run"]
    covered = sum(y - x for name, x, y, _ in spans if name in PHASES)
    assert covered >= 0.9 * (b - a)


def test_the_programs_ops_run_inside_dispatch_and_wait(traced):
    """The trajectory's XLA ops fall inside `afto.dispatch` and
    `afto.wait`, which follow each other: spans and ops share a clock."""
    spans, ops = traced
    (_, a, _, _), = [s for s in spans if s[0] == "afto.dispatch"]
    (_, _, b, _), = [s for s in spans if s[0] == "afto.wait"]
    assert ops
    for name, x, y, _ in ops:
        assert a <= x <= y <= b, name


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _in(scope, op_name):
    """Whether `scope` is on the path, bare or inside transforms."""
    return re.search(rf"(^|[/(]){scope}\)*/", op_name) is not None


@pytest.fixture(scope="module")
def kernels_forced():
    """The Pallas cut kernels on every cut evaluation, in interpret mode
    off-TPU; the traces made meanwhile are dropped afterwards."""
    from repro.kernels import ops

    real = ops.on_tpu
    jax.clear_caches()
    ops.on_tpu = lambda: True
    yield
    ops.on_tpu = real
    jax.clear_caches()


@pytest.mark.parametrize("engine", ["scan", "sweep"])
def test_the_programs_carry_the_scopes(engine, kernels_forced):
    from repro.core import afto, engine as eng

    spec = _spec(engine)
    prob, hyper = spec.problem, spec.hyper
    keys = ("gap_sq", "n_cuts_i", "n_cuts_ii")
    rec, slots = eng.record_slots(T, spec.metrics_every)
    st = afto.init_state(prob, hyper)
    if engine == "scan":
        fn = eng._build_scan(prob, hyper, None, keys, False)
        args = (st, {k: jnp.zeros(len(rec)) for k in keys}, None, None,
                jnp.ones((T, hyper.n_workers)), jnp.asarray(slots))
    else:
        r = 2
        fn = eng._build_sweep(prob, hyper, None, keys, (), False, False)
        args = (jax.tree.map(lambda x: jnp.stack([x] * r), st),
                {k: jnp.zeros((r, len(rec))) for k in keys},
                jnp.ones((r, T, hyper.n_workers)), (), None, None,
                jnp.asarray(slots))
    names = _op_names(fn.lower(*args).compile().as_text())
    for scope in ("afto_step", "cut_refresh", "gap_record", "cut_kernel"):
        assert any(_in(scope, n) for n in names), scope
    kernel = [n for n in names if _in("cut_kernel", n)]
    # the refresh differentiates through the kernels: their transposes
    # keep the scope, and under the sweep's vmap so does every call
    assert any("transpose(" in n for n in kernel)
    if engine == "sweep":
        assert any("vmap(cut_kernel)" in n for n in kernel)


def test_the_kernel_scope_holds_the_kernel_call_alone(kernels_forced):
    """Around a cut evaluation the scope holds the Pallas call and what
    it lowers to, not the padding before it or the slicing after it."""
    from repro.kernels import ops

    a, v = jnp.ones((3, 4, 256)), jnp.ones((256,))
    c, act = jnp.ones((4,)), jnp.ones((4,))

    def loss(a, v):
        return jnp.sum(ops.cut_eval(a, v, c, act) ** 2)

    f = jax.vmap(jax.grad(loss, argnums=(0, 1)), in_axes=(0, None))
    names = _op_names(jax.jit(f).lower(a, v).compile().as_text())
    scoped = {n for n in names if _in("cut_kernel", n)}
    assert any("transpose(" in n for n in scoped)
    assert any("vmap(cut_kernel)" in n for n in scoped)
    outside = re.compile(r"cut_kernel\)?/cut_eval/"
                         r"(pad|squeeze|slice|scatter|dynamic_update_slice)\b")
    assert not any(outside.search(n) for n in scoped)


def test_build_counts_still_count_retraces():
    """`BUILD_COUNTS` stays the program's retrace counter beside the
    `afto.build` span: a warm call builds nothing."""
    from repro.core import engine as eng

    spec = dataclasses.replace(_spec("scan"), n_iterations=T + 1)
    run(spec)
    before = dict(eng.BUILD_COUNTS)
    run(spec)
    assert eng.BUILD_COUNTS == before
