#!/usr/bin/env python3
"""Bring-up check: drive the system's main paths once on a TPU.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: the worker mesh only

Everything runs in this one process, through the entry points a user
calls, and nothing falls back to the CPU: without a TPU the script exits
non-zero and names the platform it found.  Each phase prints one JSON
line; any failed phase or comparison raises, so the script exits
non-zero.  The last line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Phases on one chip:
  (a) kernels   `ops.cut_eval` and `ops.fused_cut_round` with
                impl="pallas" at P=8, D=2^18: forward, `jax.grad` and a
                grad-of-grad, each against impl="ref" (max relative
                error <= 5e-4) and each with a Mosaic kernel
                (`tpu_custom_call`) in its compiled program.
  (b) engine    `run(RunSpec(engine="scan"))` on robust HPO at the
                settings of examples/robust_hpo.py, unfused and fused
                inner rounds: a Mosaic kernel in each scan program,
                finite gaps that agree, the active level-II cuts.
  (c) runtime   `serve fed --problem quadratic --workers 2 --iters 40
                --transport inproc`, whose own gates must pass.
  (d) trainer   `train --arch xlstm-125m` at full width (N=4 workers,
                batch 2, seq 129, sketch r=256, the default lr) for 4
                steps with a cut refresh every 2: finite losses, compile
                and step times, peak device memory.

`--four-chips` runs only what exists across chips: the sharded scan on
a 4-device worker mesh against the single-device scan of the same
schedule, and the trainer's `--mesh-workers 4` placement against one
device at full width, one block deep, in f32: an AFTO step, a cut
refresh and a step through the new cut, each taken sharded and
unsharded from the same state.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
KERNEL_TOL = 5e-4          # the interpret-mode parity bound of bench_kernels
GAP_RTOL, GAP_ATOL = 1e-4, 1e-6
STATE_RTOL, STATE_ATOL = 1e-4, 1e-6      # as engine_speed.sharded_record
# --log-every 1: one dispatch and one logged loss per step, so the 4
# losses and the per-step times are all visible
TRAIN_ARGV = ["--arch", "xlstm-125m", "--steps", "4", "--t-pre", "2",
              "--log-every", "1", "--stream"]
# the trainer's defaults otherwise: N=4, batch 2, seq 129, r=256, lr 3e-3
MESH_TRAIN_ARGV = ["--arch", "xlstm-125m", "--stream"]
# Sharded vs unsharded, one transition from the same f32 state: the
# worst leaf's ||sharded - unsharded|| / ||unsharded update||.  The
# summation order alone gives 1e-4 to 5e-4 on a v5e; a sharded step that
# drops one worker's update gives 0.8 (PERF.md).
UPDATE_RTOL = 1e-2
LOSS_RTOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


def emit(**fields):
    print(json.dumps(fields), flush=True)


def require(cond, what):
    if not cond:
        raise SmokeFailure(what)


def _import_repo():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {src}; run "
                         "this script from a checkout of the repository")
    for path in (ROOT, src):
        if path not in sys.path:
            sys.path.insert(0, path)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{d0.platform!r} ({d0.device_kind}); there is "
                         "no CPU path")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU devices, found "
                         f"{len(devices)}")
    return devices


# ---------------------------------------------------------------------------
# (a) kernels
# ---------------------------------------------------------------------------

def rel_err(got, want) -> float:
    """Largest over output leaves of max|got - want| / max|want|."""
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        err = np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-6)
        worst = max(worst, float(err))
    return worst


def check_kernel(name, build, args):
    """Compile build("pallas"), require a Mosaic kernel in it, run it and
    compare with build("ref")."""
    import jax

    compiled = jax.jit(build("pallas")).lower(*args).compile()
    require("tpu_custom_call" in compiled.as_text(),
            f"{name}: no Mosaic kernel (tpu_custom_call) in the compiled "
            "program")
    got = jax.block_until_ready(compiled(*args))
    want = jax.block_until_ready(jax.jit(build("ref"))(*args))
    err = rel_err(got, want)
    ok = math.isfinite(err) and err <= KERNEL_TOL
    emit(phase="kernels", check=name, max_rel_err=err, tol=KERNEL_TOL,
         tpu_custom_call=True, ok=ok)
    require(ok, f"{name}: max relative error {err} exceeds {KERNEL_TOL}")


def phase_kernels(p: int = 8, d: int = 1 << 18):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    a = 0.1 * jax.random.normal(ks[0], (p, d), jnp.float32)
    v = jax.random.normal(ks[1], (d,), jnp.float32)
    c = jax.random.normal(ks[2], (p,), jnp.float32)
    act = (jnp.arange(p) % 4 != 3).astype(jnp.float32)
    w = jax.random.normal(ks[3], (p,), jnp.float32)

    def sq_grad_v(loss):
        # grad-of-grad: the Eq. 23/24 cut-refresh shape
        return lambda a, v: jnp.sum(jax.grad(loss, argnums=1)(a, v) ** 2)

    def ce_loss(impl):
        return lambda a, v: 0.5 * jnp.sum(
            w * ops.cut_eval(a, v, c, act, impl=impl) ** 2)

    check_kernel("cut_eval",
                 lambda impl: lambda a, v: ops.cut_eval(a, v, c, act,
                                                        impl=impl), (a, v))
    check_kernel("cut_eval_grad",
                 lambda impl: jax.grad(ce_loss(impl), argnums=(0, 1)),
                 (a, v))
    check_kernel("cut_eval_grad_of_grad",
                 lambda impl: jax.grad(sq_grad_v(ce_loss(impl)),
                                       argnums=(0, 1)), (a, v))

    g = jax.random.normal(ks[4], (d,), jnp.float32)
    mask = (jnp.arange(d) % 2).astype(jnp.float32)
    s = jnp.abs(jax.random.normal(ks[5], (p,), jnp.float32))
    gam = jnp.abs(jax.random.normal(ks[6], (p,), jnp.float32))
    kw = dict(eta_z=0.05, eta_s=0.05, eta_dual=0.05, rho2=1.0)

    def rnd(impl):
        return lambda a, v: ops.fused_cut_round(a, v, g, mask, c, act, s,
                                                gam, impl=impl, **kw)

    def rnd_loss(impl):
        def loss(a, v):
            v_new, cv, s_new, gam_new = rnd(impl)(a, v)
            return (0.5 * jnp.sum(v_new ** 2) + jnp.sum(w * cv)
                    + jnp.sum(s_new) + jnp.sum(gam_new))
        return loss

    check_kernel("fused_cut_round", rnd, (a, v))
    check_kernel("fused_cut_round_grad",
                 lambda impl: jax.grad(rnd_loss(impl), argnums=(0, 1)),
                 (a, v))
    check_kernel("fused_cut_round_grad_of_grad",
                 lambda impl: jax.grad(sq_grad_v(rnd_loss(impl)),
                                       argnums=(0, 1)), (a, v))


# ---------------------------------------------------------------------------
# (b) compiled engine
# ---------------------------------------------------------------------------

def engine_mosaic_kernels(problem, hyper, n_iterations, metrics_every):
    """{instruction name: count} of the Mosaic kernel calls
    (`tpu_custom_call`) in the scan program `run(RunSpec(engine="scan"))`
    builds for this problem and hyper."""
    import collections
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import afto, engine

    state = jax.eval_shape(lambda: afto.init_state(problem, hyper))
    keys = engine._metric_keys(problem, hyper, None, state)
    record_its, slots = engine.record_slots(n_iterations, metrics_every)
    hist = {k: jax.ShapeDtypeStruct((len(record_its),), jnp.float32)
            for k in keys}
    masks = jax.ShapeDtypeStruct((n_iterations, hyper.n_workers),
                                 jnp.float32)
    slots = jax.ShapeDtypeStruct(slots.shape, slots.dtype)
    fn = engine._build_scan(problem, hyper, None, keys, donate=False)
    text = fn.lower(state, hist, None, None, masks, slots).compile() \
        .as_text()
    return dict(collections.Counter(
        m.group(1) for m in re.finditer(
            r"%([A-Za-z_]+)[.0-9]* = [^\n]*"
            r'custom_call_target="tpu_custom_call"', text)))


def phase_engine(n_iterations: int = 100, metrics_every: int = 25):
    import jax
    import numpy as np

    from repro.apps.robust_hpo import default_hyper, make_robust_hpo_problem
    from repro.core import RunSpec, StragglerConfig, run

    n, s_active, tau = 4, 3, 10          # examples/robust_hpo.py
    task = make_robust_hpo_problem("diabetes", n_workers=n, seed=0)
    sched = StragglerConfig(n_workers=n, s_active=s_active, tau=tau,
                            n_stragglers=1, straggler_slowdown=5.0, seed=0)
    runs = {}
    for fused in (False, True):
        hyper = default_hyper(task, n, s_active, tau,
                              use_fused_inner=fused)
        kernels = engine_mosaic_kernels(task.problem, hyper, n_iterations,
                                        metrics_every)
        require(kernels, f"engine (use_fused_inner={fused}): no Mosaic "
                         "kernel (tpu_custom_call) in the scan program")
        require(("fused_cut_round" in kernels) == fused,
                f"engine (use_fused_inner={fused}): the scan program's "
                f"Mosaic kernels are {sorted(kernels)}")
        t0 = time.perf_counter()
        res = run(RunSpec(problem=task.problem, hyper=hyper,
                          scheduler=sched, n_iterations=n_iterations,
                          metrics_every=metrics_every, engine="scan"))
        gaps = np.asarray(res.history["gap_sq"], np.float64)
        finite = bool(np.all(np.isfinite(gaps))) and all(
            bool(np.all(np.isfinite(np.asarray(x))))
            for x in jax.tree.leaves(res.state))
        emit(phase="engine", use_fused_inner=fused,
             mosaic_kernels=kernels,
             gap_first=float(gaps[0]), gap_last=float(gaps[-1]),
             n_cuts_ii=np.asarray(res.history["n_cuts_ii"]).tolist(),
             n_records=int(gaps.size), wall_s=time.perf_counter() - t0,
             finite=finite)
        require(finite, f"engine (use_fused_inner={fused}): non-finite "
                        "gap or state")
        runs[fused] = gaps
    agree = bool(np.allclose(runs[True], runs[False], rtol=GAP_RTOL,
                             atol=GAP_ATOL))
    rel = float(np.max(np.abs(runs[True] - runs[False])
                       / np.maximum(np.abs(runs[False]), 1e-30)))
    emit(phase="engine", check="fused_vs_unfused", max_gap_rel_diff=rel,
         rtol=GAP_RTOL, atol=GAP_ATOL, ok=agree)
    require(agree, "engine: fused and unfused gap histories disagree")


# ---------------------------------------------------------------------------
# (c) federation runtime
# ---------------------------------------------------------------------------

def phase_runtime():
    from repro.launch import serve

    argv = ["fed", "--problem", "quadratic", "--workers", "2",
            "--iters", "40", "--transport", "inproc"]
    t0 = time.perf_counter()
    rc = serve.main(argv)
    emit(phase="runtime", argv=argv, rc=rc,
         wall_s=time.perf_counter() - t0, ok=rc == 0)
    require(rc == 0, f"serve {' '.join(argv)} exited {rc}")


# ---------------------------------------------------------------------------
# (d) trainer at full width
# ---------------------------------------------------------------------------

def run_trainer(argv):
    """`train.main(argv)`; returns (history, wall seconds)."""
    from repro.launch import train

    t0 = time.perf_counter()
    hist = train.main(argv)["history"]
    return hist, time.perf_counter() - t0


def phase_trainer(argv=TRAIN_ARGV):
    import jax

    hist, wall = run_trainer(argv)
    losses = [h["loss"] for h in hist]
    ends = [h["host_s"] for h in hist]
    chunk_s = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    # --log-every 1: one dispatch per step; the refresh runs after the
    # step at every even step, so steps 3 (plain) and 2/4 (with a
    # refresh) are the steady ones, and step 1 carries the compile
    step_s = chunk_s[2]
    refresh_step_s = (chunk_s[1] + chunk_s[3]) / 2
    stats = jax.devices()[0].memory_stats() or {}
    emit(phase="trainer_memory",
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))
    finite = len(losses) == 4 and all(math.isfinite(x) for x in losses)
    refreshed = hist[-1]["cuts"] > 0
    emit(phase="trainer", argv=argv, losses=losses, cuts=hist[-1]["cuts"],
         first_step_s=chunk_s[0], compile_s=chunk_s[0] - step_s,
         steady_step_s=step_s, steady_step_with_refresh_s=refresh_step_s,
         wall_s=wall, ok=finite and refreshed)
    require(finite, f"trainer: expected 4 finite losses, got {losses}")
    require(refreshed, "trainer: no cut refresh added a cut")


# ---------------------------------------------------------------------------
# four chips: the worker mesh
# ---------------------------------------------------------------------------

def phase_mesh_engine(n_shards: int = 4, n_iterations: int = 200):
    import jax
    import jax.numpy as jnp

    from benchmarks.engine_speed import quickstart_setup
    from repro.core import run_scanned
    from repro.launch.mesh import make_worker_mesh

    mesh = make_worker_mesh(n_shards)
    spread = len({d.id for d in mesh.devices.flat})
    require(spread == n_shards,
            f"worker mesh covers {spread} devices, not {n_shards}")
    problem, hyper, _, schedule = quickstart_setup(n_iterations)
    me = max(1, n_iterations // 10)
    ref = run_scanned(problem, hyper, schedule, metrics_every=me)
    t0 = time.perf_counter()
    sh = run_scanned(problem, hyper, schedule, metrics_every=me, mesh=mesh)
    wall = time.perf_counter() - t0
    match = all(bool(jnp.allclose(a, b, rtol=STATE_RTOL, atol=STATE_ATOL))
                for a, b in zip(jax.tree.leaves(ref.state),
                                jax.tree.leaves(sh.state)))
    emit(phase="mesh_engine", n_shards=n_shards, mesh_devices=spread,
         gap_last_single=float(ref.history["gap_sq"][-1]),
         gap_last_sharded=float(sh.history["gap_sq"][-1]),
         sharded_wall_s=wall, states_allclose=match, ok=match)
    require(match, "sharded scan state differs from the single-device run")


def mesh_trainer_config(arch: str):
    """The model for the four-chip trainer check: full width (d_model,
    heads, vocab), depth cut to the first block of the stack (an mLSTM
    block for xlstm-125m), in f32 so that one SGD update is resolved
    and not a bf16 rounding step.  The mesh splits workers, not the
    model, so depth changes what each device computes, not what
    crosses chips; it is cut to keep the four compiles short."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.config import Stage

    cfg = get_config(arch)
    first = cfg.stages[0].pattern[:1]
    return dataclasses.replace(cfg, n_layers=1, stages=(Stage(first, 1),),
                               dtype="float32").validate()


def update_rel_err(old, ref, got):
    """Worst leaf of ||got - ref|| / ||ref - old||: how far the sharded
    transition is from the unsharded one, against the unsharded
    transition itself.  Returns (error, leaf path)."""
    import jax
    import numpy as np

    worst, where = 0.0, ""
    for (path, o), r, g in zip(jax.tree_util.tree_leaves_with_path(old),
                               jax.tree.leaves(ref), jax.tree.leaves(got)):
        o, r, g = (np.asarray(x, np.float64) for x in (o, r, g))
        num, den = np.linalg.norm(g - r), np.linalg.norm(r - o)
        err = (0.0 if num == 0 else math.inf) if den == 0 \
            else float(num / den)
        if not err <= worst:
            worst, where = err, jax.tree_util.keystr(path)
    return worst, where


def phase_mesh_trainer(n_shards: int = 4, cfg=None):
    """The trainer's worker mesh against one device, a transition at a
    time: from the same state, take an AFTO step, a cut refresh and a
    step that evaluates the new cut, sharded over the trainer's
    `--mesh-workers` placement and unsharded, and compare the results.
    Comparing single transitions keeps the summation order's effect at
    f32 rounding instead of letting later steps amplify it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import stream as stream_lib
    from repro.fed.trilevel_llm import (afto_llm_step, batch_stream,
                                        cut_refresh_llm)
    from repro.launch import train

    args = train.parse_args(MESH_TRAIN_ARGV
                            + ["--mesh-workers", str(n_shards)])
    cfg = cfg or mesh_trainer_config(args.arch)
    hyper, state, sched, val_loss = train._afto_setup(cfg, args)
    state = jax.tree.map(jnp.array, state)
    mesh, _, _, named = train._worker_mesh_put(state, n_shards)
    spread = len({d.id for d in mesh.devices.flat})
    require(spread == n_shards,
            f"worker mesh covers {spread} devices, not {n_shards}")
    by_worker = NamedSharding(mesh, P("data"))
    stream = batch_stream(cfg, args.workers, args.batch, args.seq,
                          seed=args.seed)
    key = jnp.asarray(stream.key)
    masks = jnp.asarray(sched.precompute(2).active, jnp.float32)

    def step(st, batch, mask):
        return afto_llm_step(cfg, hyper, st, batch, mask)

    def refresh(st, batch):
        return cut_refresh_llm(cfg, hyper, st, batch)

    single = {"step": jax.jit(step), "refresh": jax.jit(refresh)}
    sharded = {"step": jax.jit(step, out_shardings=named),
               "refresh": jax.jit(refresh, out_shardings=named)}

    def loss(st, batch):
        w = jax.tree.map(lambda x: x[0], st.X3)
        return float(val_loss(w, batch["tokens"][0]))

    for name, it in (("step", 0), ("refresh", 0), ("step", 1)):
        batch = stream_lib.batch_at(stream.spec, key, jnp.int32(it))
        ins = (batch, masks[it]) if name == "step" else (batch,)
        t0 = time.perf_counter()
        ref = jax.block_until_ready(single[name](state, *ins))
        t1 = time.perf_counter()
        got = jax.block_until_ready(sharded[name](
            jax.device_put(state, named),
            *jax.device_put(ins, (by_worker,) * len(ins))))
        t2 = time.perf_counter()
        err, where = update_rel_err(state, ref, got)
        l_ref, l_got = loss(ref, batch), loss(got, batch)
        l_rel = abs(l_got - l_ref) / abs(l_ref)
        cuts = float(jnp.sum(ref.cuts.active))
        ok = bool(err <= UPDATE_RTOL and math.isfinite(l_ref)
                  and l_rel <= LOSS_RTOL
                  and cuts == float(jnp.sum(got.cuts.active)))
        emit(phase="mesh_trainer", transition=name, iteration=it,
             n_shards=n_shards, d_model=cfg.d_model, n_layers=cfg.n_layers,
             update_rel_err=err, worst_leaf=where, rtol=UPDATE_RTOL,
             loss_single=l_ref, loss_sharded=l_got, loss_rel_diff=l_rel,
             loss_rtol=LOSS_RTOL, cuts=cuts, single_s=t1 - t0,
             sharded_s=t2 - t1, ok=ok)
        require(ok, f"sharded trainer {name} at iteration {it}: update "
                    f"off by {err} at {where} (bound {UPDATE_RTOL}), loss "
                    f"off by {l_rel}, or another cut count")
        state = ref
    require(cuts > 0, "the refresh added no cut")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the worker-mesh phases on 4 chips")
    args = ap.parse_args(argv)

    _import_repo()
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = require_tpu(4 if args.four_chips else 1)
    import jax

    emit(phase="setup", jax=jax.__version__, cache_dir=cache_dir,
         cache_entries_before=len(os.listdir(cache_dir))
         if os.path.isdir(cache_dir) else 0)
    t0 = time.perf_counter()
    phases = ([phase_mesh_engine, phase_mesh_trainer] if args.four_chips
              else [phase_kernels, phase_engine, phase_runtime,
                    phase_trainer])
    for phase in phases:
        phase()
    emit(phase="done", wall_s=time.perf_counter() - t0)
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
