"""CI elastic-membership gate: late workers join mid-run, stay exact.

Two phases, both fatal on failure:

  1. DETERMINISTIC ELASTIC CHAOS (in-process).  A 3-worker run admits
     two late workers (ids 3 and 4) mid-run through the real
     ADMIT/WELCOME boundary protocol.  The recorded Schedule must be
     WIDENED (a `width` column), both newcomers must contribute
     consumed pushes, the widened trajectory must replay BIT-EXACTLY
     through the segmented engine (`run_scanned_elastic`) AND through a
     fresh `Master(replay=...)` population, and a fixed-membership
     control run with the elastic machinery enabled-but-unused must be
     bitwise identical to one without it.

  2. REAL TCP ADMISSION (subprocesses).  A master over sockets launches
     with two worker subprocesses and `--max-workers`-style headroom; a
     third worker subprocess (`--worker 2`, beyond the launch
     population) connects mid-run and must be admitted, grow the run to
     width 3, and contribute to the quorum.  Worker 0 is then SIGKILLed
     and respawned (the reconnect path sharing the elastic accept
     loop).  Gates: the widened Schedule replays through the segmented
     engine, the gap decreases, and the master endpoint's reader-thread
     list stays pruned (no one-dead-Thread-per-rejoin leak).

  PYTHONPATH=src python -m benchmarks.elastic_runtime_smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time


def _rel_err(a, b):
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-8)))


def phase_inproc_elastic() -> dict:
    import numpy as np

    from repro.fed.runtime import problems as problems_lib
    from repro.fed.runtime import run_async
    from repro.fed.runtime.chaos import ChaosScript, run_chaos_async
    from repro.fed.runtime.membership import (FaultConfig,
                                              run_scanned_elastic)

    elastic = problems_lib.elastic_config("quadratic", 5)
    build = lambda n: problems_lib.build("quadratic", n_workers=n)  # noqa: E731
    problem, hyper = build(3)
    fault = FaultConfig(heartbeat_every=0.02, resend_every=0.1,
                        refresh_resend_every=0.1, death_timeout=2.0,
                        poll_interval=0.005, min_iter_time=0.02)

    res = run_chaos_async(problem, hyper, ChaosScript(),
                          n_iterations=24, fault=fault, elastic=elastic,
                          admit_at=((3, 0.15), (4, 0.3)))
    rec = res.arrivals
    assert rec.width is not None, "admission never widened the schedule"
    assert int(rec.width[0]) == 3 and int(rec.width[-1]) == 5, \
        rec.width.tolist()
    for j in (3, 4):
        assert float(rec.active[:, j].sum()) > 0, \
            f"late worker {j} never contributed to the quorum"
    gaps = res.history["gap_sq"]
    assert gaps[-1] < gaps[0], f"elastic run not decreasing: {gaps}"

    # the widened Schedule must replay bit-exactly: segmented engine...
    echo = run_scanned_elastic(build, rec, metrics_every=10)
    assert np.array_equal(np.asarray(res.history["gap_sq"]),
                          np.asarray(echo.history["gap_sq"])), \
        "segmented engine replay is not bitwise"
    assert np.array_equal(np.asarray(res.state.X1),
                          np.asarray(echo.state.X1))
    # ...and a fresh master population replaying the same Schedule
    res2 = run_async(problem, hyper, n_iterations=24, replay=rec,
                     fault=fault, elastic=elastic)
    assert np.array_equal(np.asarray(res2.state.X1),
                          np.asarray(res.state.X1)), \
        "Master(replay=...) of the widened schedule is not bitwise"

    # fixed-membership conformance: elastic enabled-but-unused must not
    # perturb a run (bitwise — the elastic code paths are boundary-only)
    from repro.core.scheduler import StragglerConfig, StragglerScheduler
    sched = StragglerScheduler(StragglerConfig(
        n_workers=3, s_active=hyper.s_active, tau=hyper.tau,
        seed=7)).precompute(20)
    base = run_async(problem, hyper, n_iterations=20, replay=sched,
                     fault=fault)
    gated = run_async(problem, hyper, n_iterations=20, replay=sched,
                      fault=fault, elastic=elastic)
    assert np.array_equal(np.asarray(base.state.X1),
                          np.asarray(gated.state.X1)), \
        "elastic-enabled fixed-membership run diverged from control"
    assert gated.arrivals.width is None

    return {"width": [int(w) for w in (rec.width[0], rec.width[-1])],
            "newcomer_pushes": [float(rec.active[:, j].sum())
                                for j in (3, 4)],
            "gap_first": float(gaps[0]), "gap_last": float(gaps[-1])}


def phase_tcp_admission(n_iterations: int = 90) -> dict:
    import os
    import subprocess

    import numpy as np

    from repro.fed.runtime import problems as problems_lib
    from repro.fed.runtime import run_async
    from repro.fed.runtime.membership import (FaultConfig,
                                              run_scanned_elastic)
    from repro.fed.runtime.transport import TcpTransport
    from repro.launch.serve import spawn_tcp_workers

    args = argparse.Namespace(problem="quadratic", workers=2, dim=3,
                              seed=0)
    build = lambda n: problems_lib.build(  # noqa: E731
        args.problem, n_workers=n, dim=args.dim, seed=args.seed)
    problem, hyper = build(args.workers)
    elastic = problems_lib.elastic_config(args.problem, 4, dim=args.dim,
                                          seed=args.seed)
    transport = TcpTransport(args.workers, port=0, max_workers=4)
    ep = transport.master_endpoint()
    procs = spawn_tcp_workers(args, transport.port)

    def spawn(worker: int, epoch: int = 0):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"   # the parent holds the accelerator
        src_root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env["PYTHONPATH"] = (src_root + os.pathsep
                             + env.get("PYTHONPATH", ""))
        return subprocess.Popen(
            [sys.executable, "-m", "repro.fed.runtime.worker",
             "--problem", args.problem, "--worker", str(worker),
             "--port", str(transport.port),
             "--n-workers", str(args.workers), "--dim", str(args.dim),
             "--seed", str(args.seed), "--epoch", str(epoch)], env=env)

    fault = FaultConfig(heartbeat_every=0.05, resend_every=0.2,
                        refresh_resend_every=0.2, death_timeout=5.0,
                        poll_interval=0.01, min_iter_time=0.12)
    marks = {}

    def watcher(master):
        def wait(cond, key):
            while not cond() and not master.status["done"]:
                time.sleep(0.05)
            marks[key] = master.status["t"]

        wait(lambda: master.status["t"] >= 5, "late_spawn_at")
        procs.append(spawn(2))             # --worker 2 > --workers 2
        wait(lambda: master.hyper.n_workers >= 3, "admitted_at")
        procs[0].kill()
        wait(lambda: master.status["deaths"] >= 1, "death_at")
        procs.append(spawn(0, epoch=1))
        wait(lambda: master.status["rejoins"] >= 1, "rejoin_at")
        # the thread-leak gate: reader threads of replaced sessions are
        # pruned on install — 3 live readers + the accept loop + at
        # most a couple not-yet-reaped corpses, never one per rejoin
        marks["n_threads"] = len(ep._threads)
        marks["status"] = dict(master.status)

    def hook(master):
        threading.Thread(target=watcher, args=(master,),
                         daemon=True).start()

    try:
        res = run_async(problem, hyper, n_iterations=n_iterations,
                        metrics_every=10, transport=transport,
                        master_hook=hook, fault=fault, elastic=elastic,
                        accept_timeout=120.0)
    finally:
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:
                p.kill()

    st = marks.get("status", {})
    assert st.get("n_workers", 0) == 3, \
        f"late worker never admitted: {marks}"
    assert st.get("deaths", 0) >= 1, f"kill never surfaced: {marks}"
    assert st.get("rejoins", 0) >= 1, f"respawn never rejoined: {marks}"
    assert marks.get("n_threads", 99) <= 6, \
        f"reader-thread leak: {marks.get('n_threads')} threads retained"
    rec = res.arrivals
    assert rec.width is not None and int(rec.width[-1]) == 3, \
        "TCP admission did not widen the recorded schedule"
    assert float(rec.active[:, 2].sum()) > 0, \
        "admitted worker never contributed to the quorum"
    gaps = res.history["gap_sq"]
    assert gaps[-1] < gaps[0], f"widened run not decreasing: {gaps}"
    max_stale = int(rec.max_staleness.max())

    echo = run_scanned_elastic(build, rec, metrics_every=10)
    err = _rel_err(res.history["gap_sq"], echo.history["gap_sq"])
    assert err < 2e-5, f"widened-schedule replay broken: {err}"
    assert np.array_equal(np.asarray(res.state.X1),
                          np.asarray(echo.state.X1)), \
        "widened-schedule replay is not bitwise on the carry"
    return {"late_spawn_at": marks.get("late_spawn_at"),
            "admitted_at": marks.get("admitted_at"),
            "death_at": marks.get("death_at"),
            "rejoin_at": marks.get("rejoin_at"),
            "n_threads": marks.get("n_threads"),
            "newcomer_pushes": float(rec.active[:, 2].sum()),
            "max_staleness": max_stale, "replay_rel_err": err,
            "gap_first": float(gaps[0]), "gap_last": float(gaps[-1])}


def main() -> dict:
    return {"inproc_elastic": phase_inproc_elastic(),
            "tcp_admission": phase_tcp_admission()}


if __name__ == "__main__":
    rec = main()
    json.dump(rec, sys.stdout, indent=1)
    print()
    print("elastic runtime smoke: OK")
