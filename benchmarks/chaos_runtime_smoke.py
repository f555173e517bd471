"""CI chaos gate: the runtime survives worker churn and stays exact.

Two phases, both fatal on failure:

  1. DETERMINISTIC CHAOS (in-process).  A seeded `ChaosScript` drops,
     duplicates, delays and mid-frame-cuts protocol frames AND crashes
     one worker mid-run (supervised back to life with a bumped resume
     epoch).  The master must complete every iteration, converge, keep
     the recorded staleness inside tau among live workers, record the
     degradation window, and the degraded arrival `Schedule` must
     replay through `run_scanned` back to the chaos run's trajectory.

  2. REAL PROCESS KILL (TCP).  A master over sockets with two worker
     subprocesses; mid-run, worker 0 is SIGKILLed.  The master must
     surface the death (reader DISCONNECT, not a hang), degrade onto
     the survivor, re-admit a respawned worker 0 (`--epoch 1`), finish
     with a decreasing gap, and its recorded Schedule must again
     replay through the scanned engine.

  PYTHONPATH=src python -m benchmarks.chaos_runtime_smoke
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


def phase_deterministic_chaos() -> dict:
    from repro.core import run_scanned
    from repro.fed.runtime import problems as problems_lib
    from repro.fed.runtime.chaos import ChaosScript, run_chaos_async
    from repro.fed.runtime.membership import FaultConfig

    problem, hyper = problems_lib.build("quadratic", n_workers=4)
    script = ChaosScript(seed=5, drop_p=0.08, dup_p=0.08, delay_p=0.10,
                         delay_s=0.002, cut_p=0.04,
                         crash_at_push=((2, 3),))
    fault = FaultConfig(heartbeat_every=0.02, resend_every=0.08,
                        refresh_resend_every=0.08, death_timeout=0.6,
                        poll_interval=0.005, min_iter_time=0.04)
    captured = {}
    res = run_chaos_async(problem, hyper, script, n_iterations=30,
                          fault=fault, restart_delay=0.15,
                          metrics_every=10,
                          master_hook=lambda m: captured.update(m=m))
    status = captured["m"].status
    rec = res.arrivals
    assert rec.n_iterations == 30, "chaos master did not finish"
    assert status["deaths"] >= 1, status
    assert status["rejoins"] >= 1, status
    assert rec.dead is not None and float(rec.dead[:, 2].max()) == 1.0, \
        "degradation window not recorded"
    gaps = res.history["gap_sq"]
    assert gaps[-1] < gaps[0], f"chaos run not decreasing: {gaps}"
    max_stale = int(rec.max_staleness.max())
    assert max_stale <= hyper.tau, (max_stale, hyper.tau)

    echo = run_scanned(problem, hyper, rec, metrics_every=10)
    err = _rel_err(res.history["gap_sq"], echo.history["gap_sq"])
    assert err < 2e-5, f"degraded-schedule replay broken: {err}"
    return {"deaths": status["deaths"], "rejoins": status["rejoins"],
            "dead_iterations": int(rec.dead[:, 2].sum()),
            "corrupt_frames": status["corrupt_frames"],
            "max_staleness": max_stale, "replay_rel_err": err,
            "gap_first": float(gaps[0]), "gap_last": float(gaps[-1])}


def phase_tcp_kill_and_rejoin(n_iterations: int = 90) -> dict:
    import os
    import subprocess

    from repro.core import run_scanned
    from repro.fed.runtime import problems as problems_lib
    from repro.fed.runtime import run_async
    from repro.fed.runtime.membership import FaultConfig
    from repro.fed.runtime.transport import TcpTransport
    from repro.launch.serve import spawn_tcp_workers

    args = argparse.Namespace(problem="quadratic", workers=2, dim=3,
                              seed=0)
    problem, hyper = problems_lib.build(
        args.problem, n_workers=args.workers, dim=args.dim,
        seed=args.seed)
    transport = TcpTransport(args.workers, port=0)
    transport.master_endpoint()
    procs = spawn_tcp_workers(args, transport.port)

    def respawn_worker0():
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"   # the parent holds the accelerator
        src_root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env["PYTHONPATH"] = (src_root + os.pathsep
                             + env.get("PYTHONPATH", ""))
        return subprocess.Popen(
            [sys.executable, "-m", "repro.fed.runtime.worker",
             "--problem", args.problem, "--worker", "0",
             "--port", str(transport.port),
             "--n-workers", str(args.workers), "--dim", str(args.dim),
             "--seed", str(args.seed), "--epoch", "1"], env=env)

    # pace the master so the kill -> respawn cycle (subprocess startup
    # is seconds) lands inside the run instead of after it
    fault = FaultConfig(heartbeat_every=0.05, resend_every=0.2,
                        refresh_resend_every=0.2, death_timeout=5.0,
                        poll_interval=0.01, min_iter_time=0.12)
    marks = {}

    def watcher(master):
        def wait(cond, key):
            while not cond() and not master.status["done"]:
                time.sleep(0.05)
            marks[key] = master.status["t"]

        wait(lambda: master.status["t"] >= 5, "armed_at")
        procs[0].kill()
        wait(lambda: master.status["deaths"] >= 1, "death_at")
        procs.append(respawn_worker0())
        wait(lambda: master.status["rejoins"] >= 1, "rejoin_at")
        marks["status"] = dict(master.status)

    def hook(master):
        threading.Thread(target=watcher, args=(master,),
                         daemon=True).start()

    try:
        res = run_async(problem, hyper, n_iterations=n_iterations,
                        metrics_every=10, transport=transport,
                        master_hook=hook, fault=fault,
                        accept_timeout=120.0)
    finally:
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:
                p.kill()

    st = marks.get("status", {})
    assert st.get("deaths", 0) >= 1, f"kill never surfaced: {marks}"
    assert st.get("rejoins", 0) >= 1, f"respawn never rejoined: {marks}"
    rec = res.arrivals
    assert rec.dead is not None and float(rec.dead[:, 0].max()) == 1.0, \
        "degradation window not recorded"
    gaps = res.history["gap_sq"]
    assert gaps[-1] < gaps[0], f"degraded run not decreasing: {gaps}"
    max_stale = int(rec.max_staleness.max())
    assert max_stale <= hyper.tau, (max_stale, hyper.tau)

    echo = run_scanned(problem, hyper, rec, metrics_every=10)
    err = _rel_err(res.history["gap_sq"], echo.history["gap_sq"])
    assert err < 2e-5, f"degraded-schedule replay broken: {err}"
    return {"killed_at": marks.get("armed_at"),
            "death_at": marks.get("death_at"),
            "rejoin_at": marks.get("rejoin_at"),
            "dead_iterations": int(rec.dead[:, 0].sum()),
            "max_staleness": max_stale, "replay_rel_err": err,
            "gap_first": float(gaps[0]), "gap_last": float(gaps[-1])}


def main() -> dict:
    return {"deterministic_chaos": phase_deterministic_chaos(),
            "tcp_kill_rejoin": phase_tcp_kill_and_rejoin()}


if __name__ == "__main__":
    rec = main()
    json.dump(rec, sys.stdout, indent=1)
    print()
    print("chaos runtime smoke: OK")
