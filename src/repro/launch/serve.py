"""Serving front end: batched decode + the async federation runtime.

Two subcommands:

  decode   the batched LLM serving driver (prefill + N decode steps):
      PYTHONPATH=src python -m repro.launch.serve decode \
          --arch llama3-8b --reduced --batch 4 --prompt-len 64 --gen 32
      (a bare flag invocation without a subcommand still routes here —
      the historical CLI surface.)

  fed      launch an async federation run — one master plus N workers
           over the in-process transport (threads) or TCP (real worker
           subprocesses) — streaming per-record status lines and an
           optional HTTP status endpoint:
      PYTHONPATH=src python -m repro.launch.serve fed \
          --problem quadratic --workers 2 --iters 60 --transport tcp
      GET /status on --status-port (0 picks an ephemeral port) returns
      the master's live counters as JSON (including the recent arrival
      rows).  Exits nonzero unless the stationarity gap decreased over
      the run — the end-to-end convergence gate the CI smoke step
      drives.  `--stream` runs on streamed data (workers synthesize
      their own batches) and additionally gates the recorded schedule's
      replay through the compiled engine; `--adapt-arrivals` turns on
      the closed-loop arrival policy.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.data.synthetic import make_token_stream
from repro.models import transformer as tfm


# ---------------------------------------------------------------------------
# decode: the batched serving driver
# ---------------------------------------------------------------------------

def serve(cfg, batch: int, prompt_len: int, gen: int, seed: int = 0,
          greedy: bool = True):
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = jnp.asarray(make_token_stream(cfg.vocab_size, batch,
                                            prompt_len, seed=seed))
    frames = None
    if cfg.frontend == "frames":
        frames = jax.random.normal(
            jax.random.PRNGKey(seed + 1),
            (batch, cfg.encoder_seq, cfg.d_model)).astype(jnp.bfloat16)

    prefill = jax.jit(lambda p, tk: tfm.prefill(
        cfg, p, tk, frames, max_seq=prompt_len + gen + 1))
    decode = jax.jit(lambda p, c, tk, pos: tfm.decode_step(cfg, p, c, tk,
                                                           pos))
    t0 = time.time()
    logits, caches = prefill(params, prompts)
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    t_prefill = time.time() - t0

    out_tokens = [nxt]
    t0 = time.time()
    for i in range(gen - 1):
        pos = jnp.full((batch,), prompt_len + i, jnp.int32)
        logits, caches = decode(params, caches, nxt, pos)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out_tokens.append(nxt)
    jax.block_until_ready(nxt)
    t_decode = time.time() - t0
    gen_ids = jnp.concatenate(out_tokens, axis=1)
    return {"prefill_s": t_prefill,
            "decode_s": t_decode,
            "tok_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
            "generated": np.asarray(gen_ids)}


def main_decode(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="serve decode")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    print(f"serving {cfg.name}: batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen}")
    res = serve(cfg, args.batch, args.prompt_len, args.gen, args.seed)
    print(f"prefill {res['prefill_s']:.2f}s, decode {res['decode_s']:.2f}s"
          f" ({res['tok_per_s']:.1f} tok/s)")
    print("first generations:", res["generated"][:2, :16].tolist())
    return 0


# ---------------------------------------------------------------------------
# fed: master + N workers over a live transport
# ---------------------------------------------------------------------------

def start_status_server(master, port: int):
    """Serve `master.status` as JSON on GET /status (daemon thread);
    returns the HTTPServer (read the bound port off `.server_address`)."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.rstrip("/") not in ("", "/status"):
                self.send_error(404)
                return
            body = json.dumps(master.status).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):   # stay quiet on the run's stdout
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def spawn_tcp_workers(args, port: int):
    """One `repro.fed.runtime.worker` subprocess per worker id, pointed
    at the master's bound port (each rebuilds the problem by name).
    Workers run on the host CPU: the master's process holds the
    accelerator, and a child that reached for it would fail or hang."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    base = [sys.executable, "-m", "repro.fed.runtime.worker",
            "--problem", args.problem,
            "--port", str(port), "--n-workers", str(args.workers),
            "--dim", str(args.dim), "--seed", str(args.seed)]
    # getattr: callers like the chaos smoke hand-build a minimal args
    # namespace that predates the streaming flags
    if getattr(args, "stream", False):
        base.append("--stream")   # each worker rebuilds the same Stream
    return [subprocess.Popen(base + ["--worker", str(j)], env=env)
            for j in range(args.workers)]


def run_fed(args):
    """Launch the run described by parsed `fed` args; returns
    (RunResult, status_server | None)."""
    from repro.core.scheduler import ArrivalPolicy
    from repro.fed.runtime import problems as problems_lib
    from repro.fed.runtime import run_async
    from repro.fed.runtime.membership import FaultConfig
    from repro.fed.runtime.transport import TcpTransport

    problem, hyper = problems_lib.build(
        args.problem, n_workers=args.workers, dim=args.dim,
        seed=args.seed)
    stream = None
    if args.stream:
        # TCP subprocess workers rebuild this identical Stream by name
        stream = problems_lib.build_stream(
            args.problem, n_workers=args.workers, dim=args.dim,
            seed=args.seed)
    policy = None
    if args.adapt_arrivals:
        policy = ArrivalPolicy(s_active=hyper.s_active, tau=hyper.tau)
    elastic = None
    max_workers = getattr(args, "max_workers", 0)
    if max_workers > args.workers:
        # accept ADMITs from ids [workers, max_workers): a late worker
        # (`--worker J` with J >= --workers) joins mid-run at the next
        # iteration boundary
        elastic = problems_lib.elastic_config(
            args.problem, max_workers, dim=args.dim, seed=args.seed,
            stream=bool(args.stream))

    transport, procs = None, []
    if args.transport == "tcp":
        transport = TcpTransport(args.workers, port=args.port,
                                 max_workers=max(max_workers,
                                                 args.workers))
        transport.master_endpoint()          # bind before spawning
        print(f"master listening on 127.0.0.1:{transport.port}")
        procs = spawn_tcp_workers(args, transport.port)

    fault = FaultConfig(
        death_timeout=args.death_timeout,
        min_iter_time=args.min_iter_time)
    status_server = None

    def hook(master):
        nonlocal status_server
        if args.status_port >= 0:
            status_server = start_status_server(master, args.status_port)
            print(f"status endpoint: http://127.0.0.1:"
                  f"{status_server.server_address[1]}/status")

    try:
        result = run_async(
            problem, hyper, n_iterations=args.iters,
            metrics_every=args.metrics_every, transport=transport,
            data=stream, policy=policy,
            master_hook=hook, fault=fault,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            resume=args.resume, elastic=elastic,
            accept_timeout=(args.accept_timeout
                            if args.accept_timeout > 0 else None))
    finally:
        for p in procs:
            p.wait(timeout=60)
    return result, status_server


def main_fed(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="serve fed")
    ap.add_argument("--problem", default="quadratic")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--dim", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--metrics-every", type=int, default=10)
    ap.add_argument("--transport", choices=("inproc", "tcp"),
                    default="inproc")
    ap.add_argument("--max-workers", type=int, default=0,
                    help="accept elastic ADMITs for worker ids up to "
                         "this population cap (0 = fixed membership); "
                         "late workers connect with --worker >= "
                         "--workers and join at the next iteration "
                         "boundary")
    ap.add_argument("--port", type=int, default=0,
                    help="TCP master port (0 = ephemeral)")
    ap.add_argument("--status-port", type=int, default=-1,
                    help="HTTP status port (0 = ephemeral, -1 = off)")
    ap.add_argument("--accept-timeout", type=float, default=0.0,
                    help="seconds to wait for the full worker population "
                         "at launch (0 = wait forever)")
    ap.add_argument("--death-timeout", type=float, default=10.0,
                    help="seconds of silence before a worker is "
                         "declared dead")
    ap.add_argument("--min-iter-time", type=float, default=0.0,
                    help="master pacing floor per iteration (seconds); "
                         "the chaos smoke uses it to keep a run alive "
                         "long enough to kill and respawn a worker")
    ap.add_argument("--ckpt-dir", default=None,
                    help="directory for durable master checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint the master carry every K "
                         "iterations (0 = off)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from --ckpt-dir "
                         "before running")
    ap.add_argument("--stream", action="store_true",
                    help="streamed data: workers synthesize their own "
                         "batch at the refresh's master iteration; the "
                         "run exits nonzero unless the recorded "
                         "schedule replays through run_scanned")
    ap.add_argument("--adapt-arrivals", action="store_true",
                    help="close the arrival loop: an ArrivalPolicy "
                         "adapts the effective (s, tau) per iteration "
                         "inside the paper's tau bound")
    args = ap.parse_args(argv)

    result, status_server = run_fed(args)
    for i, t in enumerate(result.history["t"]):
        print(json.dumps({
            "t": int(t),
            "gap_sq": result.history["gap_sq"][i],
            "n_cuts_ii": result.history["n_cuts_ii"][i],
            "max_staleness": result.history["max_staleness"][i]}))
    if status_server is not None:
        status_server.shutdown()

    gaps = result.history["gap_sq"]
    # Streamed runs measure the gap on a FRESH batch at each record
    # point, so a first-vs-last decrease is batch noise, not a
    # convergence signal — their gate is the exact-replay echo below.
    decreasing = bool(args.stream) or gaps[-1] < gaps[0]
    max_stale = int(result.arrivals.max_staleness.max())
    stale_ok = max_stale <= _problem_tau(args)
    trend = ("streamed (per-batch)" if args.stream
             else "decreasing" if decreasing else "NOT decreasing")
    print(f"gap {gaps[0]:.4f} -> {gaps[-1]:.4f} ({trend}); "
          f"max recorded staleness {max_stale} "
          f"(tau bound {'ok' if stale_ok else 'VIOLATED'})")
    replay_ok = True
    if args.stream:
        replay_ok = _streamed_replay_gate(args, result)
    return 0 if (decreasing and stale_ok and replay_ok) else 1


def _streamed_replay_gate(args, result) -> bool:
    """Echo a streamed run's recorded Schedule through `run_scanned`
    with the rebuilt Stream and gate the gap history at rel err 1e-5.
    The echo is a different XLA compilation context (batch synthesis
    fuses into the scan body), so the floor is ~1e-7 ulp noise, not 0.0
    — the bitwise contract is runtime replay (`Master(replay=...)`),
    pinned in tests/test_runtime.py."""
    from repro.core.engine import run_scanned
    from repro.fed.runtime import problems as problems_lib
    from repro.fed.runtime.membership import run_scanned_elastic

    if result.arrivals.width is not None:
        # a widened (elastic) run echoes through the segmented replay:
        # the engine runs each constant-width segment at its own width
        ref = run_scanned_elastic(
            lambda n: problems_lib.build(
                args.problem, n_workers=n, dim=args.dim, seed=args.seed),
            result.arrivals, metrics_every=args.metrics_every,
            build_stream=lambda n: problems_lib.build_stream(
                args.problem, n_workers=n, dim=args.dim, seed=args.seed))
    else:
        problem, hyper = problems_lib.build(
            args.problem, n_workers=args.workers, dim=args.dim,
            seed=args.seed)
        stream = problems_lib.build_stream(
            args.problem, n_workers=args.workers, dim=args.dim,
            seed=args.seed)
        ref = run_scanned(problem, hyper, result.arrivals,
                          metrics_every=args.metrics_every, data=stream)
    live = np.asarray(result.history["gap_sq"], np.float64)
    echo = np.asarray(ref.history["gap_sq"], np.float64)
    if live.shape != echo.shape:
        print(f"streamed replay gate: history shape mismatch "
              f"{live.shape} vs {echo.shape}")
        return False
    rel = float(np.max(np.abs(live - echo) /
                       np.maximum(np.abs(echo), 1e-30)))
    ok = rel <= 1e-5
    print(f"streamed replay gate: max gap rel err {rel:.3e} "
          f"({'ok' if ok else 'EXCEEDS 1e-5'})")
    return ok


def _problem_tau(args) -> int:
    from repro.fed.runtime import problems as problems_lib
    _, hyper = problems_lib.build(args.problem, n_workers=args.workers,
                                  dim=args.dim, seed=args.seed)
    return hyper.tau


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # historical CLI surface: a bare flag invocation is `decode`
    if not argv or argv[0] not in ("decode", "fed"):
        argv = ["decode"] + argv
    if argv[0] == "decode":
        return main_decode(argv[1:])
    return main_fed(argv[1:])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
