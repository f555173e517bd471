"""End-to-end training driver (deliverable b).

Trains a model of the zoo at full width (or its `--reduced` variant)
with the federated trilevel AFTO step — or plain AdamW for comparison —
on synthetic token streams, with checkpointing and loss logging.  It
runs on the default JAX backend: the full-width xlstm-125m step fits one
TPU v5e (`chip_smoke.py` drives it there); `--reduced` is the CPU size.

The default `--engine scan` drives `--scan-chunk`-sized chunks of the
trajectory (default: `--log-every`, keeping the old behavior) inside
one donated-buffer `lax.scan` over a precomputed straggler schedule
(one XLA dispatch per chunk instead of one per master iteration);
`--engine eager` keeps the per-step host loop.

`--stream` makes the scan DEVICE-RESIDENT end to end: worker token
batches are synthesized inside the scan body from fold-in PRNG keys
(`repro.fed.trilevel_llm.batch_stream`), the base key and the chunk
cursor ride the donated carry across chunk dispatches, and the whole
schedule's masks live on the device — chunk boundaries transfer NO
token data to the device (only losses/checkpoints come back out).

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
      --reduced --steps 200 --mode afto --stream
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import get_config, reduced as reduce_cfg
from repro.core.scheduler import StragglerConfig, StragglerScheduler
from repro.data import stream as stream_lib
from repro.data.synthetic import make_token_stream
from repro.fed.trilevel_llm import (FedHyper, afto_llm_step, batch_stream,
                                    cut_refresh_llm, init_fed_state,
                                    plain_train_step)
from repro.models import transformer as tfm
from repro.optim import adamw

# How many times each chunked-scan runner actually traced (python
# side-effect at trace time): warm equal-size chunks must reuse the jit
# cache — a retrace would silently break donation and recompile per
# chunk.  tests/test_launchers.py asserts these stay flat.
SCAN_TRACES = {"host": 0, "stream": 0}


def _chunk_tokens(cfg, args, start: int, stop: int) -> np.ndarray:
    n, b, s = args.workers, args.batch, args.seq
    return np.stack([
        np.asarray(make_token_stream(cfg.vocab_size, n * b, s,
                                     seed=args.seed * 7919 + it))
        .reshape(n, b, s)
        for it in range(start, stop)])


def _worker_mesh_put(state, n_shards):
    """Place the fed state on an `n_shards`-device worker mesh: stacked
    per-worker leaves (X-stacks, duals, stale views) shard their leading
    N axis over the mesh's "data" axis and the cut b-blocks shard their
    worker axis; master leaves replicate.  Returns (mesh, state,
    batch_sharding_fn, state_shardings) — GSPMD then partitions the
    chunked scan over workers, riding the same fake-device XLA_FLAGS
    machinery as the dry-run (launch with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    `state_shardings` pins the chunk runners' state out_shardings to
    these input shardings: without it GSPMD is free to hand the state
    back in a different layout, and every warm chunk then misses the
    executable cache and recompiles (same trace, new shardings)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_worker_mesh

    mesh = make_worker_mesh(n_shards, axis_name="data")
    stacked = {"X1", "X2", "X3", "theta", "stale_lam", "stale_theta",
               "z2"}
    cut_fields = {"cuts", "cuts_i"}

    def rule(path, leaf):
        names = [str(e.name) for e in path
                 if isinstance(e, jax.tree_util.GetAttrKey)]
        head = names[0] if names else ""
        if head in stacked and leaf.ndim >= 1 \
                and leaf.shape[0] % n_shards == 0:
            return P("data")
        if head in cut_fields and ("b2" in names or "b3" in names) \
                and leaf.ndim >= 2 and leaf.shape[1] % n_shards == 0:
            return P(None, "data")
        return P()

    specs = jax.tree_util.tree_map_with_path(rule, state)
    named = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, named)

    def put_batch(*arrays):
        """Arrays with the worker axis second — tokens (chunk, N, b, s),
        masks (chunk, N) or (T, N) — shard axis 1 over the mesh."""
        tok_s = NamedSharding(mesh, P(None, "data"))
        return tuple(jax.device_put(a, tok_s) for a in arrays)

    return mesh, state, put_batch, named


def run_afto_scan(cfg, args, hyper, state, sched, val_loss) -> dict:
    """Chunked compiled trajectory: `--scan-chunk` master iterations per
    donated-buffer lax.scan dispatch (defaulting to `--log-every`, the
    pre-flag behavior), schedule precomputed up front.

    Decoupling the dispatch granularity from the logging stride lets the
    chunk grow to amortize dispatch overhead at real model scale while
    keeping the log cadence; losses are still evaluated at chunk
    boundaries, so a chunk larger than `log_every` logs once per chunk
    (at the first crossed `log_every` boundary).  `--mesh-workers N`
    additionally distributes the federation over an N-device worker
    mesh (`_worker_mesh_put`).

    With `--stream` the per-chunk host token synthesis + transfer
    (`_chunk_tokens` / `jnp.asarray`) disappears entirely: the scan body
    draws each iteration's worker batches from fold-in keys on the
    absolute iteration, and the chunk loop's whole device input is the
    donated (state, key, cursor) carry — the schedule masks are put on
    the device once and sliced in-dispatch, so warm equal-size chunks
    do zero host→device transfers."""
    schedule = sched.precompute(args.steps)
    chunk = max(1, args.scan_chunk or args.log_every)
    # init_fed_state may alias buffers across fields; donation needs
    # each buffer to appear once.
    state = jax.tree.map(jnp.array, state)
    if getattr(args, "resume", False) and args.mesh_workers:
        raise ValueError("--resume with --mesh-workers is not supported "
                         "yet (restore precedes mesh placement)")
    put_batch = state_shardings = None
    if args.mesh_workers:
        mesh, state, put_batch, state_shardings = _worker_mesh_put(
            state, args.mesh_workers)
        print(f"worker mesh: {dict(mesh.shape)} over "
              f"{args.workers} federated workers")

    def step(st, batch, mask, it):
        st = afto_llm_step(cfg, hyper, st, batch, mask)
        return jax.lax.cond(
            ((it + 1) % args.t_pre == 0) & (it < args.t1),
            lambda s2: cut_refresh_llm(cfg, hyper, s2, batch),
            lambda s2: s2, st)

    if getattr(args, "stream", False):
        return _afto_scan_streamed(cfg, args, state, schedule, chunk,
                                   step, put_batch, val_loss,
                                   state_shardings)

    def body(st, xs):
        toks, mask, it = xs
        return step(st, {"tokens": toks, "val_tokens": toks}, mask, it), \
            None

    @partial(jax.jit, donate_argnums=(0,), out_shardings=state_shardings)
    def run_chunk(st, toks, masks, its):
        SCAN_TRACES["host"] += 1
        st, _ = jax.lax.scan(body, st, (toks, masks, its))
        return st

    last_toks = None     # the live chunk's tokens, for the loss slice

    def one_chunk(st, start, stop):
        nonlocal last_toks
        toks = jnp.asarray(_chunk_tokens(cfg, args, start, stop))
        masks = jnp.asarray(schedule.active[start:stop])
        if put_batch is not None:
            toks, masks = put_batch(toks, masks)
        last_toks = toks
        return run_chunk(st, toks, masks,
                         jnp.arange(start, stop, dtype=jnp.int32))

    def loss_at(st, stop):
        w = jax.tree.map(lambda x: x[0], st.X3)
        return val_loss(w, jnp.asarray(last_toks[-1][0]))

    carry, start0 = _maybe_resume(args, {"state": state})
    return _chunk_loop(args, schedule, chunk, carry["state"], one_chunk,
                       loss_at, carry_to_save=lambda st: {"state": st},
                       start=start0)


def _maybe_resume(args, template):
    """(carry, start_step): restore the latest full-carry checkpoint from
    `--ckpt-dir` when `--resume` is set, else the template untouched.

    The restored carry is exactly what `_chunk_loop` saved at a chunk
    boundary — for the streamed path (state, key, cursor), i.e. the
    whole donated scan carry — so continuing from it is bit-identical to
    the uninterrupted run by the chunking-invariance contract (schedule
    masks and stream batches key on the absolute iteration)."""
    if not (getattr(args, "resume", False) and args.ckpt_dir):
        return template, 0
    step = latest_step(args.ckpt_dir)
    if step is None:
        return template, 0
    carry = load_checkpoint(args.ckpt_dir, template, step)
    carry = jax.tree.map(
        lambda t, v: jnp.asarray(v, getattr(t, "dtype", None)),
        template, carry)
    print(json.dumps({"resumed_from": step, "ckpt_dir": args.ckpt_dir}))
    return carry, step


def _chunk_loop(args, schedule, chunk, state, one_chunk, loss_at,
                carry_to_save=None, start: int = 0) -> dict:
    """The chunk-dispatch loop shared by the host-fed and streamed scan
    drivers: log whenever a `log_every` boundary was crossed inside the
    chunk (every chunk when chunk == log_every, the default) or at the
    final — possibly partial — chunk, and save whenever a `ckpt_every`
    boundary was crossed.  `one_chunk(state, start, stop)` advances the
    donated carry; `loss_at(state, stop)` evaluates worker 0's
    validation loss at iteration stop - 1; `carry_to_save(state)` is the
    checkpoint payload — the FULL restart carry for the scan drivers
    (legacy z3-only when unset).  `start` > 0 continues a resumed run
    from that absolute step."""
    history = []
    t0 = time.perf_counter()
    for begin in range(start, args.steps, chunk):
        stop = min(begin + chunk, args.steps)
        state = one_chunk(state, begin, stop)
        if (stop // args.log_every > begin // args.log_every
                or stop == args.steps):
            history.append({"step": stop, "loss": float(loss_at(state, stop)),
                            "sim_time": float(schedule.sim_time[stop - 1]),
                            "host_s": time.perf_counter() - t0,
                            "cuts": float(jnp.sum(state.cuts.active))})
            print(json.dumps(history[-1]))
        if args.ckpt_dir and stop // args.ckpt_every > begin // args.ckpt_every:
            save_checkpoint(
                args.ckpt_dir,
                carry_to_save(state) if carry_to_save else state.z3,
                stop)
    return {"history": history}


def _afto_scan_streamed(cfg, args, state, schedule, chunk, step,
                        put_batch, val_loss, state_shardings) -> dict:
    """The `--stream` chunk driver: tokens synthesized in-scan, (state,
    key, cursor) donated across chunk dispatches, masks device-resident
    and sliced in-dispatch (`_chunk_loop` holds the boundary logic)."""
    stream = batch_stream(cfg, args.workers, args.batch, args.seq,
                          seed=args.seed)
    spec = stream.spec

    key = jnp.asarray(stream.key)
    cursor = jnp.zeros((), jnp.int32)
    carry, start0 = _maybe_resume(
        args, {"state": state, "key": key, "cursor": cursor})
    state, key, cursor = carry["state"], carry["key"], carry["cursor"]
    out_shardings = None
    if state_shardings is not None:
        # commit the scalar carry replicated and pin the outputs to the
        # input layout, so warm chunks hit the executable cache
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(
            jax.tree.leaves(state_shardings)[0].mesh, P())
        key, cursor = jax.device_put((key, cursor), rep)
        out_shardings = (state_shardings, rep, rep)

    def body(carry, xs):
        st, key = carry
        mask, it = xs
        batch = stream_lib.batch_at(spec, key, it)
        return (step(st, batch, mask, it), key), None

    @partial(jax.jit, static_argnames=("n",), donate_argnums=(0, 1, 2),
             out_shardings=out_shardings)
    def run_chunk(st, key, start, masks, n):
        SCAN_TRACES["stream"] += 1
        its = start + jnp.arange(n, dtype=jnp.int32)
        mk = jax.lax.dynamic_slice_in_dim(masks, start, n)
        (st, key), _ = jax.lax.scan(body, (st, key), (mk, its))
        return st, key, start + n

    @jax.jit
    def val_at(w, key, it):
        # worker 0's tokens at iteration `it` — the streamed stand-in
        # for the host path's `toks[-1][0]` validation slice
        toks = stream_lib.batch_at(spec, key, it, n_local=1)["tokens"][0]
        return val_loss(w, toks)

    masks = jnp.asarray(schedule.active, jnp.float32)
    if put_batch is not None:
        masks, = put_batch(masks)

    def one_chunk(st, start, stop):
        nonlocal key, cursor
        st, key, cursor = run_chunk(st, key, cursor, masks,
                                    n=stop - start)
        return st

    def loss_at(st, stop):
        w = jax.tree.map(lambda x: x[0], st.X3)
        return val_at(w, key, jnp.asarray(stop - 1, jnp.int32))

    def carry_to_save(st):
        # the WHOLE donated carry: restoring (state, key, cursor) and
        # continuing is bit-identical to the uninterrupted run
        return {"state": st, "key": key, "cursor": cursor}

    return _chunk_loop(args, schedule, chunk, state, one_chunk, loss_at,
                       carry_to_save=carry_to_save, start=start0)


def _afto_setup(cfg, args):
    """(hyper, state, sched, val_loss) for the AFTO drivers — split out
    so tests exercise `run_afto_scan` in-process."""
    n, b, s = args.workers, args.batch, args.seq
    hyper = FedHyper(n_workers=n, cut_mode=args.cut_mode,
                     sketch_r=args.sketch_r, p_max=2, k_inner=1,
                     remat=False, eta_x=args.lr, eta_z=args.lr)
    state = init_fed_state(cfg, hyper, jax.random.PRNGKey(args.seed),
                           b, s - 1)
    val_loss = jax.jit(lambda w, tk: tfm.train_loss(cfg, w, tk))
    sched = StragglerScheduler(StragglerConfig(
        n_workers=n, s_active=max(1, n - 1), tau=args.tau,
        n_stragglers=1, seed=args.seed))
    return hyper, state, sched, val_loss


def run_afto(cfg, args) -> dict:
    hyper, state, sched, val_loss = _afto_setup(cfg, args)

    if args.engine == "scan":
        return run_afto_scan(cfg, args, hyper, state, sched, val_loss)
    if args.mesh_workers:
        raise ValueError("--mesh-workers requires --engine scan")
    if getattr(args, "stream", False):
        raise ValueError("--stream requires --engine scan")
    n, b, s = args.workers, args.batch, args.seq

    step = jax.jit(lambda st, bt, m: afto_llm_step(cfg, hyper, st, bt, m))
    refresh = jax.jit(lambda st, bt: cut_refresh_llm(cfg, hyper, st, bt))
    history = []
    t0 = time.time()
    for it in range(args.steps):
        toks = make_token_stream(cfg.vocab_size, n * b, s,
                                 seed=args.seed * 7919 + it)
        toks = jnp.asarray(toks).reshape(n, b, s)
        batch = {"tokens": toks, "val_tokens": toks}
        mask, sim_t = sched.next_active()
        state = step(state, batch, jnp.asarray(mask))
        if (it + 1) % args.t_pre == 0 and it < args.t1:
            state = refresh(state, batch)
        if (it + 1) % args.log_every == 0 or it == args.steps - 1:
            w = jax.tree.map(lambda x: x[0], state.X3)
            loss = float(val_loss(w, toks[0]))
            history.append({"step": it + 1, "loss": loss,
                            "sim_time": sim_t,
                            "host_s": round(time.time() - t0, 1),
                            "cuts": float(jnp.sum(state.cuts.active))})
            print(json.dumps(history[-1]))
        if args.ckpt_dir and (it + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, state.z3, it + 1)
    return {"history": history}


def run_plain(cfg, args) -> dict:
    params = tfm.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = adamw(args.lr)
    opt_state = opt.init(params)
    step = jax.jit(lambda p, o, tk: plain_train_step(
        cfg, p, o, tk, optimizer=opt, remat=False))
    history = []
    t0 = time.time()
    b = args.workers * args.batch
    for it in range(args.steps):
        toks = jnp.asarray(make_token_stream(
            cfg.vocab_size, b, args.seq, seed=args.seed * 7919 + it))
        params, opt_state, loss = step(params, opt_state, toks)
        if (it + 1) % args.log_every == 0 or it == args.steps - 1:
            history.append({"step": it + 1, "loss": float(loss),
                            "host_s": round(time.time() - t0, 1)})
            print(json.dumps(history[-1]))
        if args.ckpt_dir and (it + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, params, it + 1)
    return {"history": history}


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke variant (CPU-friendly)")
    ap.add_argument("--mode", default="afto", choices=["afto", "plain"])
    ap.add_argument("--engine", default="scan", choices=["scan", "eager"],
                    help="scan = chunked compiled trajectory (default); "
                         "eager = one dispatch per master iteration")
    ap.add_argument("--cut-mode", default="sketch",
                    choices=["sketch", "exact"])
    ap.add_argument("--sketch-r", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2,
                    help="per-worker batch")
    ap.add_argument("--seq", type=int, default=129)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--t-pre", type=int, default=20)
    ap.add_argument("--t1", type=int, default=10_000)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--stream", action="store_true",
                    help="device-resident token stream (--engine scan): "
                         "worker batches are synthesized inside the "
                         "scan body from fold-in PRNG keys instead of "
                         "host numpy chunks, and the key/cursor carry "
                         "is donated across chunk dispatches — chunk "
                         "boundaries transfer no token data")
    ap.add_argument("--scan-chunk", type=int, default=None,
                    help="master iterations per compiled scan dispatch "
                         "(--engine scan); defaults to --log-every. "
                         "Larger chunks amortize dispatch overhead at "
                         "real model scale independently of the log "
                         "cadence")
    ap.add_argument("--mesh-workers", type=int, default=None,
                    help="distribute the federation over this many "
                         "devices (--engine scan): worker-stacked state "
                         "and cut b-blocks shard over a 1-axis mesh. "
                         "Needs >= N visible devices — set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N for "
                         "a fake-device CPU mesh (the dry-run machinery)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest full-carry checkpoint from "
                         "--ckpt-dir and continue from its step "
                         "(--engine scan; bit-identical to the "
                         "uninterrupted run)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> dict:
    args = parse_args(argv)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    print(f"training {cfg.name} mode={args.mode} steps={args.steps}")
    if args.mode == "afto":
        return run_afto(cfg, args)
    return run_plain(cfg, args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
