"""The benchmark's own traffic: straggler arrival masks.

`arrival_masks` is a copy of the program's generator,
`repro.core.scheduler.StragglerScheduler`, kept here so that the
yardstick cannot move with the program: each worker finishes
`base_latency * slowdown * lognormal(0, jitter)` after its last
broadcast; the master takes every worker the staleness bound `tau`
forces, then the earliest others up to `s_active`, waits for the slowest
of them, and also takes any other worker done by then.
"""
from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds may
    exceed 32 bits)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def arrival_masks(*args, **kwargs) -> np.ndarray:
    """(n_steps, n_workers) float32 {0, 1} masks of who arrives when."""
    return arrival_schedule(*args, **kwargs)[0]


def arrival_schedule(n_workers: int, s_active: int, tau: int,
                     n_stragglers: int, slowdown: float, n_steps: int,
                     seed: int, base_latency: float = 1.0,
                     jitter: float = 0.2):
    """(masks (n_steps, n_workers) float32, completion times (n_steps,),
    largest staleness after each iteration (n_steps,))."""
    rng = np.random.default_rng(int(seed))
    slow = np.ones(n_workers)
    slow[:n_stragglers] = slowdown
    rng.shuffle(slow)

    def draw(now):
        return now + base_latency * slow * rng.lognormal(
            0.0, jitter, size=n_workers)

    now = 0.0
    ready = draw(now)
    last = np.zeros(n_workers, np.int64)
    out = np.zeros((n_steps, n_workers), np.float32)
    done = np.zeros((n_steps,), np.float64)
    stale = np.zeros((n_steps,), np.int64)
    for t in range(1, n_steps + 1):
        forced = (t - last) >= tau
        chosen = set(np.nonzero(forced)[0].tolist())
        for j in np.argsort(ready):
            if len(chosen) >= s_active:
                break
            chosen.add(int(j))
        chosen = np.array(sorted(chosen), np.int64)
        t_done = float(np.max(ready[chosen]))
        active = np.union1d(chosen, np.nonzero(ready <= t_done)[0])
        now = t_done
        mask = np.zeros(n_workers, np.float32)
        mask[active] = 1.0
        last[active] = t
        ready = np.where(mask > 0, draw(now), ready)
        out[t - 1] = mask
        done[t - 1] = now
        stale[t - 1] = np.max(t - last)
    return out, done, stale
