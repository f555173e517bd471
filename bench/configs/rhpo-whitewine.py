"""Plain reference of the rhpo-whitewine configuration: distributed
robust hyperparameter optimisation (paper section 5.1, Eq. 31) on a
stand-in with the shape of UCI Wine Quality (white), 4898 x 11.

- level 1 (min over phi): the validation MSE of the trained model;
- level 2 (max over p): an adversarial perturbation of the training
  inputs, worker j owning block p_j, penalised by c mean(p_j^2);
- level 3 (min over w): the perturbed training MSE plus
  e^phi ||w||_1* / N, with ||w||_1* = sum sqrt(w^2 + 1e-6) - 1e-3.

The model is an MLP d -> hidden -> 1 with a tanh hidden layer.  The data
generator is a copy of the repository's stand-in (a linear plus tanh
teacher with noise, standardised labels, a test/validation/train split
sharded equally over the workers), seeded from the run's seed so that
the yardstick cannot move with the program.  Nothing here imports the
program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def make_data(c: dict, seed: int) -> dict:
    """Per-worker arrays {xtr (N, n_tr, d), ytr, xval, yval, wid} as numpy
    float32, from the seed."""
    n, d = c["n_samples"], c["n_features"]
    nw = c["n_workers"]
    rng = np.random.default_rng(
        int(seed) + zlib.crc32(c["dataset"].encode()) % 65536)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d,)).astype(np.float32) / np.sqrt(d)
    y = x @ w + 0.5 * np.tanh(x @ np.roll(w, 1)) \
        + 0.1 * rng.normal(size=(n,))
    y = ((y - y.mean()) / (y.std() + 1e-8)).astype(np.float32)
    n_test = int(n * c["test_frac"])
    x_rem, y_rem = x[n_test:], y[n_test:]
    n_val = int(len(x_rem) * c["val_frac"])
    n_tr = (len(x_rem) - n_val) // nw
    n_v = max(1, n_val // nw)

    def shard(a, per_worker):
        per = len(a) // nw
        return a[: per * nw].reshape(nw, per, *a.shape[1:])[:, :per_worker]

    return {"xtr": shard(x_rem[n_val:], n_tr), "ytr": shard(y_rem[n_val:], n_tr),
            "xval": shard(x_rem[:n_val], n_v), "yval": shard(y_rem[:n_val], n_v),
            "wid": np.arange(nw, dtype=np.int32)}


def init_weights(c: dict, key) -> dict:
    """MLP weights N(0, 1/fan_in), biases 0, as {w0, b0, w1, b1}."""
    sizes = (c["n_features"], c["hidden"], 1)
    out = {}
    for i, (din, dout) in enumerate(zip(sizes[:-1], sizes[1:])):
        out[f"w{i}"] = jax.random.normal(jax.random.fold_in(key, i),
                                         (din, dout)) / jnp.sqrt(din)
        out[f"b{i}"] = jnp.zeros((dout,), jnp.float32)
    return out


def _mlp(w, x, mm):
    h = jnp.tanh(mm("nd,dh->nh", x, w["w0"]) + w["b0"])
    return mm("nh,ho->no", h, w["w1"]) + w["b1"]


def _smoothed_l1(w, delta=1e-3):
    return sum(jnp.sum(jnp.sqrt(p ** 2 + delta ** 2) - delta)
               for p in jax.tree.leaves(w))


def objectives(c: dict, mm):
    """(f1, f2, f3), each f(data_j, x1, x2, x3) for one worker, all to be
    minimised (level 2's objective negated)."""
    nw, pen = c["n_workers"], c["adv_penalty"]

    def train_mse(d, p, w):
        pred = _mlp(w, d["xtr"] + p, mm)[:, 0]
        return jnp.mean((pred - d["ytr"]) ** 2)

    def f1(d, x1, x2, x3):
        pred = _mlp(x3, d["xval"], mm)[:, 0]
        return jnp.mean((pred - d["yval"]) ** 2)

    def f2(d, x1, x2, x3):
        p = x2[d["wid"]]
        return -(train_mse(d, p, x3) - pen * jnp.mean(p ** 2))

    def f3(d, x1, x2, x3):
        p = x2[d["wid"]]
        return train_mse(d, p, x3) \
            + jnp.exp(x1["phi"][0]) * _smoothed_l1(x3) / nw

    return f1, f2, f3


def initial_point(c: dict, data: dict):
    """(x1, x2) the federation starts from: phi = -3, no perturbation;
    x3 is `init_weights`."""
    nw, n_tr, d = data["xtr"].shape
    return ({"phi": jnp.array([-3.0], jnp.float32)},
            jnp.zeros((nw, n_tr, d), jnp.float32))
