"""Count-sketch compression of mu-cut coefficients (beyond-paper).

An exact mu-cut's coefficient vector lives in the full variable space —
at LLM scale that is P_max model-sized pytrees per polytope, which is
memory-prohibitive (see DESIGN.md §7).  We therefore restrict the x3/z3
(and x2/z2) blocks of the cut space to a fixed r-dimensional count-sketch
subspace:

    S(v)[k] = sum_{i : h(i)=k} sigma_i * v_i,

with h / sigma derived from a seeded integer hash of each element's flat
index — O(n) elementwise compute, no projection matrix is ever
materialized, and the ops are trivially shardable (the final segment-sum
reduces over the sharded axis with one small psum).

<S(a), S(b)> is an unbiased JL-style estimator of <a, b>; cuts generated
and evaluated inside the same sketch are exact *within the subspace*.
The paper-scale experiments validate sketched-vs-exact trajectories
empirically (benchmarks/sketch_fidelity.py).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

_MIX1 = jnp.uint32(2654435761)
_MIX2 = jnp.uint32(2246822519)
_MIX3 = jnp.uint32(3266489917)


def _mix(x: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Cheap integer hash (xxhash-style avalanche), uint32 -> uint32."""
    h = x * _MIX1 + seed
    h = h ^ (h >> 15)
    h = h * _MIX2
    h = h ^ (h >> 13)
    h = h * _MIX3
    return h ^ (h >> 16)


def _leaf_hashes(shape, leaf_seed: jnp.ndarray, r: int):
    n = 1
    for s in shape:
        n *= int(s)
    iota = jax.lax.iota(jnp.uint32, n)
    h = _mix(iota, leaf_seed)
    idx = (h % jnp.uint32(r)).astype(jnp.int32)
    sign = jnp.where((h >> 31) > 0, 1.0, -1.0).astype(jnp.float32)
    return idx.reshape(shape), sign.reshape(shape)


def _leaf_seeds(tree, seed: int):
    leaves, treedef = jax.tree.flatten(tree)
    seeds = [jnp.uint32((seed * 1_000_003 + 7919 * i + 1) % (2 ** 32))
             for i in range(len(leaves))]
    return leaves, treedef, seeds


def sketch(tree: Any, seed: int, r: int) -> jnp.ndarray:
    """Count-sketch a pytree into an (r,) f32 vector."""
    leaves, _, seeds = _leaf_seeds(tree, seed)
    out = jnp.zeros((r,), jnp.float32)
    for leaf, s in zip(leaves, seeds):
        idx, sign = _leaf_hashes(leaf.shape, s, r)
        vals = leaf.astype(jnp.float32) * sign
        out = out + jax.ops.segment_sum(vals.reshape(-1),
                                        idx.reshape(-1), num_segments=r)
    return out


def sketch_stacked(tree: Any, seed: int, r: int) -> jnp.ndarray:
    """Count-sketch every worker slice of an (N,)-stacked pytree: (N, r),
    equal to stacking `sketch(tree[j])`.

    One flat scatter into N*r buckets, worker j's bucket k at j*r + k,
    so every embedding-sized operand stays worker-major.  A `jax.vmap`
    of `sketch` batches the segment-sum instead, and XLA then lays the
    worker axis out minor: on a TPU the (8, 128) tile pads it to 128
    lanes, a 32x blow-up of every embedding-sized leaf at N=4.  Sharded
    over workers, each device scatters its own rows and one (N*r,)
    all-reduce joins them."""
    leaves, _, seeds = _leaf_seeds(tree, seed)
    n_workers = leaves[0].shape[0]
    offset = (r * jnp.arange(n_workers, dtype=jnp.int32))[:, None]
    out = jnp.zeros((n_workers * r,), jnp.float32)
    for leaf, s in zip(leaves, seeds):
        idx, sign = _leaf_hashes(leaf.shape[1:], s, r)
        vals = leaf.reshape(n_workers, -1).astype(jnp.float32) \
            * sign.reshape(1, -1)
        ids = idx.reshape(1, -1) + offset
        out = out + jax.ops.segment_sum(vals.reshape(-1), ids.reshape(-1),
                                        num_segments=n_workers * r)
    return out.reshape(n_workers, r)


def unsketch(template: Any, s_vec: jnp.ndarray, seed: int) -> Any:
    """Adjoint of `sketch`: lift an (r,) vector back to the tree space.

    unsketch(t, sketch(v)) has <unsketch, w> == <sketch(v), sketch(w)>,
    so using it as a gradient is exactly 'the cut acts in sketch space'.
    """
    r = s_vec.shape[0]
    leaves, treedef, seeds = _leaf_seeds(template, seed)
    out = []
    for leaf, sd in zip(leaves, seeds):
        idx, sign = _leaf_hashes(leaf.shape, sd, r)
        out.append((s_vec[idx] * sign).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


def unsketch_stacked(template: Any, s_mat: jnp.ndarray, seed: int) -> Any:
    """`unsketch` of each row of an (N, r) matrix: the (N,)-stacked tree,
    gathered worker-major from the flat N*r vector as `sketch_stacked`
    scatters into it."""
    n_workers, r = s_mat.shape
    flat = s_mat.reshape(-1)
    offset = (r * jnp.arange(n_workers, dtype=jnp.int32))[:, None]
    leaves, treedef, seeds = _leaf_seeds(template, seed)
    out = []
    for leaf, sd in zip(leaves, seeds):
        idx, sign = _leaf_hashes(leaf.shape, sd, r)
        vals = flat[idx.reshape(1, -1) + offset] * sign.reshape(1, -1)
        out.append(vals.reshape((n_workers,) + leaf.shape)
                   .astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


def sketch_dot(s_a: jnp.ndarray, s_b: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(s_a * s_b)
