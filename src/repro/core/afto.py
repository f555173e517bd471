"""AFTO: the asynchronous federated master-worker iteration (Alg. 1).

One `afto_step` is Eqs. 16-21 at a given active-worker mask; `cut_refresh`
is the T_pre-periodic hyper-polytope update (Eqs. 23-25).  Both are pure,
jit-able functions of (state, mask); asynchrony (who is active when, and
what simulated wall-clock each iteration costs) lives in
`repro.core.scheduler` on the host.

Both polytopes live in `AFTOState` as canonical `FlatCuts` (one dense
(P, D) matrix each): every cut contraction in the step reads the stored
matrix directly, and `cut_refresh` writes the two new cuts as single
rows — nothing here calls `flat_spec`/`flatten_cuts`, so the scanned
trajectory never re-materializes the operator from block trees.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import cuts as cuts_lib
from repro.core import inner as inner_lib
from repro.core import lagrangian as lag
from repro.core.types import (AFTOState, Hyper, InnerState2,
                              InnerState3, StaleView, TrilevelProblem)
from repro.utils.tree import (tree_axpy, tree_sub, tree_zeros_like)


# ---------------------------------------------------------------------------
# projections (Eq. 20/21)
# ---------------------------------------------------------------------------

def proj_lambda(lam, hyper: Hyper):
    return jnp.clip(lam, 0.0, jnp.sqrt(hyper.alpha4))


def proj_theta(theta, hyper: Hyper):
    r = jnp.sqrt(hyper.alpha5) / hyper.d1
    return jax.tree.map(lambda th: jnp.clip(th, -r, r), theta)


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------

def _stack_n(tpl, n):
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape)
                        .astype(x.dtype), tpl)


def init_state(problem: TrilevelProblem, hyper: Hyper) -> AFTOState:
    n, p = hyper.n_workers, hyper.p_max
    z1, z2, z3 = problem.x1_init, problem.x2_init, problem.x3_init
    X1, X2, X3 = (_stack_n(z1, n), _stack_n(z2, n), _stack_n(z3, n))
    theta = tree_zeros_like(X1)
    cuts_i = cuts_lib.empty_cuts(p, n, z1, z2, z3)
    cuts_ii = cuts_lib.empty_cuts(p, n, z1, z2, z3)
    inner3 = InnerState3(x3=X3, z3=z3, phi=tree_zeros_like(X3))
    inner2 = InnerState2(x2=X2, z2=z2, phi=tree_zeros_like(X2),
                         s=jnp.zeros((p,), jnp.float32),
                         gamma=jnp.zeros((p,), jnp.float32))
    stale = StaleView(z1=_stack_n(z1, n), z2=_stack_n(z2, n),
                      z3=_stack_n(z3, n),
                      lam=jnp.zeros((n, p), jnp.float32),
                      theta=tree_zeros_like(X1),
                      t_hat=jnp.zeros((n,), jnp.int32))
    return AFTOState(X1=X1, X2=X2, X3=X3, z1=z1, z2=z2, z3=z3,
                     theta=theta, lam=jnp.zeros((p,), jnp.float32),
                     cuts_i=cuts_i, cuts_ii=cuts_ii,
                     gamma_k=jnp.zeros((p,), jnp.float32),
                     inner3=inner3, inner2=inner2, stale=stale,
                     t=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# one master iteration (Eqs. 16-21)
# ---------------------------------------------------------------------------

def afto_step(problem: TrilevelProblem, hyper: Hyper, state: AFTOState,
              active, axis: str = None) -> AFTOState:
    """Eq. 16 (masked worker updates at stale views) + Eqs. 17-21 (master).

    active: (N,) {0,1} float mask of workers whose update arrives now.
    """
    return afto_step_aux(problem, hyper, state, active, axis=axis)[0]


def local_f1_grads(problem: TrilevelProblem, X1, X2, X3) -> Tuple:
    """The data-dependent worker gradients of Eq. 16: grad f1(data_j, .)
    at each worker's local point, stacked over the leading worker axis.

    This is THE federated payload of one master iteration — everything
    else in `afto_step` (the stale-dual corrections, the master z/dual
    updates) is cheap cut/consensus algebra the master applies itself.
    The async runtime (`repro.fed.runtime`) has each worker process
    compute its own row of this stack at its own pace and push it to
    the master, which completes the step via `afto_step_from_grads`.
    """
    def f1_grads(data_j, x1_j, x2_j, x3_j):
        return jax.grad(
            lambda a, b, c: problem.f1(data_j, a, b, c),
            argnums=(0, 1, 2))(x1_j, x2_j, x3_j)

    return jax.vmap(f1_grads)(problem.data, X1, X2, X3)


@jax.named_scope("afto_step")
def afto_step_aux(problem: TrilevelProblem, hyper: Hyper, state: AFTOState,
                  active, axis: str = None) -> Tuple[AFTOState, dict]:
    """`afto_step` plus the step's cut-algebra intermediates.

    The returned aux dict carries the flattened II-polytope operator and
    the cut values at the *post-step* point — exactly the products the
    stationarity gap needs at record iterations, so the compiled engine
    can fuse the gap into its record branch without recomputing them
    (`repro.core.stationarity.stationarity_gap_sq(aux=...)`).  Valid only
    while the polytope is unchanged (i.e. before any `cut_refresh`).

    axis, when set, is the worker mesh axis of a `shard_map`'d trajectory
    (`repro.core.sharded`): `state`/`problem.data`/`active` then carry
    only this shard's workers, the polytopes hold the local b-columns,
    and the ONLY cross-shard traffic is the cut-scalar psum and the
    theta-sum feeding the master z1 update — every Eq. 16 worker
    contraction stays shard-local.
    """
    # ---- workers (Eq. 16): gradients of \hat L_p at each worker's stale view
    g1_f, g2_f, g3_f = local_f1_grads(problem, state.X1, state.X2, state.X3)
    return afto_step_from_grads(problem, hyper, state, active,
                                (g1_f, g2_f, g3_f), axis=axis)


def afto_step_from_grads(problem: TrilevelProblem, hyper: Hyper,
                         state: AFTOState, active, f1_grads,
                         axis: str = None) -> Tuple[AFTOState, dict]:
    """The master half of Eq. 16-21 given precomputed worker f1-grads.

    `f1_grads` is the `(g1_f, g2_f, g3_f)` stack triple of
    `local_f1_grads`; rows of inactive workers are masked out and may
    hold anything finite (the async master zero-fills them).  With
    `f1_grads = local_f1_grads(problem, X1, X2, X3)` this is exactly
    `afto_step_aux` — the split exists so a runtime master can apply
    worker-pushed gradients stale without recomputing them.
    """
    t = state.t
    g1_f, g2_f, g3_f = f1_grads

    # consensus dual term (stale own theta) and cut terms (stale lambda):
    # the per-worker b-block sums are column slices of the canonical
    # (P, D) matrix contracted with the (N, P) stale weight table.
    g1 = jax.tree.map(jnp.add, g1_f, state.stale.theta)
    g2 = jax.tree.map(jnp.add, g2_f,
                      cuts_lib.cut_coeff_per_worker(
                          state.cuts_ii, state.stale.lam, "b2"))
    g3 = jax.tree.map(jnp.add, g3_f,
                      cuts_lib.cut_coeff_per_worker(
                          state.cuts_ii, state.stale.lam, "b3"))

    def masked_step(X, g, eta):
        return jax.tree.map(
            lambda x, gg: x - eta * _bmask(active, x) * gg, X, g)

    X1 = masked_step(state.X1, g1, hyper.eta_x)
    X2 = masked_step(state.X2, g2, hyper.eta_x)
    X3 = masked_step(state.X3, g3, hyper.eta_x)

    # ---- master Gauss-Seidel primal updates (Eqs. 17-19)
    # The canonical (P, D) operator serves the whole master step AS
    # STORED: the a-block gradients for z1/z2/z3 all come out of a
    # single w @ A mat-vec, and the same matrix feeds the cut_eval
    # kernel below — no per-step re-flatten.
    lam_a = state.lam * state.cuts_ii.active
    spec = state.cuts_ii.spec
    a_flat = state.cuts_ii.a
    ga1, ga2, ga3, _, _ = cuts_lib.cut_weighted_coeff_flat(
        spec, a_flat, lam_a)

    theta_sum = jax.tree.map(lambda th: jnp.sum(th, axis=0), state.theta)
    if axis is not None:
        theta_sum = jax.lax.psum(theta_sum, axis)
    gz1 = tree_axpy(-1.0, theta_sum, ga1)
    z1 = tree_axpy(-hyper.eta_z, gz1, state.z1)
    z2 = tree_axpy(-hyper.eta_z, ga2, state.z2)
    z3 = tree_axpy(-hyper.eta_z, ga3, state.z3)

    # ---- dual updates with projection (Eqs. 20/21)
    if axis is None:
        cutval = cuts_lib.eval_cuts_flat(
            a_flat, cuts_lib.flatten_point(spec, z1, z2, z3, X2, X3),
            state.cuts_ii.c, state.cuts_ii.active)
    else:
        cutval = cuts_lib.eval_cuts_worker_split(
            state.cuts_ii, z1, z2, z3, X2, X3, axis)
    lam = proj_lambda(
        state.lam + hyper.eta_lambda * (cutval - hyper.c1(t) * state.lam),
        hyper) * state.cuts_ii.active

    def theta_step(th_j, x1_j):
        g = tree_sub(x1_j, z1)
        return jax.tree.map(
            lambda t0, gg: t0 + hyper.eta_theta * (gg - hyper.c2(t) * t0),
            th_j, g)

    theta = proj_theta(jax.vmap(theta_step)(state.theta, X1), hyper)

    # ---- refresh stale views of the (now-active) workers
    def snap(stale_stack, fresh):
        return jax.tree.map(
            lambda s, f: jnp.where(
                _bmask(active, s) > 0,
                jnp.broadcast_to(f[None], s.shape).astype(s.dtype), s),
            stale_stack, fresh)

    stale = StaleView(
        z1=snap(state.stale.z1, z1),
        z2=snap(state.stale.z2, z2),
        z3=snap(state.stale.z3, z3),
        lam=jnp.where(active[:, None] > 0, lam[None, :], state.stale.lam),
        theta=jax.tree.map(
            lambda s, f: jnp.where(_bmask(active, s) > 0, f, s),
            state.stale.theta, theta),
        t_hat=jnp.where(active > 0, t + 1, state.stale.t_hat),
    )

    new_state = dataclasses.replace(
        state, X1=X1, X2=X2, X3=X3, z1=z1, z2=z2, z3=z3,
        theta=theta, lam=lam, stale=stale, t=t + 1)
    return new_state, {"flat_ii": a_flat, "cutval": cutval}


def _bmask(active, x):
    """Broadcast the (N,) mask against a leaf with leading worker axis."""
    return active.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)


# ---------------------------------------------------------------------------
# cut refresh (Eqs. 23-25, Alg. 1 middle block)
# ---------------------------------------------------------------------------

@jax.named_scope("cut_refresh")
def cut_refresh(problem: TrilevelProblem, hyper: Hyper,
                state: AFTOState) -> AFTOState:
    """Generate one I-layer and one II-layer mu-cut at the current point,
    then drop inactive cuts.  Runs every t_pre master iterations, t < t1.

    Each `add_cut` is one row write into the canonical (P, D) matrix
    (only the NEW cut's coefficient dict is flattened); the drop rule is
    a row mask — the block trees are never materialized here, so the
    refresh runs inside the scan without touching `flat_spec`."""
    t = state.t

    # warm-start the inner states at the current outer point (duals kept)
    inner3 = InnerState3(x3=state.X3, z3=state.z3, phi=state.inner3.phi)

    # ---- I-layer cut (Eq. 23) at (X3, z1, z2, z3)
    hi_fn = lambda X3, z3, z1, z2: inner_lib.h_i(
        problem, hyper, X3, z3, z1, z2, inner3)
    h0_i, grads_i = jax.value_and_grad(hi_fn, argnums=(0, 1, 2, 3))(
        state.X3, state.z3, state.z1, state.z2)
    gX3, gz3, gz1, gz2 = grads_i
    # derivation-correct bound (see cuts.py docstring): a1 + a2 + (N+1) a3
    bound_i = hyper.alpha1 + hyper.alpha2 + (hyper.n_workers + 1) * hyper.alpha3
    coeffs_i, c_i = cuts_lib.make_cut(
        h0_i,
        {"a1": gz1, "a2": gz2, "a3": gz3, "b3": gX3},
        {"a1": state.z1, "a2": state.z2, "a3": state.z3, "b3": state.X3},
        hyper.eps_i, hyper.mu_i, bound_i)
    cuts_i = cuts_lib.add_cut(state.cuts_i, coeffs_i, c_i, t)

    # ---- level-2 rollout under the updated I-polytope (for h_II and the
    #      gamma-based drop rule)
    inner2 = InnerState2(x2=state.X2, z2=state.z2, phi=state.inner2.phi,
                         s=state.inner2.s * cuts_i.active,
                         gamma=state.inner2.gamma * cuts_i.active)

    # ---- II-layer cut (Eq. 24) at (X2, X3, z1, z2, z3)
    hii_fn = lambda X2, z2, z1, z3, X3: inner_lib.h_ii(
        problem, hyper, X2, z2, z1, z3, X3, cuts_i, inner2)
    h0_ii, grads_ii = jax.value_and_grad(hii_fn, argnums=(0, 1, 2, 3, 4))(
        state.X2, state.z2, state.z1, state.z3, state.X3)
    gX2, gz2b, gz1b, gz3b, gX3b = grads_ii
    bound_ii = hyper.alpha1 + (hyper.n_workers + 1) * (hyper.alpha2
                                                       + hyper.alpha3)
    coeffs_ii, c_ii = cuts_lib.make_cut(
        h0_ii,
        {"a1": gz1b, "a2": gz2b, "a3": gz3b, "b2": gX2, "b3": gX3b},
        {"a1": state.z1, "a2": state.z2, "a3": state.z3,
         "b2": state.X2, "b3": state.X3},
        hyper.eps_ii, hyper.mu_ii, bound_ii)
    cuts_ii = cuts_lib.add_cut(state.cuts_ii, coeffs_ii, c_ii, t)

    # run the inner-2 rollout once to obtain gamma^K for the drop rule
    inner2_k = inner_lib.rollout2(problem, hyper, state.z1, state.z3,
                                  state.X3, cuts_i, inner2)
    gamma_k = inner2_k.gamma

    # ---- drop inactive cuts (Eq. 25); never drop the cut just added
    fresh_i = (cuts_i.age == t).astype(jnp.float32)
    cuts_i = cuts_lib.drop_inactive(cuts_i, gamma_k + fresh_i)
    fresh_ii = (cuts_ii.age == t).astype(jnp.float32)
    cuts_ii = cuts_lib.drop_inactive(cuts_ii, state.lam + fresh_ii)

    lam = state.lam * cuts_ii.active
    inner3_k = inner_lib.rollout3(problem, hyper, state.z1, state.z2, inner3)

    return dataclasses.replace(
        state, cuts_i=cuts_i, cuts_ii=cuts_ii, lam=lam, gamma_k=gamma_k,
        inner3=inner3_k, inner2=inner2_k)
