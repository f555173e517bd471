"""Worker-mesh sharded AFTO: the cut refresh (Eqs. 23-25) under shard_map.

The trajectory engine shards the federation over a mesh axis ``worker``:
each shard carries n_loc = N / n_shards workers' variable stacks
(X1/X2/X3, theta, stale views, inner duals), its own workers' slice of
``problem.data``, and a local polytope view holding the replicated
a-columns plus its workers' b-columns (`cuts.shard_cuts`).  Master
variables (z1/z2/z3, lam, cut c/active/age, t) are replicated.  The
per-iteration step then needs exactly two collectives — the cut-scalar
psum and the theta-sum psum (`afto.afto_step_aux(axis=...)`) — which is
the cut exchange the paper federates.

This module implements the remaining, harder piece: the T_pre-periodic
cut refresh.  Its inner ADMM rollouts (Eqs. 5-12) run SHARD-LOCALLY —
each round's worker updates touch only local x-stacks, and the master
z-updates reduce the per-shard gradient partials with one psum per round
(the paper's K communication rounds).  The mu-cut coefficients then need
d h_I / d(z1, z2) and d h_II / d(z1, z3, {x3_j}) THROUGH those rollouts.
jax cannot autodiff across a raw `lax.psum` on this code path (its
transpose under shard_map is another psum, which double-counts), so the
rollout VJPs are assembled by hand from shard-local `jax.vjp` calls:

  * forward rounds are split into a varying worker part, a replicated
    master part, and the psum'd aggregates that connect them;
  * the backward scan transposes each round locally and inserts the one
    collective the true adjoint requires — a psum of the cotangent
    contributions that flowed through varying (per-worker) consumption
    of replicated values;
  * inputs consumed BOTH per-worker and via replicated master algebra
    (z1 in h_II: worker objectives AND a1-columns) ride two explicit
    channels so the varying channel is psum'd and the replicated channel
    counted once.

The per-worker cut coefficients (b-blocks: 2(x_j - est_j)) and the
h-gradients w.r.t. each worker's variables stay shard-local throughout —
only z-sized gradient partials and (P,)-sized cut scalars cross the
mesh, matching the paper's communication complexity.

Everything here is validated against the single-device engine to f32
tolerance by `tests/test_sharded_engine.py` (step-by-step, across
refresh / eviction / straggler masks).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import cuts as cuts_lib
from repro.core import lagrangian as lag
from repro.core.types import (AFTOState, FlatCuts, Hyper, InnerState2,
                              InnerState3, TrilevelProblem)
from repro.utils.tree import (tree_add, tree_axpy, tree_dot, tree_norm_sq,
                              tree_sub, tree_zeros_like)

WORKER_AXIS = "worker"


def _psum(x, axis):
    return jax.lax.psum(x, axis)


def _bcast(z, x):
    """Broadcast an unstacked leaf against a worker-stacked one."""
    return jnp.broadcast_to(z[None], x.shape)


# ---------------------------------------------------------------------------
# level-3 rollout (Eqs. 5-7), sharded forward + hand-assembled VJP
# ---------------------------------------------------------------------------
#
# Round decomposition (st = InnerState3(x3 local, z3 replicated, phi
# local); l_p3 over the LOCAL workers only):
#   agg  = psum( d l_p3_loc / d z3 )                     [master uplink]
#   z3'  = z3 - eta_z * agg                              [replicated]
#   x3'  = x3 - eta_x * d l_p3_loc / d x3                [shard-local]
#   phi' = phi + eta_dual * (x3' - z3')                  [shard-local]

def _l_p3_local(problem, hyper, z1, z2, x3, z3, phi):
    """Per-worker sum of Eq. 4 over THIS shard's workers: exactly
    `lagrangian.l_p3` on the local stacks, re-exposed as a plain fn of
    explicit args so jax.vjp transposes exactly the pieces we need."""
    return lag.l_p3(problem, hyper, z1, z2,
                    InnerState3(x3=x3, z3=z3, phi=phi))


def _roll3_stats(problem, hyper, z1, z2, st):
    """Shard-partial master gradient d l_p3_loc / d z3 at the OLD round
    point (Eq. 6 steps at the old worker variables)."""
    return jax.grad(lambda z3: _l_p3_local(problem, hyper, z1, z2,
                                           st.x3, z3, st.phi))(st.z3)


def _roll3_worker(problem, hyper, z1, z2, x3, z3_old, phi, z3_new):
    g_x = jax.grad(lambda x3_: _l_p3_local(problem, hyper, z1, z2,
                                           x3_, z3_old, phi))(x3)
    x3n = tree_axpy(-hyper.eta_x, g_x, x3)
    phin = jax.tree.map(
        lambda p, x, z: p + hyper.eta_dual_inner * (x - _bcast(z, x)),
        phi, x3n, z3_new)
    return x3n, phin


def rollout3_sharded_fwd(problem, hyper, z1, z2, init: InnerState3,
                         axis: str) -> Tuple[InnerState3, tuple]:
    """K sharded rounds of Eqs. 5-7.  Returns (final, residuals): the
    per-round carries PLUS the already-psum'd aggregates, so the
    backward scan transposes without re-running any forward collective
    (`traffic_record` counts on this)."""
    def round_fn(st, _):
        agg = _psum(_roll3_stats(problem, hyper, z1, z2, st), axis)
        z3n = tree_axpy(-hyper.eta_z, agg, st.z3)
        x3n, phin = _roll3_worker(problem, hyper, z1, z2, st.x3, st.z3,
                                  st.phi, z3n)
        return InnerState3(x3=x3n, z3=z3n, phi=phin), (st, agg)

    return jax.lax.scan(round_fn, init, None, length=hyper.k_inner)


def rollout3_sharded_vjp(problem, hyper, z1, z2, residuals, ct_final,
                         axis: str):
    """d(rollout3)/d(z1, z2) against `ct_final` cotangents.

    ct_final.x3/.phi are shard-local-true, ct_final.z3 replicated-true.
    Each backward round transposes the worker/master/stats pieces with
    local jax.vjp and psums exactly the cotangent mass that crossed a
    varying consumption of a replicated value.  z1/z2 enter only through
    per-worker objectives, so their accumulated cotangents take a single
    final psum."""
    az = (tree_zeros_like(z1), tree_zeros_like(z2))

    def bwd_round(ct_acc, res_r):
        st_r, agg = res_r
        ct, (az1, az2) = ct_acc
        z3n = tree_axpy(-hyper.eta_z, agg, st_r.z3)

        _, w_vjp = jax.vjp(
            lambda z1_, z2_, x3, z3_old, phi, z3_new: _roll3_worker(
                problem, hyper, z1_, z2_, x3, z3_old, phi, z3_new),
            z1, z2, st_r.x3, st_r.z3, st_r.phi, z3n)
        d_z1w, d_z2w, d_x3, d_z3old_w, d_phi, d_z3n_w = w_vjp(
            (ct.x3, ct.phi))

        # master transpose: z3' = z3 - eta_z * agg
        ct_z3n = tree_add(ct.z3, _psum(d_z3n_w, axis))
        d_z3old_m = ct_z3n
        ct_agg = jax.tree.map(lambda g: -hyper.eta_z * g, ct_z3n)

        _, s_vjp = jax.vjp(
            lambda z1_, z2_, x3, z3_old, phi: _roll3_stats(
                problem, hyper, z1_, z2_,
                InnerState3(x3=x3, z3=z3_old, phi=phi)),
            z1, z2, st_r.x3, st_r.z3, st_r.phi)
        d_z1s, d_z2s, d_x3s, d_z3old_s, d_phis = s_vjp(ct_agg)

        ct_z3_true = tree_add(
            d_z3old_m, _psum(tree_add(d_z3old_w, d_z3old_s), axis))
        ct_new = InnerState3(x3=tree_add(d_x3, d_x3s),
                             z3=ct_z3_true,
                             phi=tree_add(d_phi, d_phis))
        return (ct_new, (tree_add(az1, tree_add(d_z1w, d_z1s)),
                         tree_add(az2, tree_add(d_z2w, d_z2s)))), None

    (ct0, (az1, az2)), _ = jax.lax.scan(
        bwd_round, (ct_final, az), residuals, reverse=True)
    del ct0                                   # init is stop-gradient'd
    return _psum(az1, axis), _psum(az2, axis)


# ---------------------------------------------------------------------------
# level-2 rollout (Eq. 11), sharded forward + hand-assembled VJP
# ---------------------------------------------------------------------------
#
# Extra structure vs level 3: the I-polytope cut terms.  The cut value
# splits as  a-part(z1, z2', z3) + psum(b-part(X3_loc))  where the
# b-part is round-invariant (X3 is a rollout input), so it is ONE
# pre-aggregate `b_agg`; the a-part and the (gamma, s) multiplier
# algebra are replicated master computation with CLOSED-FORM z2
# gradients (sum_l (gamma_l + rho2 viol_l) active_l a2_l), which keeps
# every jax.grad/vjp here collective-free.

def _l_p2_worker_local(problem, hyper, z1, x2, z2, phi, X3):
    def per_worker(data_j, x2_j, phi_j, x3_j):
        f = problem.f2(data_j, z1, x2_j, x3_j)
        r = tree_sub(x2_j, z2)
        return f + tree_dot(phi_j, r) + 0.5 * hyper.kappa2 * tree_norm_sq(r)

    return jnp.sum(jax.vmap(per_worker)(problem.data, x2, phi, X3))


def _cut_b_partial(cuts_i: FlatCuts, X3):
    """This shard's b-column contribution to the I-cut values (the
    per-worker cut scalars of Eq. 11; layer-I cuts carry zero b2)."""
    return cuts_lib.b_cols_matvec(cuts_i, None, X3)


def _cut_a_values(cuts_i: FlatCuts, z1, z2, z3, b_agg):
    """Replicated cut values: a-column contraction + the psum'd b-part."""
    raw = cuts_lib.a_cols_matvec(cuts_i, z1, z2, z3) + b_agg - cuts_i.c
    return raw * cuts_i.active


def _roll2_master(hyper, cuts_i, z1, z3, b_agg, z2, s, gamma, agg1):
    """Replicated master algebra of one Eq. 11 round: z2 step (psum'd
    worker partials + closed-form cut gradient at the OLD z2), then the
    slack / cut-multiplier updates at the new z2."""
    cutval_old = _cut_a_values(cuts_i, z1, z2, z3, b_agg)
    viol_old = (cutval_old + s) * cuts_i.active
    g_cut = cuts_lib.cut_weighted_coeff(
        cuts_i, gamma + hyper.rho2 * viol_old, "a2")
    z2n = tree_axpy(-hyper.eta_z, tree_add(agg1, g_cut), z2)

    cutval = _cut_a_values(cuts_i, z1, z2n, z3, b_agg)
    g_s = (gamma + hyper.rho2 * (cutval + s)) * cuts_i.active
    sn = jnp.maximum(0.0, s - hyper.eta_s * g_s) * cuts_i.active
    gamman = jnp.maximum(
        0.0, gamma + hyper.eta_dual_inner * (cutval + sn)) * cuts_i.active
    return z2n, sn, gamman


def _roll2_stats(problem, hyper, z1, x2, z2, phi, X3):
    """Shard-partial d l_p2_worker / d z2 at the old round point."""
    return jax.grad(lambda z2_: _l_p2_worker_local(
        problem, hyper, z1, x2, z2_, phi, X3))(z2)


def _roll2_worker(problem, hyper, z1, x2, z2_old, phi, X3, z2_new):
    g_x = jax.grad(lambda x2_: _l_p2_worker_local(
        problem, hyper, z1, x2_, z2_old, phi, X3))(x2)
    x2n = tree_axpy(-hyper.eta_x, g_x, x2)
    phin = jax.tree.map(
        lambda p, x, z: p + hyper.eta_dual_inner * (x - _bcast(z, x)),
        phi, x2n, z2_new)
    return x2n, phin


def rollout2_sharded_fwd(problem, hyper, z1, z3, X3, cuts_i: FlatCuts,
                         init: InnerState2, axis: str):
    """K sharded rounds of Eq. 11.  Returns (final, residuals, b_agg) —
    residuals carry each round's state AND its psum'd agg1, so the
    backward scan re-runs no forward collective."""
    b_agg = _psum(_cut_b_partial(cuts_i, X3), axis)

    def round_fn(st, _):
        agg1 = _psum(_roll2_stats(problem, hyper, z1, st.x2, st.z2,
                                  st.phi, X3), axis)
        z2n, sn, gamman = _roll2_master(hyper, cuts_i, z1, z3, b_agg,
                                        st.z2, st.s, st.gamma, agg1)
        x2n, phin = _roll2_worker(problem, hyper, z1, st.x2, st.z2,
                                  st.phi, X3, z2n)
        return InnerState2(x2=x2n, z2=z2n, phi=phin, s=sn,
                           gamma=gamman), (st, agg1)

    final, residuals = jax.lax.scan(round_fn, init, None,
                                    length=hyper.k_inner)
    return final, residuals, b_agg


def rollout2_sharded_vjp(problem, hyper, z1, z3, X3, cuts_i, residuals,
                         b_agg, ct_final: InnerState2, axis: str):
    """d(rollout2)/d(z1, z3, X3) against `ct_final`.

    z1 is consumed per-worker (f2) AND through the replicated a1-column
    algebra, so its cotangent accumulates on two channels — the varying
    one is psum'd, the replicated one counted once.  z3 only appears in
    the a3-columns (replicated channel); X3 only in per-worker terms and
    the b-column pre-aggregate (both shard-local-true)."""
    zero_rc = (tree_zeros_like(ct_final.z2), jnp.zeros_like(ct_final.s),
               jnp.zeros_like(ct_final.gamma))
    acc0 = (tree_zeros_like(z1), tree_zeros_like(z1),   # z1 var / rep
            tree_zeros_like(z3),                        # z3 rep
            tree_zeros_like(X3),                        # X3 var
            jnp.zeros_like(b_agg))                      # b_agg rep

    def bwd_round(ct_acc, res_r):
        st_r, agg1 = res_r
        (ct_x2, ct_phi, ct_rc), (az1v, az1r, az3r, ax3, abagg) = ct_acc
        ct_z2, ct_s, ct_gamma = ct_rc

        z2n, _, _ = _roll2_master(hyper, cuts_i, z1, z3, b_agg,
                                  st_r.z2, st_r.s, st_r.gamma, agg1)

        _, w_vjp = jax.vjp(
            lambda z1_, x2, z2_old, phi, X3_, z2_new: _roll2_worker(
                problem, hyper, z1_, x2, z2_old, phi, X3_, z2_new),
            z1, st_r.x2, st_r.z2, st_r.phi, X3, z2n)
        d_z1w, d_x2, d_z2old_w, d_phi, d_x3w, d_z2n_w = w_vjp(
            (ct_x2, ct_phi))

        # master transpose (replicated computation, counted once)
        ct_z2n_true = tree_add(ct_z2, _psum(d_z2n_w, axis))
        _, m_vjp = jax.vjp(
            lambda z1_, z3_, bagg_, z2, s, gamma, agg1_: _roll2_master(
                hyper, cuts_i, z1_, z3_, bagg_, z2, s, gamma, agg1_),
            z1, z3, b_agg, st_r.z2, st_r.s, st_r.gamma, agg1)
        (d_z1m, d_z3m, d_bagg, d_z2old_m, d_s, d_gamma,
         ct_agg1) = m_vjp((ct_z2n_true, ct_s, ct_gamma))

        _, s_vjp = jax.vjp(
            lambda z1_, x2, z2, phi, X3_: _roll2_stats(
                problem, hyper, z1_, x2, z2, phi, X3_),
            z1, st_r.x2, st_r.z2, st_r.phi, X3)
        d_z1s, d_x2s, d_z2old_s, d_phis, d_x3s = s_vjp(ct_agg1)

        ct_z2_true = tree_add(
            d_z2old_m, _psum(tree_add(d_z2old_w, d_z2old_s), axis))
        ct_new = (tree_add(d_x2, d_x2s), tree_add(d_phi, d_phis),
                  (ct_z2_true, d_s, d_gamma))
        acc = (tree_add(az1v, tree_add(d_z1w, d_z1s)),
               tree_add(az1r, d_z1m),
               tree_add(az3r, d_z3m),
               tree_add(ax3, tree_add(d_x3w, d_x3s)),
               abagg + d_bagg)
        return (ct_new, acc), None

    ct0 = (ct_final.x2, ct_final.phi,
           (ct_final.z2, ct_final.s, ct_final.gamma))
    ((_, _, _), (az1v, az1r, az3r, ax3, abagg)), _ = jax.lax.scan(
        bwd_round, (ct0, acc0), residuals, reverse=True)

    # b_agg = psum(local b-contraction(X3)): the replicated cotangent
    # flows back to every shard's own columns in full.
    _, b_vjp = jax.vjp(lambda X3_: _cut_b_partial(cuts_i, X3_), X3)
    ct_x3 = tree_add(ax3, b_vjp(abagg)[0])
    ct_z1 = tree_add(_psum(az1v, axis), az1r)
    return ct_z1, az3r, ct_x3


# ---------------------------------------------------------------------------
# mu-cut constants with worker-sharded blocks
# ---------------------------------------------------------------------------

_B_KEYS = ("b2", "b3")


def make_cut_sharded(h0, grads, point, eps, mu, bound_alpha, axis):
    """`cuts.make_cut` with the b-block inner products / norms psum'd:
    a-block terms are replicated (counted once), worker-block terms are
    shard-partial."""
    gv_rep = jnp.float32(0.0)
    sq_rep = jnp.float32(0.0)
    gv_loc = jnp.float32(0.0)
    sq_loc = jnp.float32(0.0)
    for k, g in grads.items():
        if k in _B_KEYS:
            gv_loc = gv_loc + tree_dot(g, point[k])
            sq_loc = sq_loc + tree_norm_sq(point[k])
        else:
            gv_rep = gv_rep + tree_dot(g, point[k])
            sq_rep = sq_rep + tree_norm_sq(point[k])
    loc = _psum(jnp.stack([gv_loc, sq_loc]), axis)
    gv0 = gv_rep + loc[0]
    v0_sq = sq_rep + loc[1]
    c = eps + mu * (bound_alpha + v0_sq) - h0 + gv0
    return grads, c


# ---------------------------------------------------------------------------
# the sharded cut refresh (Eqs. 23-25)
# ---------------------------------------------------------------------------

@jax.named_scope("cut_refresh")
def cut_refresh_sharded(problem: TrilevelProblem, hyper: Hyper,
                        state: AFTOState, axis: str = WORKER_AXIS
                        ) -> AFTOState:
    """`afto.cut_refresh` on a worker mesh: same math, f32-tolerance
    identical trajectories (property-tested against the single-device
    refresh).  `problem.data` and every stacked state leaf carry only
    this shard's workers; the polytopes are the local column views.

    The h_I / h_II gradients w.r.t. each shard's OWN worker variables
    ({x3_j} for Eq. 23, {x2_j}/{x3_j} for Eq. 24) are closed-form or
    locally-transposed — each worker computes its own b-block cut
    coefficients, which is exactly the paper's federated cut generation;
    the z-block (a-column) coefficients are reduced with psums via the
    hand-assembled rollout VJPs above."""
    t = state.t

    # warm-start the inner states at the current outer point (duals kept)
    inner3 = InnerState3(x3=state.X3, z3=state.z3, phi=state.inner3.phi)

    # ---- I-layer cut (Eq. 23) at (X3, z1, z2, z3)
    est3, res3 = rollout3_sharded_fwd(problem, hyper, state.z1, state.z2,
                                      inner3, axis)
    dx3 = tree_sub(state.X3, est3.x3)
    dz3 = tree_sub(state.z3, est3.z3)
    h0_i = _psum(tree_norm_sq(dx3), axis) + tree_norm_sq(dz3)
    gX3 = jax.tree.map(lambda d: 2.0 * d, dx3)       # local closed form
    gz3 = jax.tree.map(lambda d: 2.0 * d, dz3)       # replicated closed form
    ct3 = InnerState3(x3=jax.tree.map(lambda d: -2.0 * d, dx3),
                      z3=jax.tree.map(lambda d: -2.0 * d, dz3),
                      phi=tree_zeros_like(est3.phi))
    gz1, gz2 = rollout3_sharded_vjp(problem, hyper, state.z1, state.z2,
                                    res3, ct3, axis)

    bound_i = hyper.alpha1 + hyper.alpha2 + (hyper.n_workers + 1) * hyper.alpha3
    coeffs_i, c_i = make_cut_sharded(
        h0_i,
        {"a1": gz1, "a2": gz2, "a3": gz3, "b3": gX3},
        {"a1": state.z1, "a2": state.z2, "a3": state.z3, "b3": state.X3},
        hyper.eps_i, hyper.mu_i, bound_i, axis)
    cuts_i = cuts_lib.add_cut(state.cuts_i, coeffs_i, c_i, t)

    # ---- level-2 rollout under the updated I-polytope
    inner2 = InnerState2(x2=state.X2, z2=state.z2, phi=state.inner2.phi,
                         s=state.inner2.s * cuts_i.active,
                         gamma=state.inner2.gamma * cuts_i.active)
    est2, res2, b_agg = rollout2_sharded_fwd(
        problem, hyper, state.z1, state.z3, state.X3, cuts_i, inner2, axis)

    # ---- II-layer cut (Eq. 24) at (X2, X3, z1, z2, z3)
    dx2 = tree_sub(state.X2, est2.x2)
    dz2 = tree_sub(state.z2, est2.z2)
    h0_ii = _psum(tree_norm_sq(dx2), axis) + tree_norm_sq(dz2)
    gX2 = jax.tree.map(lambda d: 2.0 * d, dx2)
    gz2b = jax.tree.map(lambda d: 2.0 * d, dz2)
    ct2 = InnerState2(x2=jax.tree.map(lambda d: -2.0 * d, dx2),
                      z2=jax.tree.map(lambda d: -2.0 * d, dz2),
                      phi=tree_zeros_like(est2.phi),
                      s=jnp.zeros_like(est2.s),
                      gamma=jnp.zeros_like(est2.gamma))
    gz1b, gz3b, gX3b = rollout2_sharded_vjp(
        problem, hyper, state.z1, state.z3, state.X3, cuts_i, res2,
        b_agg, ct2, axis)

    bound_ii = hyper.alpha1 + (hyper.n_workers + 1) * (hyper.alpha2
                                                       + hyper.alpha3)
    coeffs_ii, c_ii = make_cut_sharded(
        h0_ii,
        {"a1": gz1b, "a2": gz2b, "a3": gz3b, "b2": gX2, "b3": gX3b},
        {"a1": state.z1, "a2": state.z2, "a3": state.z3,
         "b2": state.X2, "b3": state.X3},
        hyper.eps_ii, hyper.mu_ii, bound_ii, axis)
    cuts_ii = cuts_lib.add_cut(state.cuts_ii, coeffs_ii, c_ii, t)

    # the warm-started rollouts above ARE Eq. 8/12's inner estimates; the
    # single-device refresh recomputes them via CSE-merged second calls.
    gamma_k = est2.gamma

    # ---- drop inactive cuts (Eq. 25); never drop the cut just added
    fresh_i = (cuts_i.age == t).astype(jnp.float32)
    cuts_i = cuts_lib.drop_inactive(cuts_i, gamma_k + fresh_i)
    fresh_ii = (cuts_ii.age == t).astype(jnp.float32)
    cuts_ii = cuts_lib.drop_inactive(cuts_ii, state.lam + fresh_ii)

    lam = state.lam * cuts_ii.active
    return dataclasses.replace(
        state, cuts_i=cuts_i, cuts_ii=cuts_ii, lam=lam, gamma_k=gamma_k,
        inner3=est3, inner2=est2)


# ---------------------------------------------------------------------------
# communication accounting (per-step bytes the mesh actually exchanges)
# ---------------------------------------------------------------------------

def traffic_record(spec, hyper: Hyper) -> dict:
    """Analytic per-step / per-refresh all-reduce payloads in bytes (one
    logical direction, f32): an exact count of the psums the sharded
    engine performs — cut scalars, z-sized gradient partials, scalar
    norms.  Everything else (worker stacks, b-columns, data) stays
    shard-local.
    """
    na = cuts_lib.n_a_leaves(spec)
    z1 = sum(spec.sizes[:spec.nleaves[0]])
    z2 = sum(spec.sizes[spec.nleaves[0]:spec.nleaves[0]
                        + spec.nleaves[1]])
    z3 = sum(spec.sizes[spec.nleaves[0] + spec.nleaves[1]:na])
    p = hyper.p_max
    k = hyper.k_inner
    # afto_step_aux: cut-scalar psum + theta-sum psum
    step = 4 * (p + z1)
    # cut_refresh_sharded, in execution order:
    #   rollout3 fwd            k rounds x z3-sized agg
    #   rollout3 vjp            k rounds x 2 z3-sized ct psums
    #                           + final z1 + z2 accumulator psums
    #   h0_i / make_cut_i       1 + 2 scalars
    #   rollout2 fwd            1 b_agg (P,) + k rounds x z2-sized agg1
    #   rollout2 vjp            k rounds x 2 z2-sized ct psums
    #                           + final z1 accumulator psum
    #   h0_ii / make_cut_ii     1 + 2 scalars
    refresh = 4 * (3 * k * z3 + 3 * k * z2 + 2 * z1 + z2 + p + 6)
    # record branch: worker-norm scalar + theta-sum (make_gap_aux adds
    # one more (P,) cut-scalar psum only when the same iteration also
    # refreshed, i.e. step's aux was invalidated)
    gap = 4 * (1 + z1)
    return {"step_bytes": step, "refresh_bytes": refresh,
            "gap_bytes": gap}
