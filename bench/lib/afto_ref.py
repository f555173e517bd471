"""Plain reference of AFTO (the paper's Algorithm 1) on a small trilevel
problem: one master iteration (Eqs. 16-21), the periodic cut refresh
(inner ADMM rollouts, Eqs. 5-12, and the mu-cuts of Eqs. 23-25) and the
stationarity gap (Eqs. 26-27).  Nothing here imports the program.

A problem is (f1, f2, f3, data) with f(data_j, x1, x2, x3) per worker,
all minimised.  Variables are pytrees; worker copies carry a leading
(N,) axis.  A polytope is a dict of coefficient trees
{a1, a2, a3 (P, ...), b2, b3 (P, N, ...)} with offsets c, an active
mask and ages; cut l reads <a_l, z> + sum_j <b_lj, x_j> - c_l <= 0.

The master iteration at mask m (N,):
  x_j -= eta_x m_j (grad f1_j(x_j) + [stale theta_j; sum_l w_jl b_lj])
        with w_jl = stale_lam_jl active_l
  z1  -= eta_z (-sum_j theta_j + sum_l lam_l act_l a1_l), z2, z3 likewise
        with the a2, a3 blocks
  lam  = clip(lam + eta_l (g(z, x) - c1(t) lam), 0, sqrt(alpha4)) act
  theta_j = clip(theta_j + eta_th ((x1_j - z1) - c2(t) theta_j),
                 +-sqrt(alpha5)/d1)
  stale views of active workers take lam, theta and t + 1.

Every t_pre iterations (t < t1) the refresh adds an I-layer cut from
h_I = ||[X3; z3] - phi_I(z1, z2)||^2, phi_I the K-round level-3 ADMM
rollout, and a II-layer cut from h_II = ||[X2; z2] - phi_II(z1, z3, X3)||^2,
phi_II the K-round level-2 rollout under the I-polytope, each as
<grad h(v0), v> <= eps + mu (alpha + ||v0||^2) - h(v0) + <grad h(v0), v0>,
into the first free slot or over the oldest; then drops cuts whose
multipliers (gamma of the level-2 rollout, lam) are zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCKS = ("a1", "a2", "a3", "b2", "b3")


def tmap(f, *t):
    return jax.tree.map(f, *t)


def tdot(a, b):
    return sum(jnp.sum(x * y) for x, y in zip(jax.tree.leaves(a),
                                              jax.tree.leaves(b)))


def tsq(a):
    return tdot(a, a)


def tsub(a, b):
    return tmap(jnp.subtract, a, b)


def taxpy(alpha, x, y):
    return tmap(lambda u, v: alpha * u + v, x, y)


def stack(tree, n):
    return tmap(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape) + 0.0,
                tree)


def zeros_like(tree):
    return tmap(jnp.zeros_like, tree)


def bmask(mask, x):
    return mask.reshape((-1,) + (1,) * (x.ndim - 1))


# ---------------------------------------------------------------------------
# polytopes
# ---------------------------------------------------------------------------


def empty_cuts(p, n, z1, z2, z3):
    lead = lambda t, k: tmap(lambda x: jnp.zeros(k + x.shape, x.dtype), t)  # noqa: E731
    return {"a1": lead(z1, (p,)), "a2": lead(z2, (p,)), "a3": lead(z3, (p,)),
            "b2": lead(z2, (p, n)), "b3": lead(z3, (p, n)),
            "c": jnp.zeros((p,)), "active": jnp.zeros((p,)),
            "age": jnp.full((p,), -1, jnp.int32)}


def _dot_p(stacked, v):
    return sum(jnp.einsum("pd,d->p", a.reshape(a.shape[0], -1),
                          x.reshape(-1))
               for a, x in zip(jax.tree.leaves(stacked), jax.tree.leaves(v)))


def _dot_pn(stacked, v):
    return sum(jnp.einsum("pnd,nd->p", b.reshape(b.shape[0], b.shape[1], -1),
                          x.reshape(x.shape[0], -1))
               for b, x in zip(jax.tree.leaves(stacked), jax.tree.leaves(v)))


def cut_values(cuts, z1, z2, z3, X2=None, X3=None):
    val = _dot_p(cuts["a1"], z1) + _dot_p(cuts["a2"], z2) \
        + _dot_p(cuts["a3"], z3)
    if X2 is not None:
        val = val + _dot_pn(cuts["b2"], X2)
    if X3 is not None:
        val = val + _dot_pn(cuts["b3"], X3)
    return (val - cuts["c"]) * cuts["active"]


def weighted(cuts, w, block):
    """sum_l w_l block_l."""
    return tmap(lambda a: jnp.tensordot(w, a, axes=(0, 0)), cuts[block])


def per_worker(cuts, w_np, block):
    """sum_l w[j, l] b_{l, j} for each worker j, (N, ...)."""
    w = w_np * cuts["active"][None]
    return tmap(lambda b: jnp.einsum("np,pn...->n...", w, b), cuts[block])


def add_cut(cuts, grads, point, h0, eps, mu, bound, t):
    gv0 = sum(tdot(grads[k], point[k]) for k in grads)
    v0 = sum(tsq(point[k]) for k in grads)
    c = eps + mu * (bound + v0) - h0 + gv0
    score = jnp.where(cuts["active"] > 0, cuts["age"], -(2 ** 30))
    slot = jnp.argmin(score)
    out = dict(cuts)
    for k in BLOCKS:
        new = grads.get(k)
        out[k] = tmap(lambda buf, g: buf.at[slot].set(g), cuts[k],
                      new if new is not None else zeros_like(
                          tmap(lambda b: b[0], cuts[k])))
    out["c"] = cuts["c"].at[slot].set(c)
    out["active"] = cuts["active"].at[slot].set(1.0)
    out["age"] = cuts["age"].at[slot].set(t)
    return out


def drop(cuts, multipliers):
    keep = (jnp.abs(multipliers) > 1e-8).astype(jnp.float32)
    return dict(cuts, active=cuts["active"] * keep)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


def init_state(h, n, x1, x2, x3):
    p = h["p_max"]
    X1, X2, X3 = stack(x1, n), stack(x2, n), stack(x3, n)
    return {"X1": X1, "X2": X2, "X3": X3, "z1": x1, "z2": x2, "z3": x3,
            "theta": zeros_like(X1), "lam": jnp.zeros((p,)),
            "cuts_i": empty_cuts(p, n, x1, x2, x3),
            "cuts_ii": empty_cuts(p, n, x1, x2, x3),
            "gamma_k": jnp.zeros((p,)),
            "inner3": {"x3": X3, "z3": x3, "phi": zeros_like(X3)},
            "inner2": {"x2": X2, "z2": x2, "phi": zeros_like(X2),
                       "s": jnp.zeros((p,)), "gamma": jnp.zeros((p,))},
            "stale_lam": jnp.zeros((n, p)), "stale_theta": zeros_like(X1),
            "t": jnp.zeros((), jnp.int32)}


def _c(h, eta, t):
    return jnp.maximum(h["c1_floor"], 1.0 / (eta * (t + 1.0) ** 0.25))


def f1_grads(prob, X1, X2, X3):
    f1 = prob["f1"]
    return jax.vmap(lambda d, a, b, c: jax.grad(
        lambda u, v, w: f1(d, u, v, w), argnums=(0, 1, 2))(a, b, c))(
        prob["data"], X1, X2, X3)


def master_step(prob, h, st, mask):
    t = st["t"].astype(jnp.float32)
    cuts = st["cuts_ii"]
    g1f, g2f, g3f = f1_grads(prob, st["X1"], st["X2"], st["X3"])
    g1 = tmap(jnp.add, g1f, st["stale_theta"])
    g2 = tmap(jnp.add, g2f, per_worker(cuts, st["stale_lam"], "b2"))
    g3 = tmap(jnp.add, g3f, per_worker(cuts, st["stale_lam"], "b3"))

    def step(X, g):
        return tmap(lambda x, gg: x - h["eta_x"] * bmask(mask, x) * gg, X, g)

    X1, X2, X3 = step(st["X1"], g1), step(st["X2"], g2), step(st["X3"], g3)
    lam_a = st["lam"] * cuts["active"]
    theta_sum = tmap(lambda th: jnp.sum(th, axis=0), st["theta"])
    z1 = taxpy(-h["eta_z"], taxpy(-1.0, theta_sum,
                                  weighted(cuts, lam_a, "a1")), st["z1"])
    z2 = taxpy(-h["eta_z"], weighted(cuts, lam_a, "a2"), st["z2"])
    z3 = taxpy(-h["eta_z"], weighted(cuts, lam_a, "a3"), st["z3"])
    g = cut_values(cuts, z1, z2, z3, X2, X3)
    lam = jnp.clip(st["lam"] + h["eta_lambda"]
                   * (g - _c(h, h["eta_lambda"], t) * st["lam"]),
                   0.0, jnp.sqrt(h["alpha4"])) * cuts["active"]
    r = jnp.sqrt(h["alpha5"]) / h["d1"]
    c2 = jnp.maximum(h["c2_floor"],
                     1.0 / (h["eta_theta"] * (t + 1.0) ** 0.25))
    theta = tmap(lambda th, x, z: jnp.clip(
        th + h["eta_theta"] * ((x - z[None]) - c2 * th), -r, r),
        st["theta"], X1, z1)
    on = mask > 0
    return dict(st, X1=X1, X2=X2, X3=X3, z1=z1, z2=z2, z3=z3, lam=lam,
                theta=theta,
                stale_lam=jnp.where(on[:, None], lam[None], st["stale_lam"]),
                stale_theta=tmap(lambda s, f: jnp.where(
                    bmask(on, s), f, s), st["stale_theta"], theta),
                t=st["t"] + 1)


# ---------------------------------------------------------------------------
# inner rollouts and the refresh
# ---------------------------------------------------------------------------


def rollout3(prob, h, z1, z2, init):
    f3 = prob["f3"]

    def lp3(x3, z3, phi):
        def one(d, x, ph):
            r = tsub(x, z3)
            return f3(d, z1, z2, x) + tdot(ph, r) + 0.5 * h["kappa3"] * tsq(r)
        return jnp.sum(jax.vmap(one)(prob["data"], x3, phi))

    def rnd(st, _):
        x3, z3, phi = st["x3"], st["z3"], st["phi"]
        x_new = taxpy(-h["eta_x"], jax.grad(lp3, 0)(x3, z3, phi), x3)
        z_new = taxpy(-h["eta_z"], jax.grad(lp3, 1)(x3, z3, phi), z3)
        phi_new = tmap(lambda p, x, z: p + h["eta_dual_inner"] * (x - z[None]),
                       phi, x_new, z_new)
        return {"x3": x_new, "z3": z_new, "phi": phi_new}, None

    return jax.lax.scan(rnd, init, None, length=h["k_inner"])[0]


def rollout2(prob, h, z1, z3, X3, cuts_i, init):
    f2 = prob["f2"]
    act = cuts_i["active"]

    def lp2(x2, z2, phi, s, gamma):
        def one(d, x, ph, x3):
            r = tsub(x, z2)
            return f2(d, z1, x, x3) + tdot(ph, r) + 0.5 * h["kappa2"] * tsq(r)
        total = jnp.sum(jax.vmap(one)(prob["data"], x2, phi, X3))
        viol = (cut_values(cuts_i, z1, z2, z3, None, X3) + s) * act
        return total + jnp.sum(gamma * viol) + 0.5 * h["rho2"] * jnp.sum(
            viol ** 2)

    def rnd(st, _):
        args = (st["x2"], st["z2"], st["phi"], st["s"], st["gamma"])
        x_new = taxpy(-h["eta_x"], jax.grad(lp2, 0)(*args), st["x2"])
        z_new = taxpy(-h["eta_z"], jax.grad(lp2, 1)(*args), st["z2"])
        cv = cut_values(cuts_i, z1, z_new, z3, None, X3)
        g_s = (st["gamma"] + h["rho2"] * (cv + st["s"])) * act
        s_new = jnp.maximum(0.0, st["s"] - h["eta_s"] * g_s) * act
        phi_new = tmap(lambda p, x, z: p + h["eta_dual_inner"] * (x - z[None]),
                       st["phi"], x_new, z_new)
        cv_new = cut_values(cuts_i, z1, z_new, z3, None, X3)
        gamma_new = jnp.maximum(0.0, st["gamma"] + h["eta_dual_inner"]
                                * (cv_new + s_new)) * act
        return {"x2": x_new, "z2": z_new, "phi": phi_new, "s": s_new,
                "gamma": gamma_new}, None

    return jax.lax.scan(rnd, init, None, length=h["k_inner"])[0]


def refresh(prob, h, st):
    t = st["t"]
    n = h["n_workers"]
    sg = jax.lax.stop_gradient
    inner3 = {"x3": st["X3"], "z3": st["z3"], "phi": st["inner3"]["phi"]}

    def h_i(X3, z3, z1, z2):
        est = rollout3(prob, h, z1, z2, sg(inner3))
        return tsq(tsub(X3, est["x3"])) + tsq(tsub(z3, est["z3"]))

    h0, (gX3, gz3, gz1, gz2) = jax.value_and_grad(h_i, (0, 1, 2, 3))(
        st["X3"], st["z3"], st["z1"], st["z2"])
    cuts_i = add_cut(
        st["cuts_i"], {"a1": gz1, "a2": gz2, "a3": gz3, "b3": gX3},
        {"a1": st["z1"], "a2": st["z2"], "a3": st["z3"], "b3": st["X3"]},
        h0, h["eps_i"], h["mu_i"],
        h["alpha1"] + h["alpha2"] + (n + 1) * h["alpha3"], t)
    inner2 = dict(st["inner2"], x2=st["X2"], z2=st["z2"],
                  s=st["inner2"]["s"] * cuts_i["active"],
                  gamma=st["inner2"]["gamma"] * cuts_i["active"])

    def h_ii(X2, z2, z1, z3, X3):
        est = rollout2(prob, h, z1, z3, X3, cuts_i, sg(inner2))
        return tsq(tsub(X2, est["x2"])) + tsq(tsub(z2, est["z2"]))

    h0, (gX2, gz2, gz1, gz3, gX3) = jax.value_and_grad(
        h_ii, (0, 1, 2, 3, 4))(st["X2"], st["z2"], st["z1"], st["z3"],
                               st["X3"])
    cuts_ii = add_cut(
        st["cuts_ii"],
        {"a1": gz1, "a2": gz2, "a3": gz3, "b2": gX2, "b3": gX3},
        {"a1": st["z1"], "a2": st["z2"], "a3": st["z3"], "b2": st["X2"],
         "b3": st["X3"]}, h0, h["eps_ii"], h["mu_ii"],
        h["alpha1"] + (n + 1) * (h["alpha2"] + h["alpha3"]), t)
    inner2_k = rollout2(prob, h, st["z1"], st["z3"], st["X3"], cuts_i,
                        inner2)
    gamma_k = inner2_k["gamma"]
    cuts_i = drop(cuts_i, gamma_k + (cuts_i["age"] == t))
    cuts_ii = drop(cuts_ii, st["lam"] + (cuts_ii["age"] == t))
    return dict(st, cuts_i=cuts_i, cuts_ii=cuts_ii,
                lam=st["lam"] * cuts_ii["active"], gamma_k=gamma_k,
                inner3=rollout3(prob, h, st["z1"], st["z2"], inner3),
                inner2=inner2_k)


def gap_sq(prob, h, st):
    """||grad of L_p||^2 at the state, with the projected dual residuals
    (Eqs. 26-27)."""
    cuts = st["cuts_ii"]
    lam_a = st["lam"] * cuts["active"]
    g1f, g2f, g3f = f1_grads(prob, st["X1"], st["X2"], st["X3"])
    w = jnp.broadcast_to(lam_a[None], (h["n_workers"],) + lam_a.shape)
    g1 = tmap(jnp.add, g1f, st["theta"])
    g2 = tmap(jnp.add, g2f, per_worker(cuts, w, "b2"))
    g3 = tmap(jnp.add, g3f, per_worker(cuts, w, "b3"))
    gap = tsq(g1) + tsq(g2) + tsq(g3)
    r = jnp.sqrt(h["alpha5"]) / h["d1"]
    step = tmap(lambda th, x, z: jnp.clip(
        th + h["eta_theta"] * (x - z[None]), -r, r), st["theta"], st["X1"],
        st["z1"])
    gap = gap + tsq(tmap(lambda a, b: (a - b) / h["eta_theta"],
                         st["theta"], step))
    theta_sum = tmap(lambda th: jnp.sum(th, axis=0), st["theta"])
    gap = gap + tsq(taxpy(-1.0, theta_sum, weighted(cuts, lam_a, "a1"))) \
        + tsq(weighted(cuts, lam_a, "a2")) + tsq(weighted(cuts, lam_a, "a3"))
    cv = cut_values(cuts, st["z1"], st["z2"], st["z3"], st["X2"], st["X3"])
    res = (st["lam"] - jnp.clip(st["lam"] + h["eta_lambda"] * cv, 0.0,
                                jnp.sqrt(h["alpha4"]))) / h["eta_lambda"]
    return gap + jnp.sum((res * cuts["active"]) ** 2)


def solve(prob, h, st, masks, record_every, cast=None):
    """The whole trajectory over masks (T, N): (final state, gap at every
    `record_every`-th iteration and at the last, NaN elsewhere).  `cast`,
    where given, rounds the state after each update (the control's
    storage precision)."""
    T = masks.shape[0]
    cast = cast or (lambda s: s)

    def body(st, xs):
        mask, it = xs
        st = cast(master_step(prob, h, st, mask))
        do = (st["t"] % h["t_pre"] == 0) & (st["t"] - 1 < h["t1"])
        st = cast(jax.lax.cond(do, lambda s: refresh(prob, h, s),
                               lambda s: s, st))
        rec = ((it + 1) % record_every == 0) | (it == T - 1)
        gap = jax.lax.cond(rec, lambda s: gap_sq(prob, h, s),
                           lambda s: jnp.float32(jnp.nan), st)
        return st, gap

    st, gaps = jax.lax.scan(body, st, (masks, jnp.arange(T)))
    return st, gaps
