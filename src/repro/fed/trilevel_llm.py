"""AFTO instantiated for LLM-scale architectures (the paper's robust-HPO
trilevel, Eq. 31, with the model zoo as level 3).

Variables (DESIGN.md §5):
  x1 = phi   : per-category regularization log-strengths (d1 = 4: embed /
               mixer / mlp / other) — exact everywhere (tiny).
  x2 = p     : adversarial embedding perturbation; worker j owns block j
               (Eq. 31's p' = [p'_1..p'_N]), so local copies store only
               their own (b_local, seq, d_model) block — exact by the
               block structure of Eq. 31, not an approximation.
  x3 = w     : model weights; worker copies are a leading-(N,) stacked
               param tree sharded (worker -> data axis, tensor dims ->
               model axis).

Cut storage: phi-blocks exact; x2/x3/z2/z3 blocks either EXACT (stacked
model-sized coefficient trees — the paper-faithful baseline whose memory
blow-up the dry-run quantifies) or SKETCHED into an r-dim count-sketch
subspace (beyond-paper; see fed/sketch.py).

Worker gradients in Eq. 16 never reference the master's z directly (f1
depends only on local variables; z enters L_p through theta/lambda terms
whose x-gradients are the stale duals and cut coefficients), so the only
per-worker stale state is (theta_j, lambda) — small — and asynchrony at
LLM scale is exact, not approximated.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.data import stream as stream_lib
from repro.fed.sketch import (sketch as _sketch, sketch_stacked,
                              unsketch as _unsketch, unsketch_stacked)
from repro.models import config as mcfg
from repro.models import transformer as tfm
from repro.utils.tree import (tree_axpy, tree_dot, tree_norm_sq, tree_sub,
                              tree_zeros_like)


def _register(cls, data_fields, meta_fields=()):
    jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=list(meta_fields))
    return cls


# ---------------------------------------------------------------------------
# hyper / state
# ---------------------------------------------------------------------------

N_PHI = 4  # regularization categories: embed / mixer / mlp / other


@dataclasses.dataclass(frozen=True)
class FedHyper:
    n_workers: int = 16
    k_inner: int = 1
    p_max: int = 2
    cut_mode: str = "exact"        # exact | sketch
    sketch_r: int = 4096
    adv_penalty: float = 1.0       # c in Eq. 31
    eta_x: float = 1e-2
    eta_z: float = 1e-2
    eta_lambda: float = 1e-2
    eta_theta: float = 1e-2
    eta_dual_inner: float = 1e-2
    kappa3: float = 1.0
    eps_i: float = 1e-3
    eps_ii: float = 1e-3
    mu_i: float = 0.5
    mu_ii: float = 0.5
    alpha: float = 1e4             # shared variable-norm bound
    alpha4: float = 100.0
    alpha5: float = 100.0
    c1_floor: float = 1e-3
    c2_floor: float = 1e-3
    remat: bool = True
    unroll: bool = False            # python-unroll layer loops (dry-run)
    first_order_cuts: bool = False  # stop-grad through the inner rollout
    seed_i: int = 1                # sketch seeds per cut layer
    seed_ii: int = 2

    def c1(self, t):
        return jnp.maximum(self.c1_floor,
                           1.0 / (self.eta_lambda * (t + 1.0) ** 0.25))

    def c2(self, t):
        return jnp.maximum(self.c2_floor,
                           1.0 / (self.eta_theta * (t + 1.0) ** 0.25))


@dataclasses.dataclass
class LLMCutSet:
    """Cuts over (z1, z2, z3, {x2_j}, {x3_j}).

    exact mode: a2/a3 are (P,)-stacked trees, b2/b3 are (P,N,)-stacked.
    sketch mode: a2/a3 are (P, r) arrays, b2/b3 are (P, N, r)."""
    a1: jnp.ndarray               # (P, N_PHI) — always exact
    a2: Any
    a3: Any
    b2: Any
    b3: Any
    c: jnp.ndarray                # (P,)
    active: jnp.ndarray           # (P,)
    age: jnp.ndarray              # (P,)


_register(LLMCutSet, ["a1", "a2", "a3", "b2", "b3", "c", "active", "age"])


@dataclasses.dataclass
class FedLLMState:
    X1: jnp.ndarray               # (N, N_PHI)
    X2: jnp.ndarray               # (N, b_local, seq, d_model) own blocks
    X3: Any                       # (N,)-stacked model params
    z1: jnp.ndarray               # (N_PHI,)
    z2: jnp.ndarray               # (N, b_local, seq, d_model)
    z3: Any                       # model params
    theta: jnp.ndarray            # (N, N_PHI) consensus duals
    lam: jnp.ndarray              # (P,)
    cuts: LLMCutSet               # II-layer polytope (enters L_p)
    cuts_i: LLMCutSet             # I-layer polytope (enters level-2 inner)
    gamma_k: jnp.ndarray          # (P,) last inner multipliers (drop rule)
    stale_lam: jnp.ndarray        # (N, P)
    stale_theta: jnp.ndarray      # (N, N_PHI)
    t: jnp.ndarray                # iteration


_register(FedLLMState, ["X1", "X2", "X3", "z1", "z2", "z3", "theta", "lam",
                        "cuts", "cuts_i", "gamma_k", "stale_lam",
                        "stale_theta", "t"])


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_n(tree, n):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def _empty_cuts(hyper: FedHyper, x2_block, params) -> LLMCutSet:
    p, n = hyper.p_max, hyper.n_workers
    if hyper.cut_mode == "sketch":
        r = hyper.sketch_r
        a2 = jnp.zeros((p, r), jnp.float32)
        a3 = jnp.zeros((p, r), jnp.float32)
        b2 = jnp.zeros((p, n, r), jnp.float32)
        b3 = jnp.zeros((p, n, r), jnp.float32)
    else:
        def stack_p(tree):
            return jax.tree.map(
                lambda x: jnp.zeros((p,) + x.shape, x.dtype), tree)

        def stack_pn(tree):
            return jax.tree.map(
                lambda x: jnp.zeros((p, n) + x.shape, x.dtype), tree)

        a2 = stack_p(_stack_n(x2_block, n))   # z2 is the (N,...) stack
        a3 = stack_p(params)
        b2 = stack_pn(x2_block)
        b3 = stack_pn(params)
    return LLMCutSet(
        a1=jnp.zeros((p, N_PHI), jnp.float32), a2=a2, a3=a3, b2=b2, b3=b3,
        c=jnp.zeros((p,), jnp.float32),
        active=jnp.zeros((p,), jnp.float32),
        age=jnp.full((p,), -1, jnp.int32))


def init_fed_state(cfg: mcfg.ModelConfig, hyper: FedHyper, key,
                   b_local: int, seq: int) -> FedLLMState:
    n = hyper.n_workers
    params = tfm.init_params(cfg, key)
    x2_block = jnp.zeros((b_local, seq, cfg.d_model), jnp.bfloat16)
    p = hyper.p_max
    return FedLLMState(
        X1=jnp.full((n, N_PHI), -3.0, jnp.float32),
        X2=jnp.zeros((n,) + x2_block.shape, x2_block.dtype),
        X3=_stack_n(params, n),
        z1=jnp.full((N_PHI,), -3.0, jnp.float32),
        z2=jnp.zeros((n,) + x2_block.shape, x2_block.dtype),
        z3=params,
        theta=jnp.zeros((n, N_PHI), jnp.float32),
        lam=jnp.zeros((p,), jnp.float32),
        cuts=_empty_cuts(hyper, x2_block, params),
        cuts_i=_empty_cuts(hyper, x2_block, params),
        gamma_k=jnp.zeros((p,), jnp.float32),
        stale_lam=jnp.zeros((n, p), jnp.float32),
        stale_theta=jnp.zeros((n, N_PHI), jnp.float32),
        t=jnp.zeros((), jnp.int32))


def batch_stream(cfg: mcfg.ModelConfig, n_workers: int, b_local: int,
                 seq: int, seed=0, zipf_a: float = 1.2) -> stream_lib.Stream:
    """Device-resident token stream for the LLM AFTO step: each worker's
    per-iteration {tokens, val_tokens} chunk is synthesized inside the
    scan from fold-in keys (`repro.data.stream`), replacing the
    host-side `data.synthetic.make_token_stream` round-trip.  Batches
    stack to the `afto_llm_step` layout ((N, b_local, seq) int32);
    tokens double as val_tokens exactly like the host driver's chunks.
    """
    def sample(key):
        toks = stream_lib.zipf_tokens(key, (b_local, seq),
                                      cfg.vocab_size, zipf_a)
        return {"tokens": toks, "val_tokens": toks}

    return stream_lib.make_stream(sample, n_workers, seed)


# ---------------------------------------------------------------------------
# objectives (per worker)
# ---------------------------------------------------------------------------

def _phi_category(path) -> int:
    name = ""
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            name = str(entry.key)
            break
    if name in ("embed", "lm_head", "enc_pos"):
        return 0
    if name in ("wq", "wk", "wv", "wo", "xwq", "xwk", "xwv", "xwo",
                "in_proj", "out_proj", "conv_w", "xproj", "wz", "wo_gate",
                "rz", "a_log"):
        return 1
    if name in ("wi", "wg", "router"):
        return 2
    return 3


def reg_term(phi, params):
    """sum_cat exp(phi_cat) * ||params_cat||^2 / size_cat."""
    sq = [jnp.zeros((), jnp.float32)] * N_PHI
    cnt = [0] * N_PHI

    leaves = jax.tree_util.tree_leaves_with_path(params)
    for path, leaf in leaves:
        c = _phi_category(path)
        sq[c] = sq[c] + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        cnt[c] += int(leaf.size)
    total = jnp.zeros((), jnp.float32)
    for c in range(N_PHI):
        if cnt[c]:
            total = total + jnp.exp(phi[c]) * sq[c] / cnt[c]
    return total


def f1_loss(cfg, w_j, batch_j, hyper: FedHyper):
    """Clean validation CE for one worker."""
    return tfm.train_loss(cfg, w_j, batch_j["val_tokens"],
                          batch_j.get("val_frames"), remat=hyper.remat,
                          unroll=hyper.unroll)


def f3_loss(cfg, phi, p_j, w_j, batch_j, hyper: FedHyper):
    """Perturbed train CE + e^phi regularization (level 3, minimized)."""
    ce = tfm.train_loss(cfg, w_j, batch_j["tokens"],
                        batch_j.get("frames"), remat=hyper.remat,
                        unroll=hyper.unroll, embed_perturbation=p_j)
    return ce + reg_term(phi, w_j)


def f2_loss(cfg, phi, p_j, w_j, batch_j, hyper: FedHyper):
    """Negated adversarial objective (level 2 maximizes)."""
    ce = tfm.train_loss(cfg, w_j, batch_j["tokens"],
                        batch_j.get("frames"), remat=hyper.remat,
                        unroll=hyper.unroll, embed_perturbation=p_j)
    return -(ce - hyper.adv_penalty
             * jnp.mean(jnp.square(p_j.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# cut algebra (mode-dispatched)
# ---------------------------------------------------------------------------

def _dot_stacked_p(stacked, v):
    """<a_l, v> per cut slot; stacked leaves have leading (P,)."""
    leaves = jax.tree.leaves(jax.tree.map(
        lambda a, x: jnp.einsum(
            "pd,d->p", a.reshape(a.shape[0], -1).astype(jnp.float32),
            x.reshape(-1).astype(jnp.float32)), stacked, v))
    return sum(leaves)


def _dot_stacked_pn(stacked, V):
    leaves = jax.tree.leaves(jax.tree.map(
        lambda b, x: jnp.einsum(
            "pnd,nd->p",
            b.reshape(b.shape[0], b.shape[1], -1).astype(jnp.float32),
            x.reshape(x.shape[0], -1).astype(jnp.float32)), stacked, V))
    return sum(leaves)


def eval_llm_cuts(hyper: FedHyper, cuts: LLMCutSet, z1, z2, z3, X2, X3,
                  seed: int):
    val = jnp.einsum("pd,d->p", cuts.a1, z1)
    if hyper.cut_mode == "sketch":
        r = hyper.sketch_r
        s_z2 = _sketch(z2, seed, r)
        s_z3 = _sketch(z3, seed, r)
        s_x2 = sketch_stacked(X2, seed, r)   # (N,r)
        s_x3 = sketch_stacked(X3, seed, r)
        val = val + cuts.a2 @ s_z2 + cuts.a3 @ s_z3 \
            + jnp.einsum("pnr,nr->p", cuts.b2, s_x2) \
            + jnp.einsum("pnr,nr->p", cuts.b3, s_x3)
    else:
        val = val + _dot_stacked_p(cuts.a2, z2) \
            + _dot_stacked_p(cuts.a3, z3) \
            + _dot_stacked_pn(cuts.b2, X2) \
            + _dot_stacked_pn(cuts.b3, X3)
    return (val - cuts.c) * cuts.active


def _contract_b(hyper: FedHyper, cuts: LLMCutSet, weights_np, block: str,
                template, seed: int):
    """sum_l w[j,l] * b_{l,j} as a per-worker tree (the worker-update cut
    gradient)."""
    w = weights_np * cuts.active[None, :]
    b = getattr(cuts, block)
    if hyper.cut_mode == "sketch":
        coeff = jnp.einsum("np,pnr->nr", w, b)                  # (N,r)
        return unsketch_stacked(template, coeff, seed)
    return jax.tree.map(
        lambda bb: jnp.einsum("np,pn...->n...", w,
                              bb.astype(jnp.float32)).astype(bb.dtype), b)


def _contract_a(hyper: FedHyper, cuts: LLMCutSet, weights_p, block: str,
                template, seed: int):
    w = weights_p * cuts.active
    a = getattr(cuts, block)
    if hyper.cut_mode == "sketch":
        coeff = jnp.einsum("p,pr->r", w, a)
        return _unsketch(template, coeff, seed)
    return jax.tree.map(
        lambda aa: jnp.tensordot(w, aa.astype(jnp.float32),
                                 axes=(0, 0)).astype(aa.dtype), a)


def _store_block(hyper: FedHyper, cur, grad_tree, slot, seed: int,
                 per_worker: bool):
    """Write one cut's coefficient block into slot (sketch or exact)."""
    if hyper.cut_mode == "sketch":
        r = hyper.sketch_r
        if per_worker:
            s = sketch_stacked(grad_tree, seed, r)
        else:
            s = _sketch(grad_tree, seed, r)
        return cur.at[slot].set(s)
    return jax.tree.map(lambda buf, g: buf.at[slot].set(g.astype(buf.dtype)),
                        cur, grad_tree)


# ---------------------------------------------------------------------------
# the per-iteration AFTO step (Eqs. 16-21, LLM instantiation)
# ---------------------------------------------------------------------------

def afto_llm_step(cfg: mcfg.ModelConfig, hyper: FedHyper,
                  state: FedLLMState, batch: Dict[str, Any],
                  active: jnp.ndarray) -> FedLLMState:
    """batch: worker-stacked {"val_tokens": (N,b,S), "tokens": (N,b,S),
    optional frames}.  active: (N,) mask."""
    t = state.t
    seed = hyper.seed_ii

    # ---- workers (Eq. 16)
    g3_f1 = jax.vmap(lambda w, bj: jax.grad(
        lambda ww: f1_loss(cfg, ww, bj, hyper))(w))(
        state.X3, batch)
    g3_cut = _contract_b(hyper, state.cuts, state.stale_lam, "b3",
                         state.z3, seed)
    g2_cut = _contract_b(hyper, state.cuts, state.stale_lam, "b2",
                         state.X2[0], seed)

    def bmask(x):
        return active.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)

    X3 = jax.tree.map(
        lambda x, gf, gc: x - hyper.eta_x * bmask(x)
        * (gf + gc).astype(x.dtype),
        state.X3, g3_f1, g3_cut)
    X2 = jax.tree.map(
        lambda x, gc: x - hyper.eta_x * bmask(x) * gc.astype(x.dtype),
        state.X2, g2_cut)
    # x1: f1 has no phi-gradient; theta (stale) + no cut block -> dual pull
    X1 = state.X1 - hyper.eta_x * active[:, None] * state.stale_theta

    # ---- master (Eqs. 17-19)
    gz1 = -jnp.sum(state.theta, axis=0) \
        + jnp.einsum("p,pd->d", state.lam * state.cuts.active, state.cuts.a1)
    z1 = state.z1 - hyper.eta_z * gz1
    gz2 = _contract_a(hyper, state.cuts, state.lam, "a2", state.z2, seed)
    z2 = jax.tree.map(lambda z, g: z - hyper.eta_z * g.astype(z.dtype),
                      state.z2, gz2)
    gz3 = _contract_a(hyper, state.cuts, state.lam, "a3", state.z3, seed)
    z3 = jax.tree.map(lambda z, g: z - hyper.eta_z * g.astype(z.dtype),
                      state.z3, gz3)

    # ---- duals (Eqs. 20/21)
    cutval = eval_llm_cuts(hyper, state.cuts, z1, z2, z3, X2, X3, seed)
    lam = jnp.clip(
        state.lam + hyper.eta_lambda * (cutval - hyper.c1(t) * state.lam),
        0.0, jnp.sqrt(hyper.alpha4)) * state.cuts.active
    r_theta = jnp.sqrt(hyper.alpha5) / N_PHI
    theta = jnp.clip(
        state.theta + hyper.eta_theta
        * ((X1 - z1[None]) - hyper.c2(t) * state.theta),
        -r_theta, r_theta)

    # ---- stale views of newly-active workers
    stale_lam = jnp.where(active[:, None] > 0, lam[None], state.stale_lam)
    stale_theta = jnp.where(active[:, None] > 0, theta, state.stale_theta)

    return dataclasses.replace(
        state, X1=X1, X2=X2, X3=X3, z1=z1, z2=z2, z3=z3, theta=theta,
        lam=lam, stale_lam=stale_lam, stale_theta=stale_theta, t=t + 1)


# ---------------------------------------------------------------------------
# cut refresh (Eqs. 23-25, LLM instantiation)
# ---------------------------------------------------------------------------

def _rollout3(cfg, hyper: FedHyper, z1, Z2, X3_0, z3_0, batch):
    """K rounds of the level-3 federated ADMM (Eqs. 5-7); differentiable
    w.r.t. (z1, Z2).  Duals start at zero each refresh (re-initialized —
    the paper leaves inner warm-starting unspecified).  Duals are f32
    (the ascent update promotes to f32, so the scan carry must start
    f32)."""
    phi0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                        X3_0)

    def round_fn(carry, _):
        X3, z3, duals = carry

        def worker_grad(w, p_j, d_j, dual_j):
            def local(w_):
                cons = tree_dot(dual_j, tree_sub(w_, z3)) \
                    + 0.5 * hyper.kappa3 * tree_norm_sq(tree_sub(w_, z3))
                return f3_loss(cfg, z1, p_j, w_, d_j, hyper) + cons
            return jax.grad(local)(w)

        g = jax.vmap(worker_grad)(X3, Z2, batch, duals)
        X3_new = jax.tree.map(
            lambda x, gg: (x - hyper.eta_x * gg.astype(x.dtype)), X3, g)
        # master step at old X3 (Eq. 6): grad_z3 = -sum_j(dual + k(x-z))
        gz = jax.tree.map(
            lambda d, x, z: -jnp.sum(
                d + hyper.kappa3 * (x - z[None]), axis=0),
            duals, jax.tree.map(lambda a: a.astype(jnp.float32), X3),
            jax.tree.map(lambda a: a.astype(jnp.float32), z3))
        z3_new = jax.tree.map(
            lambda z, gg: z - hyper.eta_z * gg.astype(z.dtype), z3, gz)
        duals_new = jax.tree.map(
            lambda d, x, z: d + hyper.eta_dual_inner
            * (x.astype(jnp.float32) - z.astype(jnp.float32)[None]),
            duals, X3_new, z3_new)
        return (X3_new, z3_new, duals_new), None

    (X3_k, z3_k, _), _ = jax.lax.scan(
        round_fn, (X3_0, z3_0, phi0), None, length=hyper.k_inner)
    return X3_k, z3_k


def _rollout2(cfg, hyper: FedHyper, z1, z3, X2_0, Z2_0, X3, batch,
              cuts_i: LLMCutSet):
    """K rounds of the level-2 inner ADMM: workers ascend the adversarial
    objective; the I-layer polytope enters via multipliers gamma."""
    gamma0 = jnp.zeros_like(cuts_i.c)
    s0 = jnp.zeros_like(cuts_i.c)
    seed = hyper.seed_i

    def round_fn(carry, _):
        X2, Z2, gamma, s = carry

        def worker_grad(p_j, w_j, d_j, z2_j):
            def local(p_):
                cons = 0.5 * hyper.kappa3 * jnp.sum(
                    jnp.square((p_ - z2_j).astype(jnp.float32)))
                return f2_loss(cfg, z1, p_, w_j, d_j, hyper) + cons
            return jax.grad(local)(p_j)

        g = jax.vmap(worker_grad)(X2, X3, batch, Z2)
        # cut-gradient contribution on x2 blocks (gamma-weighted)
        g_cut = _contract_b(hyper, cuts_i, jnp.broadcast_to(
            gamma[None], (hyper.n_workers,) + gamma.shape), "b2", X2[0],
            seed)
        X2_new = jax.tree.map(
            lambda x, ga, gc: x - hyper.eta_x * (ga + gc).astype(x.dtype),
            X2, g, g_cut)
        Z2_new = Z2 - hyper.eta_z * hyper.kappa3 * (Z2 - X2)
        # I-layer cut value at (z1, z2'=Z2_new, z3, {x3_j}=X3); x2 blocks
        # do not participate in I-layer cuts (their b2 slots are zero)
        cutval = eval_llm_cuts(hyper, cuts_i, z1, Z2_new, z3,
                               X2_new, X3, seed)
        s_new = jnp.maximum(0.0, s - hyper.eta_x * (gamma + cutval + s)) \
            * cuts_i.active
        gamma_new = jnp.maximum(
            0.0, gamma + hyper.eta_dual_inner * (cutval + s_new)) \
            * cuts_i.active
        return (X2_new, Z2_new, gamma_new, s_new), None

    (X2_k, Z2_k, gamma_k, _), _ = jax.lax.scan(
        round_fn, (X2_0, Z2_0, gamma0, s0), None, length=hyper.k_inner)
    return X2_k, Z2_k, gamma_k


def _add_llm_cut(hyper: FedHyper, cuts: LLMCutSet, grads: Dict[str, Any],
                 point: Dict[str, Any], h0, eps, mu, bound, t, seed
                 ) -> LLMCutSet:
    # integer eviction scores (f32 1e9+age loses age bits; see
    # core/cuts.add_cut)
    score = jnp.where(cuts.active > 0, cuts.age, jnp.int32(-(2 ** 30)))
    slot = jnp.argmin(score)
    gv0 = jnp.float32(0.0)
    v0_sq = jnp.float32(0.0)
    for k in grads:
        gv0 = gv0 + tree_dot(grads[k], point[k])
        v0_sq = v0_sq + tree_norm_sq(point[k])
    c = eps + mu * (bound + v0_sq) - h0 + gv0
    return LLMCutSet(
        a1=cuts.a1.at[slot].set(grads.get(
            "a1", jnp.zeros((N_PHI,), jnp.float32))),
        a2=_store_block(hyper, cuts.a2, grads["a2"], slot, seed, False)
        if "a2" in grads else cuts.a2,
        a3=_store_block(hyper, cuts.a3, grads["a3"], slot, seed, False)
        if "a3" in grads else cuts.a3,
        b2=_store_block(hyper, cuts.b2, grads["b2"], slot, seed, True)
        if "b2" in grads else cuts.b2,
        b3=_store_block(hyper, cuts.b3, grads["b3"], slot, seed, True)
        if "b3" in grads else cuts.b3,
        c=cuts.c.at[slot].set(c),
        active=cuts.active.at[slot].set(1.0),
        age=cuts.age.at[slot].set(jnp.asarray(t, jnp.int32)))


def cut_refresh_llm(cfg: mcfg.ModelConfig, hyper: FedHyper,
                    state: FedLLMState, batch) -> FedLLMState:
    t = state.t
    n = hyper.n_workers

    # ---- I-layer cut (Eq. 23): h_I = ||[X3; z3] - rollout3(z1, Z2)||^2
    def h_i(X3, z3, z1, Z2):
        ro = _rollout3(cfg, hyper, z1, Z2,
                       jax.lax.stop_gradient(X3),
                       jax.lax.stop_gradient(z3), batch)
        if hyper.first_order_cuts:
            ro = jax.lax.stop_gradient(ro)
        X3_k, z3_k = ro
        return tree_norm_sq(tree_sub(X3, X3_k)) \
            + tree_norm_sq(tree_sub(z3, z3_k))

    h0_i, g_i = jax.value_and_grad(h_i, argnums=(0, 1, 2, 3))(
        state.X3, state.z3, state.z1, state.z2)
    gX3, gz3, gz1, gz2 = g_i
    bound_i = (n + 3) * hyper.alpha
    cuts_i = _add_llm_cut(
        hyper, state.cuts_i,
        {"a1": gz1, "a2": gz2, "a3": gz3, "b3": gX3},
        {"a1": state.z1, "a2": state.z2, "a3": state.z3, "b3": state.X3},
        h0_i, hyper.eps_i, hyper.mu_i, bound_i, t, hyper.seed_i)

    # ---- II-layer cut (Eq. 24): h_II = ||[X2; Z2] - rollout2(...)||^2
    def h_ii(X2, Z2, z1, z3, X3):
        ro = _rollout2(cfg, hyper, z1, z3,
                       jax.lax.stop_gradient(X2),
                       jax.lax.stop_gradient(Z2), X3, batch, cuts_i)
        X2_k, Z2_k, gamma_k = ro
        if hyper.first_order_cuts:
            X2_k, Z2_k = (jax.lax.stop_gradient(X2_k),
                          jax.lax.stop_gradient(Z2_k))
        h = jnp.sum(jnp.square((X2 - X2_k).astype(jnp.float32))) \
            + jnp.sum(jnp.square((Z2 - Z2_k).astype(jnp.float32)))
        return h, gamma_k

    (h0_ii, gamma_k), g_ii = jax.value_and_grad(
        h_ii, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        state.X2, state.z2, state.z1, state.z3, state.X3)
    gX2, gZ2, gz1b, gz3b, gX3b = g_ii
    bound_ii = (2 * n + 2) * hyper.alpha
    cuts_ii = _add_llm_cut(
        hyper, state.cuts,
        {"a1": gz1b, "a2": gZ2, "a3": gz3b, "b2": gX2, "b3": gX3b},
        {"a1": state.z1, "a2": state.z2, "a3": state.z3,
         "b2": state.X2, "b3": state.X3},
        h0_ii, hyper.eps_ii, hyper.mu_ii, bound_ii, t, hyper.seed_ii)

    # ---- drop rule (Eq. 25), newly-added cuts exempt
    fresh_i = (cuts_i.age == t).astype(jnp.float32)
    keep_i = ((jnp.abs(gamma_k) > 1e-8).astype(jnp.float32) + fresh_i) > 0
    cuts_i = dataclasses.replace(
        cuts_i, active=cuts_i.active * keep_i.astype(jnp.float32))
    fresh_ii = (cuts_ii.age == t).astype(jnp.float32)
    keep_ii = ((jnp.abs(state.lam) > 1e-8).astype(jnp.float32)
               + fresh_ii) > 0
    cuts_ii = dataclasses.replace(
        cuts_ii, active=cuts_ii.active * keep_ii.astype(jnp.float32))

    return dataclasses.replace(
        state, cuts_i=cuts_i, cuts=cuts_ii,
        lam=state.lam * cuts_ii.active, gamma_k=gamma_k)


# ---------------------------------------------------------------------------
# plain (non-trilevel) reference training step
# ---------------------------------------------------------------------------

def plain_train_step(cfg: mcfg.ModelConfig, params, opt_state, tokens,
                     frames=None, optimizer=None, remat: bool = True,
                     unroll: bool = False):
    from repro.optim import adamw
    from repro.optim.optimizers import apply_updates
    opt = optimizer or adamw(3e-4, weight_decay=0.1)
    loss, grads = jax.value_and_grad(
        lambda p: tfm.train_loss(cfg, p, tokens, frames, unroll=unroll,
                                 remat=remat))(params)
    updates, opt_state = opt.update(grads, opt_state, params)
    return apply_updates(params, updates), opt_state, loss
