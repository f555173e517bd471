"""Jit'd public wrappers around the Pallas kernels.

`interpret=None` (the default everywhere) resolves in
`kernels.platform`: Mosaic on a TPU backend, the Pallas interpreter
(bit-accurate semantics, kernel body in Python) elsewhere.  The cut
path routes here; the models do not call the attention and mLSTM
kernels (`ModelConfig.attn_impl` picks a jnp attention path).

Autodiff contract for the cut path: `cut_eval` (and the fused inner
round) are differentiable THROUGH the kernels to arbitrary order.  The
forward, the hand-written backward kernels (the `da = g a^T` rank-1 and
`dv = g^T A` row-reduction in `kernels/cut_eval.py`) and every
higher-order term route through the {mv, vm, outer} primitive closure in
`kernels.cut_ad`, whose JVP/transpose rules recurse into each other —
so the grad-of-grad'd inner-Lagrangian paths (cut refresh, Eqs. 23/24)
no longer force `impl="ref"`.  (The old caveat that a linearized
`pallas_call` has no JVP rule is resolved by the primitives, not by a
`custom_jvp`-over-`custom_vjp` composition — the latter has no transpose
for its tangent calls and dies under reverse mode.)
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.kernels import cut_ad as _cut_ad
from repro.kernels import cut_eval as _cut_eval_mod
from repro.kernels import flash_attention as _flash_mod
from repro.kernels import inner_round as _round_mod
from repro.kernels import mlstm_chunk as _mlstm_mod
from repro.kernels.platform import on_tpu, resolve_interpret

# trace-count pins (CI-style regression guards): incremented at TRACE
# time, so a warm jit cache keeps them flat and an unroll regression
# (e.g. mlstm_sequence falling back to a host chunk loop) multiplies
# the per-trace count.
TRACE_COUNTS: collections.Counter = collections.Counter()


# ---------------------------------------------------------------------------
# cut_eval — the (P, D) cut contraction, AD-complete through the kernel
# ---------------------------------------------------------------------------
# The custom-VJP plumbing that used to live here (kernel forward, jnp
# backward, no JVP) is replaced by the cut_ad primitive closure: the
# backward algebra da = (g*active) v^T / dv = (g*active)^T A now runs on
# the hand-written rank1/vecmat kernels via the mv transpose rule, and
# the epilogue (- c) * active is plain jnp whose autodiff supplies
# dc/dactive.

@functools.partial(jax.jit, static_argnames=("block_d", "interpret", "impl"))
def cut_eval(a, v, c, active, block_d: int = None,
             interpret: bool = None, impl: str = None):
    """(A @ v - c) * active — the single routing point for cut mat-vecs.

    impl="pallas": the Pallas kernels (interpret off-TPU, Mosaic on TPU)
    via the `cut_ad` primitives — forward, reverse, and arbitrary-order
    grad-of-grad all stay kernel-backed, and the sweep vmap batches
    natively.  impl="ref": the identical-math jnp mat-vec (the test
    oracle).  impl=None auto-routes: the Mosaic kernels on TPU, the jnp
    form elsewhere — off-TPU the kernel only exists in interpret mode,
    an emulation-order correctness tool (measured 3-8x slower per call
    at quickstart D and ~1000x at paper-scale D), while XLA compiles the
    jnp form to the same wide contraction the kernel implements.

    block_d defaults to the kernel's full tile; the kernel itself clamps
    the tile to the (128-aligned) variable space, so small cut spaces
    aren't padded to a full paper-scale tile."""
    if impl is None:
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return (a.astype(jnp.float32) @ v.astype(jnp.float32) - c) * active
    if block_d is None:
        block_d = _cut_eval_mod.BLOCK_D
    raw = _cut_ad.matvec(a, v, block_d=block_d, interpret=interpret)
    return (raw - c) * active


# ---------------------------------------------------------------------------
# fused level-2 inner-ADMM cut round
# ---------------------------------------------------------------------------

def _fused_round_math(mv, vm, a, v, g_other, mask, c, active, s, gamma,
                      eta_z, eta_s, eta_dual, rho2):
    """The round algebra on abstract mv/vm contractions — instantiated
    with jnp (the oracle) or the cut_ad primitives (the kernel-backed
    tangent path).  Mirrors `inner.rollout2`'s round body exactly."""
    cv0 = (mv(a, v) - c) * active
    viol = (cv0 + s) * active
    w = (gamma + rho2 * viol) * active
    v_new = v - eta_z * (g_other + vm(w, a) * mask)
    cv1 = (mv(a, v_new) - c) * active
    g_s = (gamma + rho2 * (cv1 + s)) * active
    s_new = jnp.maximum(0.0, s - eta_s * g_s) * active
    gamma_new = jnp.maximum(0.0, gamma + eta_dual * (cv1 + s_new)) * active
    return v_new, cv1, s_new, gamma_new


def _fused_round_ref(a, v, g_other, mask, c, active, s, gamma, *,
                     eta_z, eta_s, eta_dual, rho2):
    af = a.astype(jnp.float32)
    return _fused_round_math(
        lambda A, x: af @ x.astype(jnp.float32),
        lambda g, A: g.astype(jnp.float32) @ af,
        a, v.astype(jnp.float32), g_other.astype(jnp.float32),
        mask.astype(jnp.float32), c, active, s, gamma,
        eta_z, eta_s, eta_dual, rho2)


def _fused_round_prims(block_d, interpret, eta_z, eta_s, eta_dual, rho2,
                       a, v, g_other, mask, c, active, s, gamma):
    """The same round decomposed onto the cut_ad primitives: three
    kernel-backed contractions, transposable/differentiable to any
    order.  This is the tangent (and hence the whole AD) path of the
    fused op; the monolithic two-pass kernel stays on the primal."""
    mv = functools.partial(_cut_ad.matvec, block_d=block_d,
                           interpret=interpret)
    vm = functools.partial(_cut_ad.vecmat, block_d=block_d,
                           interpret=interpret)
    return _fused_round_math(
        lambda A, x: mv(A, x), lambda g, A: vm(g, A),
        a, v.astype(jnp.float32), g_other.astype(jnp.float32),
        mask.astype(jnp.float32), c, active, s, gamma,
        eta_z, eta_s, eta_dual, rho2)


@functools.partial(jax.custom_jvp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _fused_round_p(block_d, interpret, eta_z, eta_s, eta_dual, rho2,
                   a, v, g_other, mask, c, active, s, gamma):
    return _round_mod.fused_cut_round(
        a, v, g_other, mask, c, active, s, gamma,
        eta_z=eta_z, eta_s=eta_s, eta_dual=eta_dual, rho2=rho2,
        block_d=block_d, interpret=interpret)


@_fused_round_p.defjvp
def _fused_round_jvp(block_d, interpret, eta_z, eta_s, eta_dual, rho2,
                     primals, tangents):
    # primal through the two-pass fused kernel; tangents through the
    # primitive decomposition (same math, one extra streamed pass),
    # which the cut_ad closure keeps transposable — so reverse mode and
    # grad-of-grad through the fused round stay kernel-backed.
    primal_out = _fused_round_p(block_d, interpret, eta_z, eta_s,
                                eta_dual, rho2, *primals)
    fn = functools.partial(_fused_round_prims, block_d, interpret,
                           eta_z, eta_s, eta_dual, rho2)
    _, tangent_out = jax.jvp(fn, primals, tangents)
    return primal_out, tangent_out


@functools.partial(jax.jit, static_argnames=(
    "eta_z", "eta_s", "eta_dual", "rho2", "block_d", "interpret", "impl"))
def fused_cut_round(a, v, g_other, mask, c, active, s, gamma, *,
                    eta_z: float, eta_s: float, eta_dual: float,
                    rho2: float, block_d: int = None,
                    interpret: bool = None, impl: str = None):
    """One fused level-2 inner-ADMM cut round (see kernels/inner_round).

    Returns (v_new, cutval_new, s_new, gamma_new).  impl="pallas": the
    single two-pass Pallas kernel on the primal, the `cut_ad` primitive
    decomposition on every tangent/cotangent (differentiable to any
    order).  impl="ref": the identical-math jnp decomposition — the
    scan-of-jnp oracle `inner.rollout2` uses off-TPU.  impl=None
    auto-routes like `cut_eval`."""
    if impl is None:
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return _fused_round_ref(a, v, g_other, mask, c, active, s, gamma,
                                eta_z=eta_z, eta_s=eta_s,
                                eta_dual=eta_dual, rho2=rho2)
    interpret = resolve_interpret(interpret)
    if block_d is None:
        block_d = _cut_eval_mod.BLOCK_D
    return _fused_round_p(block_d, interpret, eta_z, eta_s, eta_dual,
                          rho2, a, v, g_other, mask, c, active, s, gamma)


# ---------------------------------------------------------------------------
# attention / mLSTM
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = None):
    """Pads S/T to block multiples, calls the kernel, unpads."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    bq = min(block_q, max(8, s))
    bk = min(block_k, max(8, t))
    s_pad = ((s + bq - 1) // bq) * bq
    t_pad = ((t + bk - 1) // bk) * bk
    # padded K positions must never win the softmax: causal masking
    # handles q_pad; for k_pad rely on causal (k_pos > q_pos). For
    # non-causal inputs no mask covers the padding — require exact
    # block multiples there.
    if not causal and (t_pad != t or s_pad != s):
        raise ValueError(
            "non-causal flash_attention requires block-aligned shapes: "
            f"got q seq len {s} (block_q={bq}, padded {s_pad}) and "
            f"k/v seq len {t} (block_k={bk}, padded {t_pad}); pad the "
            "inputs to block multiples or use causal=True")
    qp = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    out = _flash_mod.flash_attention(qp, kp, vp, causal=causal,
                                     window=window, block_q=bq, block_k=bk,
                                     interpret=interpret)
    return out[:, :s]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mlstm_chunk(q, k, v, li, lf, c, n, m, interpret: bool = None):
    return _mlstm_mod.mlstm_chunk(q, k, v, li, lf, c, n, m,
                                  interpret=interpret)


def mlstm_sequence(q, k, v, li, lf, state, chunk: int = 256,
                   interpret: bool = None):
    """Full-sequence chunkwise mLSTM via the kernel: q/k/v (B,S,H,hd),
    li/lf (B,S,H); state dict(c,n,m) as in models.xlstm.

    The full chunks run as ONE `lax.scan` over stacked chunk slices
    (the kernel body is traced once regardless of sequence length —
    pinned by `TRACE_COUNTS["mlstm_seq_body"]`); a ragged tail shorter
    than `chunk` is a single extra kernel call at its own length (a
    second trace, but only when S % chunk != 0)."""
    b, s, h, hd = q.shape
    n_full = s // chunk
    tail = s - n_full * chunk

    def to_bh(a):                     # (B,S,H,...) -> (B,H,S,...)
        return a.transpose(0, 2, 1, 3) if a.ndim == 4 \
            else a.transpose(0, 2, 1)[..., None]

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    lib, lfb = to_bh(li), to_bh(lf)
    c = state["c"]
    n = state["n"][:, :, None]
    m = state["m"][:, :, None, None]

    ys = []
    if n_full:
        def chunked(a):               # (B,H,S,x) -> (n_full, B,H,chunk,x)
            lead = a[:, :, :n_full * chunk]
            return lead.reshape(b, h, n_full, chunk,
                                lead.shape[-1]).transpose(2, 0, 1, 3, 4)

        def body(carry, xs):
            TRACE_COUNTS["mlstm_seq_body"] += 1
            c, n, m = carry
            qc, kc, vc, lic, lfc = xs
            y, c, n, m = mlstm_chunk(qc, kc, vc, lic, lfc, c, n, m,
                                     interpret=interpret)
            return (c, n, m), y

        (c, n, m), ys_scan = jax.lax.scan(
            body, (c, n, m),
            tuple(chunked(x) for x in (qb, kb, vb, lib, lfb)))
        ys.append(ys_scan.transpose(1, 2, 0, 3, 4)
                  .reshape(b, h, n_full * chunk, hd))
    if tail:
        sl = slice(n_full * chunk, s)
        y_t, c, n, m = mlstm_chunk(qb[:, :, sl], kb[:, :, sl],
                                   vb[:, :, sl], lib[:, :, sl],
                                   lfb[:, :, sl], c, n, m,
                                   interpret=interpret)
        ys.append(y_t)
    y = jnp.concatenate(ys, axis=2).transpose(0, 2, 1, 3)
    return y, {"c": c, "n": n[:, :, 0], "m": m[:, :, 0, 0]}
