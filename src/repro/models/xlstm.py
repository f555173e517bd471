"""xLSTM sequence mixers: mLSTM (matrix memory) and sLSTM (scalar memory).

mLSTM training/prefill uses the chunkwise-parallel form (matmul-heavy,
MXU-friendly — this is also what the `mlstm_chunk` Pallas kernel tiles):
within a chunk, intra-chunk terms are a decayed attention-like matmul;
across chunks the (hd x hd) matrix memory C and normalizer n are carried
with a per-chunk max-stabilizer m.  Decode is the O(1) recurrent update.

sLSTM keeps a per-head scalar-memory recurrence with exponential gating
and a stabilizer state; it is inherently sequential, so training scans
over time (cheap at xlstm-125m scale).

Both cells end in a per-head layer norm (the xLSTM paper's group norm)
before the output projection.  The mLSTM read-out divides by a
per-(position, head) normalizer that can sit at its floor; the head norm
cancels that scalar, without which the block Jacobian grows with the
normalizer's inverse and the gradient grows geometrically with depth.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def mlstm_init(key, d, n_heads, head_dim, dtype):
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, n_heads, head_dim), dtype, fan_in=d),
        "wk": dense_init(ks[1], (d, n_heads, head_dim), dtype, fan_in=d),
        "wv": dense_init(ks[2], (d, n_heads, head_dim), dtype, fan_in=d),
        "wi": dense_init(ks[3], (d, n_heads), jnp.float32, fan_in=d),
        "wf": dense_init(ks[4], (d, n_heads), jnp.float32, fan_in=d),
        "fb": jnp.full((n_heads,), 3.0, jnp.float32),  # forget-bias ~ keep
        "out_norm": jnp.zeros((n_heads, head_dim), dtype),
        "wo": dense_init(ks[5], (n_heads, head_dim, d), dtype,
                         fan_in=n_heads * head_dim),
    }


def slstm_init(key, d, n_heads, head_dim, dtype):
    ks = jax.random.split(key, 7)
    return {
        "wz": dense_init(ks[0], (d, n_heads, head_dim), dtype, fan_in=d),
        "wo_gate": dense_init(ks[1], (d, n_heads, head_dim), dtype,
                              fan_in=d),
        "wi": dense_init(ks[2], (d, n_heads), jnp.float32, fan_in=d),
        "wf": dense_init(ks[3], (d, n_heads), jnp.float32, fan_in=d),
        "fb": jnp.full((n_heads,), 3.0, jnp.float32),
        "rz": dense_init(ks[4], (n_heads, head_dim, head_dim), dtype,
                         fan_in=head_dim),  # block-diag recurrent weights
        "out_norm": jnp.zeros((n_heads, head_dim), dtype),
        "wo": dense_init(ks[5], (n_heads, head_dim, d), dtype,
                         fan_in=n_heads * head_dim),
    }


def init_mlstm_state(batch, n_heads, head_dim):
    return {"c": jnp.zeros((batch, n_heads, head_dim, head_dim),
                           jnp.float32),
            "n": jnp.zeros((batch, n_heads, head_dim), jnp.float32),
            "m": jnp.full((batch, n_heads), -1e9, jnp.float32)}


def init_slstm_state(batch, n_heads, head_dim):
    return {"c": jnp.zeros((batch, n_heads, head_dim), jnp.float32),
            "n": jnp.zeros((batch, n_heads, head_dim), jnp.float32),
            "h": jnp.zeros((batch, n_heads, head_dim), jnp.float32),
            "m": jnp.full((batch, n_heads), -1e9, jnp.float32)}


def head_norm(y, scale, eps: float = 1e-6):
    """Per-head layer norm of a cell read-out y (..., H, hd), scaled by
    (1 + scale) with scale (H, hd); returns f32."""
    y = y.astype(jnp.float32)
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), axis=-1, keepdims=True)
    return (y - mu) * jax.lax.rsqrt(var + eps) \
        * (1.0 + scale.astype(jnp.float32))


# ---------------------------------------------------------------------------
# mLSTM chunkwise (this math is the Pallas kernel's oracle)
# ---------------------------------------------------------------------------

def mlstm_chunk_body(q, k, v, li, lf, state):
    """One chunk. q/k/v: (B,L,H,hd); li/lf: (B,L,H) log gates;
    state: dict(c,n,m).  Returns (y (B,L,H,hd), new_state)."""
    b, l, h, hd = q.shape
    c_prev, n_prev, m_prev = state["c"], state["n"], state["m"]

    bcum = jnp.cumsum(lf, axis=1)                     # (B,L,H) inclusive
    btot = bcum[:, -1]                                # (B,H)
    # log-decay from chunk start to position t (exclusive of t's own f? we
    # use inclusive: f applies before the write at t, standard mLSTM)
    g_inter = bcum                                    # decay applied to C_prev
    # intra-chunk log weights: D_ts = bcum_t - bcum_s + li_s for s <= t
    dmat = bcum[:, :, None] - bcum[:, None] + li[:, None]   # (B,L,L,H)
    tri = jnp.tril(jnp.ones((l, l), bool))
    dmat = jnp.where(tri[None, :, :, None], dmat, -jnp.inf)

    # stabilizer: per position max of (inter, intra)
    m_inter = g_inter + m_prev[:, None]               # (B,L,H)
    m_intra = jnp.max(dmat, axis=2)                   # (B,L,H)
    m_t = jnp.maximum(m_inter, m_intra)

    w_inter = jnp.exp(m_inter - m_t)                  # (B,L,H)
    w_intra = jnp.exp(dmat - m_t[:, :, None])         # (B,L,L,H)

    qf = q.astype(jnp.float32) / jnp.sqrt(hd)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    # intra: y_t += sum_s w_intra[t,s] (q_t . k_s) v_s
    scores = jnp.einsum("blhd,bshd->blsh", qf, kf) * w_intra
    y_intra = jnp.einsum("blsh,bshd->blhd", scores, vf)
    den_intra = jnp.sum(scores, axis=2)               # (B,L,H)

    # inter: y_t += w_inter[t] q_t C_prev ; den += w_inter q_t . n_prev
    y_inter = jnp.einsum("blhd,bhde->blhe", qf, c_prev) * w_inter[..., None]
    den_inter = jnp.einsum("blhd,bhd->blh", qf, n_prev) * w_inter

    den = jnp.abs(den_intra + den_inter)
    den = jnp.maximum(den, jnp.exp(-m_t))             # xLSTM normalizer
    y = (y_intra + y_inter) / den[..., None]

    # state update to end of chunk
    m_new = jnp.maximum(btot + m_prev, jnp.max(
        btot[:, None] - bcum + li, axis=1))           # (B,H)
    w_c = jnp.exp(btot + m_prev - m_new)              # decay on C_prev
    w_k = jnp.exp(btot[:, None] - bcum + li - m_new[:, None])  # (B,L,H)
    c_new = c_prev * w_c[:, :, None, None] \
        + jnp.einsum("blh,blhd,blhe->bhde", w_k, kf, vf)
    n_new = n_prev * w_c[..., None] + jnp.einsum("blh,blhd->bhd", w_k, kf)
    return y, {"c": c_new, "n": n_new, "m": m_new}


def _gates(params, x):
    li = jnp.einsum("bld,dh->blh", x.astype(jnp.float32), params["wi"])
    lf = jax.nn.log_sigmoid(
        jnp.einsum("bld,dh->blh", x.astype(jnp.float32), params["wf"])
        + params["fb"])
    return li, lf


def mlstm_apply(params, x, chunk: int = 256, state=None
                ) -> Tuple[jnp.ndarray, dict]:
    """x: (B,S,d) -> (y (B,S,d), state)."""
    b, s, d = x.shape
    h, hd = params["wq"].shape[1], params["wq"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    li, lf = _gates(params, x)
    if state is None:
        state = init_mlstm_state(b, h, hd)

    n_chunks = max(1, s // chunk)
    cl = s // n_chunks

    def split(a):
        return a.reshape(b, n_chunks, cl, *a.shape[2:]).transpose(
            1, 0, 2, *range(3, a.ndim + 1))

    def body(st, inp):
        qc, kc, vc, lic, lfc = inp
        y, st = mlstm_chunk_body(qc, kc, vc, lic, lfc, st)
        return st, y

    state, ys = jax.lax.scan(body, state,
                             (split(q), split(k), split(v),
                              split(li), split(lf)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)
    y = head_norm(y, params["out_norm"]).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", y, params["wo"]), state


def mlstm_decode(params, x, state) -> Tuple[jnp.ndarray, dict]:
    """O(1) recurrent step; x: (B,1,d)."""
    b = x.shape[0]
    h, hd = params["wq"].shape[1], params["wq"].shape[2]
    q = jnp.einsum("bd,dhk->bhk", x[:, 0], params["wq"])
    k = jnp.einsum("bd,dhk->bhk", x[:, 0], params["wk"])
    v = jnp.einsum("bd,dhk->bhk", x[:, 0], params["wv"])
    li, lf = _gates(params, x)
    li, lf = li[:, 0], lf[:, 0]                        # (B,H)

    m_new = jnp.maximum(lf + state["m"], li)
    wf = jnp.exp(lf + state["m"] - m_new)[..., None]
    wi = jnp.exp(li - m_new)[..., None]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    c = state["c"] * wf[..., None] \
        + (wi[..., None] * kf[..., None] * vf[:, :, None])
    n = state["n"] * wf + wi * kf
    qf = q.astype(jnp.float32) / jnp.sqrt(hd)
    num = jnp.einsum("bhd,bhde->bhe", qf, c)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)),
                      jnp.exp(-m_new))
    y = head_norm(num / den[..., None], params["out_norm"])
    y = y.astype(x.dtype)[:, None]                      # (B,1,H,hd)
    out = jnp.einsum("bshk,hkd->bsd", y, params["wo"])
    return out, {"c": c, "n": n, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def _slstm_step(params, st, zt, ot_gate, lit, lft):
    """One recurrence step. zt/ot_gate: (B,H,hd); lit/lft: (B,H)."""
    rz = jnp.einsum("bhd,hde->bhe", st["h"].astype(params["rz"].dtype),
                    params["rz"]).astype(jnp.float32)
    z = jnp.tanh(zt.astype(jnp.float32) + rz)
    m_new = jnp.maximum(lft + st["m"], lit)
    wf = jnp.exp(lft + st["m"] - m_new)[..., None]
    wi = jnp.exp(lit - m_new)[..., None]
    c = wf * st["c"] + wi * z
    n = wf * st["n"] + wi
    h = jax.nn.sigmoid(ot_gate.astype(jnp.float32)) * c \
        / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "h": h, "m": m_new}


def slstm_apply(params, x, state=None) -> Tuple[jnp.ndarray, dict]:
    b, s, d = x.shape
    h_heads, hd = params["wz"].shape[1], params["wz"].shape[2]
    z = jnp.einsum("bsd,dhk->bshk", x, params["wz"])
    og = jnp.einsum("bsd,dhk->bshk", x, params["wo_gate"])
    li = jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32), params["wi"])
    lf = jax.nn.log_sigmoid(
        jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32), params["wf"])
        + params["fb"])
    if state is None:
        state = init_slstm_state(b, h_heads, hd)

    def body(st, inp):
        zt, ot, lit, lft = inp
        st = _slstm_step(params, st, zt, ot, lit, lft)
        return st, st["h"]

    state, hs = jax.lax.scan(
        body, state,
        (z.transpose(1, 0, 2, 3), og.transpose(1, 0, 2, 3),
         li.transpose(1, 0, 2), lf.transpose(1, 0, 2)))
    y = head_norm(hs.transpose(1, 0, 2, 3),
                  params["out_norm"]).astype(x.dtype)   # (B,S,H,hd)
    return jnp.einsum("bshk,hkd->bsd", y, params["wo"]), state


def slstm_decode(params, x, state) -> Tuple[jnp.ndarray, dict]:
    z = jnp.einsum("bd,dhk->bhk", x[:, 0], params["wz"])
    og = jnp.einsum("bd,dhk->bhk", x[:, 0], params["wo_gate"])
    li = jnp.einsum("bd,dh->bh", x[:, 0].astype(jnp.float32), params["wi"])
    lf = jax.nn.log_sigmoid(
        jnp.einsum("bd,dh->bh", x[:, 0].astype(jnp.float32), params["wf"])
        + params["fb"])
    state = _slstm_step(params, state, z, og, li, lf)
    y = head_norm(state["h"], params["out_norm"]).astype(x.dtype)[:, None]
    return jnp.einsum("bshk,hkd->bsd", y, params["wo"]), state
