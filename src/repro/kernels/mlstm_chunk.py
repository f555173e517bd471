"""Pallas TPU kernel: chunkwise mLSTM (xLSTM's matrix-memory mixer).

One kernel invocation processes one (batch, head) pair's chunk of L
tokens against the carried (hd x hd) matrix memory C, normalizer n and
stabilizer m, producing the chunk's outputs and the updated state.  The
math mirrors `repro.models.xlstm.mlstm_chunk_body` (the oracle).

TPU adaptation: the recurrence is evaluated in its chunkwise-parallel
form so the inner ops are (L x hd)x(hd x hd) and (L x L) matmuls on the
MXU; the matrix memory tile stays resident in VMEM across the chunk.
Grid: (batch, heads) — independent programs, no sequential axis; the
sequential scan over chunks lives in the caller (ops.mlstm_sequence).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _mlstm_chunk_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref,
                        c_ref, n_ref, m_ref,
                        y_ref, c_out_ref, n_out_ref, m_out_ref, *, scale):
    q = q_ref[0, 0].astype(jnp.float32) * scale        # (L, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32)              # (L, 1)
    lf = lf_ref[0, 0].astype(jnp.float32)              # (L, 1)
    c_prev = c_ref[0, 0].astype(jnp.float32)           # (hd, hd)
    n_prev = n_ref[0, 0].astype(jnp.float32)           # (1, hd)
    m_prev = m_ref[0, 0].astype(jnp.float32)           # (1, 1)

    l = q.shape[0]
    bcum = jnp.cumsum(lf, axis=0)                      # (L,1) inclusive
    btot = bcum[l - 1:l]                               # (1,1)

    # intra-chunk decay matrix D[t,s] = bcum_t - bcum_s + li_s (s <= t)
    dmat = bcum - bcum.T + li.T                        # (L,L)
    row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    dmat = jnp.where(col <= row, dmat, NEG_INF)

    m_inter = bcum + m_prev                            # (L,1)
    m_intra = jnp.max(dmat, axis=1, keepdims=True)     # (L,1)
    m_t = jnp.maximum(m_inter, m_intra)

    w_inter = jnp.exp(m_inter - m_t)                   # (L,1)
    w_intra = jnp.exp(dmat - m_t)                      # (L,L)

    scores = (q @ k.T) * w_intra                       # (L,L)
    y_intra = scores @ v                               # (L,hd)
    den_intra = jnp.sum(scores, axis=1, keepdims=True)

    y_inter = (q @ c_prev) * w_inter                   # (L,hd)
    den_inter = (q @ n_prev.T) * w_inter               # (L,1)

    den = jnp.maximum(jnp.abs(den_intra + den_inter), jnp.exp(-m_t))
    y_ref[0, 0] = ((y_intra + y_inter) / den).astype(y_ref.dtype)

    # end-of-chunk state
    m_new = jnp.maximum(btot + m_prev,
                        jnp.max(btot - bcum + li, axis=0, keepdims=True))
    w_c = jnp.exp(btot + m_prev - m_new)               # (1,1)
    w_k = jnp.exp(btot - bcum + li - m_new)            # (L,1)
    c_out_ref[0, 0] = (c_prev * w_c + (k * w_k).T @ v).astype(
        c_out_ref.dtype)
    n_out_ref[0, 0] = (n_prev * w_c + jnp.sum(k * w_k, axis=0,
                                              keepdims=True)).astype(
        n_out_ref.dtype)
    m_out_ref[0, 0] = m_new.astype(m_out_ref.dtype)


def mlstm_chunk(q, k, v, li, lf, c, n, m, *,
                interpret: Optional[bool] = None):
    """One chunk for all (batch, head) pairs.

    q/k/v: (B,H,L,hd); li/lf: (B,H,L,1); c: (B,H,hd,hd); n: (B,H,1,hd);
    m: (B,H,1,1).  Returns (y (B,H,L,hd), c', n', m')."""
    b, h, l, hd = q.shape
    scale = 1.0 / (hd ** 0.5)
    grid = (b, h)
    spec = lambda *dims: pl.BlockSpec((1, 1) + dims,
                                      lambda bb, hh: (bb, hh, 0, 0))
    kernel = functools.partial(_mlstm_chunk_kernel, scale=scale)
    y, c2, n2, m2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec(l, hd), spec(l, hd), spec(l, hd),
                  spec(l, 1), spec(l, 1),
                  spec(hd, hd), spec(1, hd), spec(1, 1)],
        out_specs=[spec(l, hd), spec(hd, hd), spec(1, hd), spec(1, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v, li, lf, c, n, m)
    return y, c2, n2, m2
