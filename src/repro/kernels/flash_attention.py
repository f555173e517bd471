"""Pallas TPU kernel: blockwise (flash) GQA attention forward.

Streams KV in (block_k x head_dim) VMEM tiles against a resident
(block_q x head_dim) query tile with the usual running-max/denominator
online softmax, so the (S x T) score matrix never exists in HBM —
this is the kernel that replaces the dry-run's naive attention on real
TPUs (and the §Perf chunked-attention iteration mirrors it in jnp).

Grid: (batch, q_heads, q_blocks, k_blocks), k innermost/sequential.
Causal + sliding-window masking happens on block offsets inside the
kernel; GQA maps q-head h to kv-head h // (H // Hkv) in the BlockSpec
index maps, so no KV replication is materialized.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -2.0 ** 20


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q, block_k, causal, window, scale):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)               # (bk, hd)

    s = q @ k.T                                       # (bq, bk)

    qb = pl.program_id(2)
    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + p @ v
    m_scr[...] = m_new

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B,S,H,hd); k/v: (B,T,Hkv,hd) -> (B,S,H,hd).  S % block_q == 0
    and T % block_k == 0 (the ops wrapper pads)."""
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / (hd ** 0.5)

    qt = q.transpose(0, 2, 1, 3)       # (B,H,S,hd)
    kt = k.transpose(0, 2, 1, 3)       # (B,Hkv,T,hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, h, s // block_q, t // block_k)
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, causal=causal,
                               window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bb, hh, qb, kb: (bb, hh, qb, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, qb, kb, g=g: (bb, hh // g, kb, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, qb, kb, g=g: (bb, hh // g, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bb, hh, qb, kb: (bb, hh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # denominator l
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
