"""Pallas TPU kernel: one fused level-2 inner-ADMM cut round.

One round of the Eq. 11 Jacobi sweep touches the canonical (P, D) cut
matrix three times when expressed as separate ops — the cut values at
the old consensus point (inside the Eq. 6 master gradient), the weighted
row-combination that IS that gradient's cut term, and the cut values at
the new point (the Eq. 11 slack/gamma steps).  XLA runs those as three
HBM passes over A.  This kernel fuses the whole cut side of the round
into ONE `pallas_call` that streams A exactly twice (the minimum: the
second mat-vec depends on the first's result through the z2 update):

  phase 0 (mv pass)   : acc    = A @ v                 tile-accumulated
      at the last tile: cutval0 = (acc - c) * active
                        viol    = (cutval0 + s) * active
                        w       = (gamma + rho2 * viol) * active
  phase 1 (fused pass): per D tile j —
                        g_cut_j = w^T A_j                      (Eq. 6 cut term)
                        v_new_j = v_j - eta_z*(g_other_j + g_cut_j * mask_j)
                        acc2   += A_j @ v_new_j
      at the last tile: cutval1 = (acc2 - c) * active
                        s'      = max(0, s - eta_s*(gamma
                                      + rho2*(cutval1 + s)) * active) * active
                        gamma'  = max(0, gamma
                                      + eta_dual*(cutval1 + s')) * active

`g_other` is the flattened non-cut part of the Eq. 6 master gradient
(zeros outside the z2 columns) and `mask` selects the z2 (a2-block)
columns, so v_new differs from v only where the round actually updates
the consensus variable.  The grid is (2, n_tiles): the TPU iterates the
grid lexicographically on one core, so the phase-0 accumulator and the
weight vector sit in scratch VMEM and are complete before phase 1 reads
them, the same way `kernels/mlstm_chunk.py` keeps its matrix memory
resident across a chunk.  The step scalars (eta_z, eta_s, eta_dual,
rho2) are jit-static hyper-parameters and close over the kernel body.

The identical-math jnp oracle and the AD story (a `custom_jvp` whose
tangents run through the `kernels.cut_ad` primitive decomposition, so
the fused op stays differentiable to arbitrary order) live in
`kernels.ops.fused_cut_round`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cut_eval import BLOCK_D, P_PAD, _clamp_block
from repro.kernels.platform import resolve_interpret


def _round_kernel(a_ref, v_ref, g_ref, mask_ref, c_ref, act_ref, s_ref,
                  gam_ref, vnew_ref, cv_ref, snew_ref, gamnew_ref,
                  acc_ref, w_ref, *, eta_z, eta_s, eta_dual, rho2):
    ph = pl.program_id(0)
    j = pl.program_id(1)
    nd = pl.num_programs(1)

    @pl.when((ph == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)              # (P_pad, block_d)

    @pl.when(ph == 0)
    def _mv_pass():
        v = v_ref[...].astype(jnp.float32)          # (1, block_d)
        acc_ref[...] += jnp.sum(a * v, axis=1, keepdims=True)
        # defined content for the not-yet-updated v_new block; phase 1
        # revisits and overwrites it with the real update
        vnew_ref[...] = v

    @pl.when((ph == 0) & (j == nd - 1))
    def _weights():
        act = act_ref[...]
        cv0 = (acc_ref[...] - c_ref[...]) * act
        viol = (cv0 + s_ref[...]) * act
        w_ref[...] = (gam_ref[...] + rho2 * viol) * act
        acc_ref[...] = jnp.zeros_like(acc_ref)      # reuse for phase 1

    @pl.when(ph == 1)
    def _update_pass():
        v = v_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        msk = mask_ref[...].astype(jnp.float32)
        g_cut = jnp.sum(w_ref[...] * a, axis=0, keepdims=True)  # (1, bd)
        v_new = v - eta_z * (g + g_cut * msk)
        vnew_ref[...] = v_new
        acc_ref[...] += jnp.sum(a * v_new, axis=1, keepdims=True)

    @pl.when((ph == 1) & (j == nd - 1))
    def _epilogue():
        act = act_ref[...]
        s = s_ref[...]
        gam = gam_ref[...]
        cv1 = (acc_ref[...] - c_ref[...]) * act
        g_s = (gam + rho2 * (cv1 + s)) * act
        s_new = jnp.maximum(0.0, s - eta_s * g_s) * act
        gam_new = jnp.maximum(0.0, gam + eta_dual * (cv1 + s_new)) * act
        cv_ref[...] = cv1
        snew_ref[...] = s_new
        gamnew_ref[...] = gam_new


def fused_cut_round(a, v, g_other, mask, c, active, s, gamma, *,
                    eta_z: float, eta_s: float, eta_dual: float,
                    rho2: float, block_d: int = BLOCK_D,
                    interpret: Optional[bool] = None):
    """One fused level-2 cut round.

    a: (P, D) cut matrix, v: (D,) flattened point at the OLD z2,
    g_other: (D,) non-cut master gradient (zeros off the z2 columns),
    mask: (D,) {0,1} z2-column selector, c/active/s/gamma: (P,) rows.
    Returns (v_new (D,), cutval_new (P,), s_new (P,), gamma_new (P,)),
    all f32."""
    p, d = a.shape
    p_pad = ((p + P_PAD - 1) // P_PAD) * P_PAD
    block_d = _clamp_block(d, block_d)
    d_pad = ((d + block_d - 1) // block_d) * block_d

    a_p = jnp.zeros((p_pad, d_pad), a.dtype).at[:p, :d].set(a)

    def row(x):
        return jnp.zeros((1, d_pad), jnp.float32).at[0, :d].set(
            x.astype(jnp.float32))

    def col(x):
        return jnp.zeros((p_pad, 1), jnp.float32).at[:p, 0].set(
            x.astype(jnp.float32))

    kernel = functools.partial(_round_kernel, eta_z=eta_z, eta_s=eta_s,
                               eta_dual=eta_dual, rho2=rho2)
    wide = pl.BlockSpec((1, block_d), lambda ph, j: (0, j))
    small = pl.BlockSpec((p_pad, 1), lambda ph, j: (0, 0))
    v_new, cv, s_new, gam_new = pl.pallas_call(
        kernel,
        grid=(2, d_pad // block_d),
        in_specs=[
            pl.BlockSpec((p_pad, block_d), lambda ph, j: (0, j)),
            wide, wide, wide, small, small, small, small,
        ],
        out_specs=[wide, small, small, small],
        out_shape=[
            jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((p_pad, 1), jnp.float32),    # mv accumulator
            pltpu.VMEM((p_pad, 1), jnp.float32),    # phase-0 weights
        ],
        interpret=resolve_interpret(interpret),
    )(a_p, row(v), row(g_other), row(mask), col(c), col(active), col(s),
      col(gamma))
    return v_new[0, :d], cv[:p, 0], s_new[:p, 0], gam_new[:p, 0]
