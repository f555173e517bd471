"""Pallas TPU kernels: hyper-polyhedral cut contractions (fwd + bwd).

The paper's per-iteration hot spot (Eqs. 14, 20) is the wide contraction
of the canonical (P, D) cut matrix against a flattened variable point.
On TPU the variable dimension D is huge (the sketched cut space, or a
flattened paper-scale variable block), so every kernel here streams D in
VMEM-resident tiles along a sequential grid axis; P is padded to the
8-sublane boundary and partials accumulate in f32.

Three kernels cover the whole AD closure of the cut path (see
`kernels.cut_ad` for the primitive registrations that wire them into
jvp/transpose rules):

  matvec(a, v)  = A @ v      (P,)    the forward cut contraction
  vecmat(g, a)  = g^T A      (D,)    the row-reduction backward (dv)
  rank1(x, y)   = x y^T      (P, D)  the rank-1 backward (da)

`cut_eval` composes matvec with the tiny (P,)-sized epilogue
`(A v - c) * active` (jnp — O(P) work, fused by XLA around the kernel).

TPU adaptation (vs a GPU cutting-plane loop): one grid step's tile
(P_pad x block_d) is shaped for the MXU's (8x128) lanes — the row count
of cuts is tiny, so each kernel is deliberately a wide streaming op that
lives in VMEM, not an HBM-bound gather.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

P_PAD = 8          # sublane alignment for the cut axis
BLOCK_D = 2048     # lane-dim tile (multiple of 128)


def _clamp_block(d: int, block_d: int) -> int:
    # never tile wider than the (128-aligned) variable space itself —
    # quickstart-scale D would otherwise zero-pad to a full 2048 lane
    # tile and waste the whole MXU row on padding.
    return min(block_d, max(128, ((d + 127) // 128) * 128))


def _pad_mat(a, p_pad: int, d_pad: int):
    p, d = a.shape
    return jnp.zeros((p_pad, d_pad), a.dtype).at[:p, :d].set(a)


def _pad_row(v, d_pad: int):
    return jnp.zeros((1, d_pad), v.dtype).at[0, :v.shape[0]].set(v)


def _pad_col(x, p_pad: int):
    return jnp.zeros((p_pad, 1), x.dtype).at[:x.shape[0], 0].set(x)


def _scoped(call, *args):
    """`call(*args)`, the Mosaic call alone, in the name scope
    `cut_kernel` that a profile is read by.  XLA names a custom call
    after its innermost scope, so `cut_eval` sits inside it: every cut
    kernel call is `cut_eval.N` in the compiled program, under `vmap`,
    JVP and transpose alike."""
    with jax.named_scope("cut_kernel"), jax.named_scope("cut_eval"):
        return call(*args)


# ---------------------------------------------------------------------------
# forward: matvec  (P,) = A @ v
# ---------------------------------------------------------------------------

def _matvec_kernel(a_ref, v_ref, out_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.float32)          # (P_pad, block_d)
    v = v_ref[...].astype(jnp.float32)          # (1, block_d)
    out_ref[...] += jnp.sum(a * v, axis=1, keepdims=True)  # (P_pad, 1)


def matvec(a, v, *, block_d: int = BLOCK_D, interpret: Optional[bool] = None):
    """a: (P, D), v: (D,) -> (P,) f32 raw contraction A @ v."""
    p, d = a.shape
    p_pad = ((p + P_PAD - 1) // P_PAD) * P_PAD
    block_d = _clamp_block(d, block_d)
    d_pad = ((d + block_d - 1) // block_d) * block_d
    out = _scoped(pl.pallas_call(
        _matvec_kernel,
        grid=(d_pad // block_d,),
        in_specs=[
            pl.BlockSpec((p_pad, block_d), lambda j: (0, j)),
            pl.BlockSpec((1, block_d), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((p_pad, 1), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p_pad, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    ), _pad_mat(a, p_pad, d_pad), _pad_row(v, d_pad))
    return out[:p, 0]


# ---------------------------------------------------------------------------
# backward (dv): vecmat  (D,) = g^T A — row-reduction over the cut axis
# ---------------------------------------------------------------------------

def _vecmat_kernel(g_ref, a_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)          # (P_pad, 1)
    a = a_ref[...].astype(jnp.float32)          # (P_pad, block_d)
    out_ref[...] = jnp.sum(g * a, axis=0, keepdims=True)   # (1, block_d)


def vecmat(g, a, *, block_d: int = BLOCK_D, interpret: Optional[bool] = None):
    """g: (P,), a: (P, D) -> (D,) f32 row-reduction g^T A.

    Each D tile is independent (the reduction runs over the resident P
    rows), so the grid has no sequential accumulator."""
    p, d = a.shape
    p_pad = ((p + P_PAD - 1) // P_PAD) * P_PAD
    block_d = _clamp_block(d, block_d)
    d_pad = ((d + block_d - 1) // block_d) * block_d
    out = _scoped(pl.pallas_call(
        _vecmat_kernel,
        grid=(d_pad // block_d,),
        in_specs=[
            pl.BlockSpec((p_pad, 1), lambda j: (0, 0)),
            pl.BlockSpec((p_pad, block_d), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    ), _pad_col(g, p_pad), _pad_mat(a, p_pad, d_pad))
    return out[0, :d]


# ---------------------------------------------------------------------------
# backward (da): rank1  (P, D) = x y^T — the outer-product update
# ---------------------------------------------------------------------------

def _rank1_kernel(x_ref, y_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)          # (P_pad, 1)
    y = y_ref[...].astype(jnp.float32)          # (1, block_d)
    out_ref[...] = x * y                        # (P_pad, block_d)


def rank1(x, y, *, block_d: int = BLOCK_D, interpret: Optional[bool] = None):
    """x: (P,), y: (D,) -> (P, D) f32 rank-1 outer product x y^T."""
    p, d = x.shape[0], y.shape[0]
    p_pad = ((p + P_PAD - 1) // P_PAD) * P_PAD
    block_d = _clamp_block(d, block_d)
    d_pad = ((d + block_d - 1) // block_d) * block_d
    out = _scoped(pl.pallas_call(
        _rank1_kernel,
        grid=(d_pad // block_d,),
        in_specs=[
            pl.BlockSpec((p_pad, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, block_d), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((p_pad, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((p_pad, d_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    ), _pad_col(x, p_pad), _pad_row(y, d_pad))
    return out[:p, :d]


def cut_eval(a, v, c, active, *, block_d: int = BLOCK_D,
             interpret: Optional[bool] = None):
    """a: (P, D), v: (D,), c: (P,), active: (P,) -> (P,) cut values.

    One streaming `matvec` kernel launch plus the O(P) jnp epilogue
    (identical math to the previously fused single-kernel form)."""
    return (matvec(a, v, block_d=block_d, interpret=interpret) - c) * active
