"""Differentiation-closed bilinear primitives over the cut matrix.

The three contractions the cut path ever needs —

    mv(a, v)    = A @ v            (P,)   forward cut values
    vm(g, a)    = g^T A            (D,)   row-reduction (the dv backward)
    outer(x, y) = x y^T            (P, D) rank-1 update (the da backward)

— are registered as first-class JAX primitives whose JVP, transpose and
batching rules are expressed in terms of EACH OTHER:

    jvp  mv    : (da, dv) -> mv(da, v) + mv(a, dv)
    T{mv}      : ct -> da = outer(ct, v),  dv = vm(ct, a)
    T{vm}      : ct -> dg = mv(a, ct),     da = outer(g, ct)
    T{outer}   : ct -> dx = mv(ct, y),     dy = vm(x, ct)

The set is closed under linearization AND transposition, so reverse
mode — and reverse-over-reverse, the Eq. 23/24 cut-refresh grad-of-grad
through the inner-ADMM rollouts — stays on the hand-written Pallas
kernels to arbitrary order; no differentiated path needs the
``impl="ref"`` fallback anymore.  (The obvious alternative, a
``custom_jvp``-over-``custom_vjp`` composition, fails in reverse mode on
this jax: the custom_vjp calls appearing in the tangent computation have
no transpose rule, so ``jax.grad`` of anything containing the JVP dies
with ``Transpose rule ... for 'custom_vjp_call_jaxpr' not
implemented``.)

Each primitive lowers through `mlir.lower_fun` to its kernel wrapper in
`kernels.cut_eval` (interpret mode off-TPU for bit-accurate testing,
Mosaic on a real TPU backend); ``block_d`` / ``interpret`` ride along as
static bind params so every rule's recursive binds inherit the caller's
tiling.  Batching (the sweep engine's run axis) vmaps the kernel
natively via `jax.vmap` of the impl.  All three primitives emit f32 (the
kernels accumulate in f32 regardless of input dtype); transpose rules
cast cotangents back to the primal dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.extend as jex
import jax.numpy as jnp
from jax.interpreters import ad, batching, mlir

from repro.kernels import cut_eval as _kern
from repro.kernels.platform import resolve_interpret


# --- kernel-backed impls (also the lowering + batching bodies) -------------

def _mv_impl(a, v, *, block_d, interpret):
    return _kern.matvec(a, v, block_d=block_d, interpret=interpret)


def _vm_impl(g, a, *, block_d, interpret):
    return _kern.vecmat(g, a, block_d=block_d, interpret=interpret)


def _outer_impl(x, y, *, block_d, interpret):
    return _kern.rank1(x, y, block_d=block_d, interpret=interpret)


def _register(name, impl, abstract_eval):
    p = jex.core.Primitive(name)
    p.def_impl(functools.partial(_eager, impl))
    p.def_abstract_eval(abstract_eval)
    mlir.register_lowering(p, mlir.lower_fun(impl, multiple_results=False))

    def batch_rule(args, dims, **kw):
        x, y = args
        out = jax.vmap(functools.partial(impl, **kw), in_axes=dims)(x, y)
        return out, 0

    batching.primitive_batchers[p] = batch_rule
    return p


def _eager(impl, *args, **kw):
    return impl(*args, **kw)


def _f32(shape):
    return jax.core.ShapedArray(shape, jnp.float32)


mv_p = _register("cut_mv", _mv_impl,
                 lambda a, v, **kw: _f32((a.shape[0],)))
vm_p = _register("cut_vm", _vm_impl,
                 lambda g, a, **kw: _f32((a.shape[1],)))
outer_p = _register("cut_outer", _outer_impl,
                    lambda x, y, **kw: _f32((x.shape[0], y.shape[0])))


# --- JVPs: bilinear, each rule recurses into the same primitive ------------

ad.defjvp(mv_p,
          lambda da, a, v, **kw: mv_p.bind(da, v, **kw),
          lambda dv, a, v, **kw: mv_p.bind(a, dv, **kw))
ad.defjvp(vm_p,
          lambda dg, g, a, **kw: vm_p.bind(dg, a, **kw),
          lambda da, g, a, **kw: vm_p.bind(g, da, **kw))
ad.defjvp(outer_p,
          lambda dx, x, y, **kw: outer_p.bind(dx, y, **kw),
          lambda dy, x, y, **kw: outer_p.bind(x, dy, **kw))


# --- transposes: the closure property ---------------------------------------

def _cast_like(ct, primal):
    dtype = primal.aval.dtype if ad.is_undefined_primal(primal) else None
    return ct if dtype is None or ct.dtype == dtype else ct.astype(dtype)


def _mv_transpose(ct, a, v, **kw):
    ct = ad.instantiate_zeros(ct)
    if ad.is_undefined_primal(a):
        return _cast_like(outer_p.bind(ct, v, **kw), a), None
    return None, _cast_like(vm_p.bind(ct, a, **kw), v)


def _vm_transpose(ct, g, a, **kw):
    ct = ad.instantiate_zeros(ct)
    if ad.is_undefined_primal(g):
        return _cast_like(mv_p.bind(a, ct, **kw), g), None
    return None, _cast_like(outer_p.bind(g, ct, **kw), a)


def _outer_transpose(ct, x, y, **kw):
    ct = ad.instantiate_zeros(ct)
    if ad.is_undefined_primal(x):
        return _cast_like(mv_p.bind(ct, y, **kw), x), None
    return None, _cast_like(vm_p.bind(x, ct, **kw), y)


ad.primitive_transposes[mv_p] = _mv_transpose
ad.primitive_transposes[vm_p] = _vm_transpose
ad.primitive_transposes[outer_p] = _outer_transpose


# --- public entry points ----------------------------------------------------

def matvec(a, v, *, block_d: int = None, interpret: Optional[bool] = None):
    """(P,) = A @ v through the kernel, differentiable to any order."""
    block_d = _kern.BLOCK_D if block_d is None else block_d
    return mv_p.bind(a, v, block_d=block_d,
                     interpret=resolve_interpret(interpret))


def vecmat(g, a, *, block_d: int = None, interpret: Optional[bool] = None):
    """(D,) = g^T A through the kernel, differentiable to any order."""
    block_d = _kern.BLOCK_D if block_d is None else block_d
    return vm_p.bind(g, a, block_d=block_d,
                     interpret=resolve_interpret(interpret))


def outer(x, y, *, block_d: int = None, interpret: Optional[bool] = None):
    """(P, D) = x y^T through the kernel, differentiable to any order."""
    block_d = _kern.BLOCK_D if block_d is None else block_d
    return outer_p.bind(x, y, block_d=block_d,
                        interpret=resolve_interpret(interpret))
