"""Compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed with jaxlib, so a program can be lowered
and compiled for a v5e that is only described: what Mosaic or XLA would
refuse on the chip (an unaligned tile, too much VMEM, a program larger
than HBM) fails here.  Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so a module-level description would
make pytest-xdist workers collect different tests.  Keep every such
compile in this one file.  The persistent compilation cache is off
around these compiles: their entries could not be read back without a
chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

P, D = 8, 1 << 18            # the paper-scale sketched cut space
HBM_BYTES = 16 * 2 ** 30     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(fn, abstract args) for one cut kernel at (P, D), Mosaic only."""
    from repro.kernels.cut_eval import matvec, rank1, vecmat
    from repro.kernels.inner_round import fused_cut_round

    row, col, mat = _spec((D,), sh), _spec((P,), sh), _spec((P, D), sh)
    if name == "matvec":
        return (lambda a, v: matvec(a, v, interpret=False),
                (mat, row))
    if name == "vecmat":
        return (lambda g, a: vecmat(g, a, interpret=False),
                (col, mat))
    if name == "rank1":
        return (lambda x, y: rank1(x, y, interpret=False),
                (col, row))
    return (lambda *xs: fused_cut_round(
        *xs, eta_z=0.05, eta_s=0.05, eta_dual=0.05, rho2=1.0,
        interpret=False),
        (mat, row, row, row, col, col, col, col))


@pytest.mark.parametrize("name", ["matvec", "vecmat", "rank1",
                                  "fused_cut_round"])
def test_cut_kernel_compiles_with_mosaic(name, one_chip, no_compile_cache):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cut_eval_grad_of_grad_compiles_with_mosaic(one_chip,
                                                    no_compile_cache):
    from repro.kernels import ops

    def loss(a, v, c, act):
        cv = ops.cut_eval(a, v, c, act, impl="pallas", interpret=False)
        return 0.5 * jnp.sum(cv ** 2)

    def gog(a, v, c, act):
        inner = lambda v: jnp.sum(  # noqa: E731
            jax.grad(loss, argnums=1)(a, v, c, act) ** 2)
        return jax.grad(inner)(v)

    args = (_spec((P, D), one_chip), _spec((D,), one_chip),
            _spec((P,), one_chip), _spec((P,), one_chip))
    text = jax.jit(gog).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_full_width_llm_step_fits_one_chip(one_chip, no_compile_cache):
    """The xlstm-125m AFTO step at the trainer's defaults (N=4 workers,
    batch 2, seq 129, sketch r=256) fits one chip's HBM.  A worker-
    vmapped count sketch once laid the worker axis out minor and asked
    for 19.8 GB."""
    from repro.configs import get_config
    from repro.fed.trilevel_llm import (FedHyper, afto_llm_step,
                                        init_fed_state)

    cfg = get_config("xlstm-125m")
    n, b, s = 4, 2, 129
    hyper = FedHyper(n_workers=n, cut_mode="sketch", sketch_r=256,
                     p_max=2, k_inner=1, remat=False)
    state = jax.eval_shape(
        lambda k: init_fed_state(cfg, hyper, k, b, s - 1),
        jax.random.PRNGKey(0))
    state = jax.tree.map(lambda x: _spec(x.shape, one_chip, x.dtype), state)
    toks = _spec((n, b, s), one_chip, jnp.int32)
    batch = {"tokens": toks, "val_tokens": toks}
    mask = _spec((n,), one_chip)
    compiled = jax.jit(
        lambda st, bt, m: afto_llm_step(cfg, hyper, st, bt, m)
    ).lower(state, batch, mask).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"
