"""Federated LLM trilevel step + sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.data.synthetic import make_token_stream
from repro.fed import (FedHyper, afto_llm_step, cut_refresh_llm,
                       init_fed_state, param_specs)
from repro.models import init_params
from repro.utils.tree import tree_any_nan

N, B, S = 4, 2, 32


def _setup(cut_mode="sketch"):
    cfg = reduced(get_config("llama3-8b"))
    hyper = FedHyper(n_workers=N, cut_mode=cut_mode, sketch_r=128,
                     p_max=2, k_inner=1, remat=False)
    state = init_fed_state(cfg, hyper, jax.random.PRNGKey(0), B, S)
    toks = jnp.asarray(make_token_stream(cfg.vocab_size, N * B, S + 1)
                       ).reshape(N, B, S + 1)
    batch = {"tokens": toks, "val_tokens": toks}
    return cfg, hyper, state, batch


@pytest.mark.parametrize("cut_mode", ["sketch", "exact"])
def test_afto_llm_step_and_refresh(cut_mode):
    cfg, hyper, state, batch = _setup(cut_mode)
    active = jnp.ones((N,), jnp.float32)
    state = afto_llm_step(cfg, hyper, state, batch, active)
    state = cut_refresh_llm(cfg, hyper, state, batch)
    state = afto_llm_step(cfg, hyper, state, batch, active)
    assert float(jnp.sum(state.cuts.active)) >= 1
    assert float(jnp.sum(state.cuts_i.active)) >= 1
    assert not bool(tree_any_nan(state.X3))
    assert not bool(tree_any_nan(state.z3))
    assert int(state.t) == 2


def test_inactive_workers_frozen():
    cfg, hyper, state, batch = _setup()
    active = jnp.array([1.0, 0.0, 0.0, 1.0])
    new = afto_llm_step(cfg, hyper, state, batch, active)
    for leaf0, leaf1 in zip(jax.tree.leaves(state.X3),
                            jax.tree.leaves(new.X3)):
        # inactive worker rows unchanged
        np.testing.assert_array_equal(np.asarray(leaf0[1]),
                                      np.asarray(leaf1[1]))
        np.testing.assert_array_equal(np.asarray(leaf0[2]),
                                      np.asarray(leaf1[2]))


def test_param_specs_rules():
    mesh = AbstractMesh((4, 4), ("data", "model"))
    cfg = reduced(get_config("mixtral-8x22b"))
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = param_specs(params, mesh)
    flat = {jax.tree_util.keystr(k): v for k, v
            in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    # embedding sharded over vocab
    assert flat["['embed']"] == P("model", None)
    # attention wq: (R, d, H, hd) -> heads over model
    wq_keys = [k for k in flat if "wq" in k]
    assert all(flat[k] == P(None, None, "model", None) for k in wq_keys)
    # MoE experts over model: (R, E, d, f)
    moe_wi = [k for k in flat if "'moe'" in k and "'wi'" in k]
    assert moe_wi and all(flat[k] == P(None, "model", None, None)
                          for k in moe_wi)


def test_param_specs_divisibility_fallback():
    mesh = AbstractMesh((2, 16), ("data", "model"))
    cfg = reduced(get_config("xlstm-125m"))  # 4 heads < 16-way model axis
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = param_specs(params, mesh)
    for path, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]:
        key = jax.tree_util.keystr(path)
        if "wq" in key:   # (R, d=256, H=4, hd) — H not divisible by 16
            assert spec == P(None, None, None, None), (key, spec)


def test_worker_stack_axis():
    mesh = AbstractMesh((4, 4), ("data", "model"))
    cfg = reduced(get_config("llama3-8b"))
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((4,) + x.shape, x.dtype), params)
    specs = param_specs(stacked, mesh, stack_axes=("data",))
    for path, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]:
        assert spec[0] == "data", (jax.tree_util.keystr(path), spec)


def test_sketch_vs_exact_cut_agreement():
    """Sketched cut values approximate exact ones (same trajectory seed).

    This is the fidelity check for the beyond-paper sketched mu-cuts."""
    from repro.fed.trilevel_llm import eval_llm_cuts
    cfg, hyper_s, state_s, batch = _setup("sketch")
    _, hyper_e, state_e, _ = _setup("exact")
    active = jnp.ones((N,), jnp.float32)
    for st, hy in ((state_s, hyper_s), (state_e, hyper_e)):
        pass
    state_s = cut_refresh_llm(cfg, hyper_s, state_s, batch)
    state_e = cut_refresh_llm(cfg, hyper_e, state_e, batch)
    val_s = eval_llm_cuts(hyper_s, state_s.cuts, state_s.z1, state_s.z2,
                          state_s.z3, state_s.X2, state_s.X3,
                          hyper_s.seed_ii)
    val_e = eval_llm_cuts(hyper_e, state_e.cuts, state_e.z1, state_e.z2,
                          state_e.z3, state_e.X2, state_e.X3,
                          hyper_e.seed_ii)
    # identical states at t=0 -> the *active* slot values should be close
    # in relative terms (JL distortion of the sketch)
    a_s = float(val_s[np.argmax(np.asarray(state_s.cuts.active))])
    a_e = float(val_e[np.argmax(np.asarray(state_e.cuts.active))])
    assert np.isfinite(a_s) and np.isfinite(a_e)
    if abs(a_e) > 1e-3:
        assert abs(a_s - a_e) / abs(a_e) < 0.5


def test_fed_state_checkpoint_roundtrip(tmp_path):
    """Production resume path: the full FedLLMState (params, duals, cut
    sets, counters) roundtrips through the checkpoint layer."""
    import numpy as np
    from repro.checkpoint import load_checkpoint, save_checkpoint

    cfg, hyper, state, batch = _setup("sketch")
    state = afto_llm_step(cfg, hyper, state, batch,
                          jnp.ones((N,), jnp.float32))
    save_checkpoint(str(tmp_path / "fed"), state, step=1)
    restored = load_checkpoint(str(tmp_path / "fed"), state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert int(restored.t) == 1


def _worker_stack(tree, n):
    """n distinct per-worker copies of `tree`, stacked on a leading axis."""
    return jax.tree.map(
        lambda x: jnp.stack([x * (1.0 + 0.5 * j) + 0.01 * j
                             for j in range(n)]), tree)


def test_sketch_stacked_equals_per_worker_sketch():
    from repro.fed.sketch import sketch, sketch_stacked

    cfg = reduced(get_config("xlstm-125m"))
    X3 = _worker_stack(init_params(cfg, jax.random.PRNGKey(0)), N)
    one = jax.jit(lambda t: sketch(t, 2, 64))
    got = np.asarray(jax.jit(lambda t: sketch_stacked(t, 2, 64))(X3))
    want = np.stack([np.asarray(one(jax.tree.map(lambda x: x[j], X3)))
                     for j in range(N)])
    # the same compiled per-worker sum: bitwise equal
    np.testing.assert_array_equal(got, want)
    # the worker-vmapped form it replaces sums in another order
    vmapped = np.asarray(jax.vmap(lambda x: sketch(x, 2, 64))(X3))
    np.testing.assert_allclose(got, vmapped, rtol=0,
                               atol=1e-5 * np.max(np.abs(want)))


def test_unsketch_stacked_equals_per_worker_unsketch():
    from repro.fed.sketch import unsketch, unsketch_stacked

    cfg = reduced(get_config("xlstm-125m"))
    template = init_params(cfg, jax.random.PRNGKey(0))
    coeff = jax.random.normal(jax.random.PRNGKey(1), (N, 64))
    got = unsketch_stacked(template, coeff, 2)
    for j in range(N):
        want = unsketch(template, coeff[j], 2)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g[j]), np.asarray(w))


def test_stacked_sketch_sharded_over_workers_matches_one_device():
    """Sharded over a worker mesh, the worker-major scatter and gather
    give the single-device values: each device handles its own rows."""
    from jax.sharding import NamedSharding

    from repro.fed.sketch import sketch_stacked, unsketch_stacked
    from repro.launch.mesh import make_worker_mesh

    cfg = reduced(get_config("xlstm-125m"))
    template = init_params(cfg, jax.random.PRNGKey(0))
    X3 = _worker_stack(template, N)
    mesh = make_worker_mesh(N, axis_name="data")
    by_worker = NamedSharding(mesh, P("data"))
    sk = jax.jit(lambda t: sketch_stacked(t, 2, 64))
    want = np.asarray(sk(X3))
    got = np.asarray(sk(jax.device_put(X3, by_worker)))
    # each bucket sums ~10^4 entries, in another order on the mesh
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.max(np.abs(want)))

    un = jax.jit(lambda c: unsketch_stacked(template, c, 2),
                 out_shardings=by_worker)
    coeff = jax.random.normal(jax.random.PRNGKey(1), (N, 64))
    for g, w in zip(jax.tree.leaves(un(jax.device_put(coeff, by_worker))),
                    jax.tree.leaves(unsketch_stacked(template, coeff, 2))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
